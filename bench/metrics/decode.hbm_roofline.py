"""The decode program's share of its HBM roofline.  Least time: the weights
plus the KV of the active contexts (``serve_decode``'s bytes) at the HBM
peak, summed over decode calls, in the bytes the records' model family
counts.  Device time: in each ``paged_attn`` span (the model call), the
longest device program that overlaps it."""


def read(rec):
    if rec.get("peaks") is None:
        return None
    tr = rec.get("trace")
    dec = [s for s in rec.get("prof", []) if s.op == "serve_decode"]
    if tr is None or not dec:
        return None
    lo, hi = tr.window()
    spans = [(s, e) for s, e in tr.host_spans("paged_attn")
             if s >= lo and e <= hi]
    if len(spans) != len(dec):
        return None
    t_dev = 0
    for dev in tr.devices():
        mods = tr.planes[dev].get("XLA Modules", [])
        for s, e in spans:
            over = [d for _, ms, d in mods if ms < e and ms + d > s]
            t_dev += max(over, default=0)
    if not t_dev:
        return None
    t_dev /= 1e9 * len(tr.devices())
    cfg, fam = rec["cfg"], rec["family"]
    per_token = fam.kv_bytes_per_token(cfg)
    least = sum(fam.decode_bytes(cfg, s.nbytes // per_token)
                for s in dec) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / t_dev
