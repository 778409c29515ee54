"""Median time to first token over the requests due in the window (the
end-to-end definition, read in the traced run): above the knee the queue
grows all through the window, so this swings with the smallest change and
is a per-layer reading, not a gated metric."""


def read(rec):
    return rec.get("e2e", {}).get("ttft_p50_ms")
