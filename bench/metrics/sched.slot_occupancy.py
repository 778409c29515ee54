"""Share of decode slot-steps that produced a token in the window: tokens
decoded over (decode steps x slots per decode PE x decode PEs), from the
scheduler's counters."""


def read(rec):
    s, fl = rec.get("sched", {}), rec.get("fleet", {})
    steps = s.get("decode_steps", 0)
    if not steps:
        return None
    slots = fl["num_slots"] * fl["decode_per_pod"] * fl.get("n_pods", 1)
    return 100.0 * s["decode_tokens"] / (steps * slots)
