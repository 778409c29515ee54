"""Host milliseconds per decode step of the scheduler's own work: the time
under its phase spans (``sched_*``) less the spans of the work it hands the
chip inside them (``serve_prefill``, ``stream_flush``, ``kv_stage``,
``serve_decode``, which never nest in one another), over the scheduler's
``decode_steps`` in the window.  What is left is bookkeeping, admission,
the token reads and eviction."""

PHASES = ("sched_recover", "sched_prefill", "sched_admit", "sched_resume",
          "sched_decode")
WORK = ("serve_prefill", "stream_flush", "kv_stage", "serve_decode")


def read(rec):
    steps = rec.get("sched", {}).get("decode_steps", 0)
    prof = rec.get("prof", [])
    phases = [s.wall_s for s in prof if s.op in PHASES]
    if not phases or not steps:
        return None
    work = sum(s.wall_s for s in prof if s.op in WORK)
    return (sum(phases) - work) / steps * 1e3
