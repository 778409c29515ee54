"""Blocking device-to-host reads on the decode path per decode step: the
scheduler's ``host_reads`` (each slot's token, and the paged writeback's
read of the slot positions) over its ``decode_steps`` in the window."""


def read(rec):
    s = rec.get("sched", {})
    steps = s.get("decode_steps", 0)
    if "host_reads" not in s or not steps:
        return None
    return s["host_reads"] / steps
