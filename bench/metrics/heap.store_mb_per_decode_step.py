"""Megabytes of heap pool rebuilt per decode step: the scheduler's
``heap_store_bytes`` over its ``decode_steps`` in the window.  Every store
into the symmetric heap replaces a whole pool array, so this reads what
the stores cost, not the bytes they carry."""


def read(rec):
    s = rec.get("sched", {})
    steps = s.get("decode_steps", 0)
    if "heap_store_bytes" not in s or not steps:
        return None
    return s["heap_store_bytes"] / steps / 1e6
