"""Heap stores per decode step: the scheduler's ``heap_stores`` over its
``decode_steps`` in the window.  Each store rebuilds a whole pool array, so
with ``heap.store_mb_per_decode_step`` this tells fewer stores from cheaper
ones."""


def read(rec):
    s = rec.get("sched", {})
    steps = s.get("decode_steps", 0)
    if "heap_stores" not in s or not steps:
        return None
    return s["heap_stores"] / steps
