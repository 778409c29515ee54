"""Median host time of the ``kv_writeback`` span over decode calls: reading
the slots' positions to the host and storing each new token's KV block
into the heap."""
import statistics


def read(rec):
    t = [s.wall_s for s in rec.get("prof", []) if s.op == "kv_writeback"]
    return statistics.median(t) * 1e3 if t else None
