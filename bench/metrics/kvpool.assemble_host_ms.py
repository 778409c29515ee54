"""Median host time of the ``kv_assemble`` span over decode calls: reading
the decode PE's pool row and gathering the slots' KV into the dense cache
the model call takes."""
import statistics


def read(rec):
    t = [s.wall_s for s in rec.get("prof", []) if s.op == "kv_assemble"]
    return statistics.median(t) * 1e3 if t else None
