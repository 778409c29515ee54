"""Median host time of the program's ``serve_decode`` scope: one decode PE's
step over its slot bank, pool assembly and heap writeback included."""
import statistics


def read(rec):
    t = [s.wall_s for s in rec.get("prof", []) if s.op == "serve_decode"]
    return statistics.median(t) * 1e3 if t else None
