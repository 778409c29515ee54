"""Median host time of the program's ``serve_prefill`` scope (one request's
prefill, blocked until its first token is ready)."""
import statistics


def read(rec):
    t = [s.wall_s for s in rec.get("prof", []) if s.op == "serve_prefill"]
    return statistics.median(t) * 1e3 if t else None
