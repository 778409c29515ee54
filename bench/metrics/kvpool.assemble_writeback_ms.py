"""Median over decode calls of ``serve_decode`` minus the ``paged_attn``
scope inside it: assembling the slots' KV from the pool row and writing
each new token back into the heap, around the model call."""
import statistics


def read(rec):
    dec = [s.wall_s for s in rec.get("prof", []) if s.op == "serve_decode"]
    att = [s.wall_s for s in rec.get("prof", []) if s.op == "paged_attn"]
    if not dec or len(dec) != len(att):
        return None
    return statistics.median(d - a for d, a in zip(dec, att)) * 1e3
