"""Median device time of one paged-decode assembly: the durations of the
``jit_assemble_pages(...)`` module on the ``XLA Modules`` line, over the
calls that ran inside the traced window.  A program without that module
reads as no metric."""
import re
import statistics

MODULE = re.compile(r"^jit_assemble_pages\(")


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    lo, hi = tr.window()
    t = [d for dev in tr.devices()
         for n, s, d in tr.planes[dev].get("XLA Modules", [])
         if MODULE.match(n) and s >= lo and s + d <= hi]
    return statistics.median(t) / 1e6 if t else None
