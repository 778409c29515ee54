"""Share of the traced window in which no operation ran on the chip."""
from bench.harness import trace


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr.devices():
        return None
    lo, hi = tr.window()
    busy = trace.length(trace.busy(tr, tr.devices()[0], lo, hi))
    return 100.0 * (1.0 - busy / (hi - lo))
