"""Capture the profiler's trace of the measured window and reduce it.

The reduction works on a plain structure, ``{plane: {line: [(name, start_ns,
dur_ns), ...]}}``, so the tests can hold it to a small recorded trace.  Host
spans come from ``jax.profiler.TraceAnnotation`` names that the harness sets
(the window, each fleet step, and the program's wall-clock profiler
scopes, which the traced run mirrors into annotations); device operations
from the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane.  Both are on the
profiler's one clock.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import json
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench_window"


class Trace:
    def __init__(self, planes: dict):
        self.planes = planes

    # --------------------------------------------------------------- load
    @classmethod
    def from_xplane(cls, path: str, host_names) -> "Trace":
        """Device ops and modules of every TPU plane; on the host, only the
        events named in ``host_names``."""
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        planes = {}
        for pl in pd.planes:
            if DEVICE_PLANE.match(pl.name):
                planes[pl.name] = {
                    ln.name: [(e.name, int(e.start_ns), int(e.duration_ns))
                              for e in ln.events]
                    for ln in pl.lines if ln.name in (OPS_LINE, MODULES_LINE)}
            elif pl.name.startswith("/host:"):
                lines = {}
                for ln in pl.lines:
                    evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                           for e in ln.events if e.name in host_names]
                    if evs:
                        lines[ln.name] = evs
                if lines:
                    planes[pl.name] = lines
        return cls(planes)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls({p: {ln: [tuple(e) for e in evs]
                            for ln, evs in lines.items()}
                        for p, lines in json.load(f).items()})

    # ------------------------------------------------------------ queries
    def devices(self) -> list:
        return sorted(p for p in self.planes if DEVICE_PLANE.match(p))

    def ops(self, device: str) -> list:
        return self.planes[device].get(OPS_LINE, [])

    def host_spans(self, name: str) -> list:
        """(start_ns, end_ns) of every host event called ``name``."""
        out = []
        for p, lines in self.planes.items():
            if not p.startswith("/host:"):
                continue
            for evs in lines.values():
                out += [(s, s + d) for n, s, d in evs if n == name]
        return sorted(out)

    def window(self) -> tuple:
        spans = self.host_spans(WINDOW)
        if not spans:
            raise ValueError(f"no {WINDOW!r} span in the trace")
        return spans[0]


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> int:
    """Total length of the intersection of two disjoint sorted lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def busy(trace: Trace, device: str, lo: int, hi: int) -> list:
    """Disjoint intervals in [lo, hi] in which an op ran on ``device``."""
    return clip(union((s, s + d) for _, s, d in trace.ops(device)), lo, hi)


def op_time(trace: Trace, pattern: str, lo: int, hi: int) -> float:
    """Seconds of device ops whose name matches ``pattern`` in [lo, hi],
    the union per device, averaged over devices."""
    rx = re.compile(pattern)
    devs = trace.devices()
    tot = 0
    for dev in devs:
        tot += length(clip(union((s, s + d) for n, s, d in trace.ops(dev)
                                 if rx.search(n)), lo, hi))
    return tot / 1e9 / max(1, len(devs))


def reduce(trace: Trace, activities=()) -> dict:
    """busy_s (averaged over devices), window_s, and the breakdown: the
    device ops that took most time, and idle time by the innermost host
    activity (``activities``, outermost first) in force when it fell."""
    lo, hi = trace.window()
    devs = trace.devices()
    per_dev = {d: busy(trace, d, lo, hi) for d in devs}
    busy_s = sum(length(v) for v in per_dev.values()) / 1e9 / max(1, len(devs))
    ops = collections.Counter()
    for d in devs:
        for n, s, dur in trace.ops(d):
            c = min(s + dur, hi) - max(s, lo)
            if c > 0:
                ops[short(n)] += c
    idle = collections.Counter()
    spans = {a: union(clip(trace.host_spans(a), lo, hi)) for a in activities}
    for d in devs:
        gaps, t = [], lo
        for s, e in per_dev[d]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for g in gaps:
            rest = [g]
            for a in reversed(activities):           # innermost first
                cov = union(clip(spans[a], *g))
                if not cov:
                    continue
                idle[a] += sum(overlap([r], cov) for r in rest)
                rest = _minus(rest, cov)
            idle["no activity"] += length(rest)
    n = max(1, len(devs))
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / 1e9 / n] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v / 1e9 / n] for k, v in idle.most_common(10)
                      if v > 0],
    }


def short(op: str) -> str:
    """An HLO op as the breakdown names it: its instruction name without
    the number and its result type without layouts, e.g.
    ``copy bf16[3,165150720]``."""
    name, _, rest = op.partition(" = ")
    name = re.sub(r"^%|\.\d+$", "", name.strip())
    if not rest:
        return name[:100]
    depth, end = 0, len(rest)
    for i, ch in enumerate(rest):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            end = i
            break
    typ = re.sub(r"\{[^{}]*\}", "", rest[:end])
    typ = re.sub(r"/\*[^*]*\*/", "", typ)
    return f"{name} {typ}"[:100]


def _minus(parts, cover) -> list:
    out = []
    for s, e in parts:
        t = s
        for cs, ce in cover:
            if ce <= t or cs >= e:
                continue
            if cs > t:
                out.append((t, cs))
            t = max(t, ce)
        if t < e:
            out.append((t, e))
    return out


def annotate(name: str, on: bool = True):
    """A ``TraceAnnotation`` of ``name`` when ``on``, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Capture:
    """``with Capture(dir) as cap: ...`` traces the block into ``dir``;
    ``cap.trace(host_names)`` reads it back, then the files are removed."""

    def __init__(self, directory: str):
        self.dir = directory

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def trace(self, host_names) -> Trace:
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no xplane file under {self.dir}")
        try:
            return Trace.from_xplane(found[0], set(host_names) | {WINDOW})
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
