"""One run of one cell: parse the command line, check the chip, hand the
cell to its driver, print the result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared for ``correct``
with its limit.  The same numbers are the last lines of standard error.
Without a TPU, with fewer chips than the cell asks for, or without the
program's ``src/`` beside the benchmark, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Callable, Dict, Optional

from . import registry as registry_mod


def process_start() -> float:
    """``time.monotonic()`` at which this process started (Linux), else
    now: set-up is counted from there."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


T_START = process_start()


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after its window and its check."""
    attempted: int
    failed: int
    e2e: Dict[str, float]                  # end-to-end metric -> value
    records: dict                          # what per-layer readers read
    checks: Dict[str, tuple]               # name -> (value, limit)
    memory_peak_bytes: int
    trace: Optional[dict] = None           # trace.reduce(...) of the window

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())


@dataclasses.dataclass
class Session:
    """Everything a driver gets: the cell, its pieces, and the run's
    arguments and instruments."""
    name: str
    workload: dict
    config: dict
    family: object                         # families/<config's family>.py
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    watch: object
    registry: registry_mod.Registry
    t_start: float = T_START
    t_window: Optional[float] = None

    def window_opens(self) -> None:
        """Called by the driver at its first timed step: set-up ends."""
        self.t_window = time.monotonic()

    @property
    def arch(self):
        """The program's ``ArchConfig`` at the configuration's widths, as
        its family builds it."""
        return self.family.arch_config(self.config)

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_start

    @property
    def peaks(self):
        """The chip's published peaks; None off the chip (tests)."""
        from .peaks import PEAKS
        return PEAKS.get(self.devices[0].device_kind)

    def scratch(self, name: str) -> str:
        """A directory for the run's files under TMPDIR."""
        import tempfile
        return os.path.join(tempfile.gettempdir(), f"bench-{name}")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(n: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    from .peaks import peaks
    peaks(devices[0].device_kind)          # an unknown chip is an error
    return devices[:n]


def open_session(args, root: str, *, chips: Callable = find_chips,
                 bench: str = registry_mod.BENCH) -> Session:
    reg = registry_mod.Registry(root, bench)
    wl = reg.workload(args.workload)
    cfg = reg.config(wl["config"])
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise NoChip(f"no program source at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    devices = chips(int(wl["chips"]))
    from .compile_watch import CompileWatch
    return Session(name=args.workload, workload=wl, config=cfg,
                   family=reg.family(cfg["family"]),
                   traffic=reg.traffic(wl["traffic"]), seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   devices=devices, watch=CompileWatch(), registry=reg)


def result(sess: Session, out: Outcome) -> dict:
    reg = sess.registry
    if sess.trace:
        metrics = {}
        for m in reg.per_layer(sess.name):
            v = reg.reader(m["name"]).read(out.records)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = dict(out.e2e, setup_s=sess.setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in reg.end_to_end(sess.name)}
    dev = sess.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(sess.devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    doc = {"correct": out.correct, "attempted": out.attempted,
           "failed": out.failed, "metrics": metrics, "device": device}
    if sess.trace and out.trace is not None:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        doc["breakdown"] = {"device_ops": out.trace["device_ops"],
                            "idle_gaps": out.trace["idle_gaps"]}
    doc["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in out.checks.items()}
    return doc


def peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(argv, root: str, **kw) -> dict:
    args = parse(argv)
    sess = open_session(args, root, **kw)
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    log(f"cell {sess.name}: {len(sess.devices)} x "
        f"{sess.devices[0].device_kind}, seed {sess.seed}, "
        f"{sess.seconds:g} s, trace {int(sess.trace)}, compile cache {cache}")
    drv = sess.registry.driver(sess.workload["driver"])
    out = drv.run(sess)
    doc = result(sess, out)
    for k, (v, lim) in out.checks.items():
        log(f"check {k}: {v!r} (limit {lim!r}) "
            f"{'ok' if v <= lim else 'FAILED'}")
    return doc


def main(argv, root: str) -> int:
    try:
        doc = run(argv, root)
    except NoChip as e:
        log(f"not run: {e}")
        return 2
    print(json.dumps(doc), flush=True)
    return 0
