"""Find every piece of the benchmark by its name.

``BENCHMARK.json`` lists cells and metrics.  A cell's own file
(``workloads/<cell>.json``) names its configuration, its traffic mix and its
driver kind; each of those is a file of its own (``configs/<name>.json``,
``traffic/<name>.json``, ``drivers/<kind>.py``).  A configuration names its
model family (``"family"``), a module ``families/<family>.py`` that holds
what depends on the architecture (``FAMILY``), and each per-layer metric is
a reader ``metrics/<name>.py``.  Adding a cell, a configuration, a traffic
mix, a model family, a driver kind or a metric is adding files: nothing here
lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

#: what a family module exposes: the program's ``ArchConfig`` at the
#: configuration's widths, the plain reference
#: (``Reference(cfg, seed, *, rows)`` with ``.gaps`` and ``.logits``), the
#: work counted from shapes, and the program's span names the family adds
#: to the traced run's host activities
FAMILY = ("arch_config", "Reference", "prefill_flops", "token_flops",
          "kv_bytes_per_token", "decode_bytes", "SPANS")


class Registry:
    def __init__(self, root: str, bench: str = BENCH):
        self.root = root                 # the checkout: BENCHMARK.json
        self.bench = bench               # the benchmark's own directory
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    # ------------------------------------------------------------- files
    def _json(self, kind: str, name: str) -> dict:
        if not NAME.match(name):
            raise ValueError(f"bad {kind} name {name!r}")
        path = os.path.join(self.bench, kind, f"{name}.json")
        with open(path) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        cell = next((w for w in self.spec["workloads"] if w["name"] == name),
                    None)
        if cell is None:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json")
        doc = self._json("workloads", name)
        for key in ("config", "traffic", "chips"):
            if doc[key] != cell[key]:
                raise ValueError(f"{name}: {key} {doc[key]!r} in its file, "
                                 f"{cell[key]!r} in BENCHMARK.json")
        return doc

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def _module(self, kind: str, name: str):
        if not NAME.match(name):
            raise ValueError(f"bad {kind} name {name!r}")
        path = os.path.join(self.bench, kind, f"{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def driver(self, kind: str):
        return self._module("drivers", kind)

    def reader(self, metric: str):
        return self._module("metrics", metric)

    def family(self, name: str):
        """The model family module ``families/<name>.py``, which has to
        expose every name of ``FAMILY``."""
        mod = self._module("families", name)
        missing = [k for k in FAMILY if not hasattr(mod, k)]
        if missing:
            raise AttributeError(f"{mod.__file__} lacks {', '.join(missing)}")
        return mod

    # ----------------------------------------------------------- metrics
    def end_to_end(self, cell: str) -> list:
        """The cell's end-to-end metrics, ``setup_s`` among them."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        """Per-layer metrics read in this cell: those listing it, and those
        without a list whose end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]
