#!/usr/bin/env python3
"""The readings a cell's limits are set from: the program's number and its
control's, over several seeds, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed it runs the cell as ``bench/run.py`` does (set-up, a window
of ``--seconds``, the check) and also puts the cell's control in the
program's place: the plain reference in float8 e4m3 (the nearest
precision below the configuration's bfloat16), scored at the same
positions of the same sampled requests.  One JSON line per seed on standard output:
``{"seed": n, "checks": {name: value, ...}}``.  The benchmark's own runs do
not run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.harness import session
    first = True
    for seed in (int(s) for s in args.seeds.split(",")):
        sess = session.open_session(session.parse(
            ["--workload", args.workload, "--seed", str(seed), "--seconds",
             str(args.seconds)]), ROOT)
        if first:
            from repro.launch import compile_cache
            compile_cache.enable()
            first = False
        out = sess.registry.driver(sess.workload["driver"]).run(
            sess, control=True)
        print(json.dumps({"seed": seed,
                          "checks": {k: v for k, (v, _) in
                                     out.checks.items()},
                          "e2e": out.e2e}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
