#!/usr/bin/env python3
"""The benchmark's command; see ``bench/harness/session.py``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program's ``src/``.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    from bench.harness import session
    sys.exit(session.main(sys.argv[1:], ROOT))
