"""The command refuses to run without a TPU or without the program, and a
cell added as files alone runs through the whole harness on the CPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import session
from bench.tests import tiny

REPO = tiny.REPO


def _run_cmd(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **dict(extra_env))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-4b.prompt-heavy", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run_cmd(REPO)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cmd(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_added_cell_runs(tmp_path, no_cache, cpu_chips):
    """A configuration, a traffic mix and a cell that exist only as new
    files run end to end, and the result line has the contract's keys,
    ``checks`` last."""
    root, bench = tiny.make_tree(tmp_path)
    doc = session.run(["--workload", "tiny.open", "--seed", str(2**33 + 1),
                       "--seconds", "2", "--trace", "0"], root,
                      chips=cpu_chips, bench=bench)
    assert list(doc) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert doc["correct"] is True and doc["attempted"] > 0
    assert set(doc["metrics"]) == {
                                   "output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in doc["metrics"].values())
    assert doc["checks"]["max_logit_gap"]["value"] <= 0.2
    json.dumps(doc)


def test_added_metric_reader(tmp_path, no_cache, cpu_chips):
    """A per-layer metric is a new reader file plus its entry."""
    root, bench = tiny.make_tree(tmp_path)
    with open(os.path.join(bench, "metrics", "sched.prefills.py"), "w") as f:
        f.write("def read(rec):\n    return rec['sched'].get('prefills')\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["per_layer"].append({
        "name": "sched.prefills", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "frontend and scheduler",
        "moves": "output_tokens_per_s", "workloads": ["tiny.open"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    doc = session.run(["--workload", "tiny.open", "--seed", "3",
                       "--seconds", "2", "--trace", "1"], root,
                      chips=cpu_chips, bench=bench)
    assert doc["correct"] is True
    assert doc["metrics"]["sched.prefills"]["value"] > 0
    assert "busy_s" in doc["device"] and "breakdown" in doc


@pytest.mark.parametrize("argv", [
    ["--workload", "x", "--seed", "1", "--seconds", "1", "--trace", "2"],
    ["--workload", "x", "--seconds", "1"],
])
def test_bad_arguments(argv):
    with pytest.raises(SystemExit):
        session.parse(argv)
