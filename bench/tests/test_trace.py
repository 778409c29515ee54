"""The reduction from a device trace to busy time, program time and
roofline shares, on hand-made traces and on a small trace recorded on the
chip (``data/``)."""
import glob
import importlib.util
import os

import pytest

from bench.harness import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def make(ops, host, mods=()):
    return trace.Trace({DEV: {"XLA Ops": list(ops),
                              "XLA Modules": list(mods)},
                        HOST: {"python3": list(host)}})


def test_union_overlap_and_busy():
    assert trace.union([(5, 10), (0, 6), (20, 30), (30, 31)]) == [
        (0, 10), (20, 31)]
    assert trace.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    tr = make([("a", 0, 10), ("b", 5, 10), ("c", 30, 5)],
              [("bench_window", 0, 40)])
    assert trace.busy(tr, DEV, 0, 40) == [(0, 15), (30, 35)]
    assert trace.op_time(tr, "^[ab]$", 0, 40) == pytest.approx(15e-9)
    assert trace.op_time(tr, "c", 0, 32) == pytest.approx(2e-9)


def test_reduce_attributes_idle_to_innermost_activity():
    tr = make([("a", 10, 10), ("b", 50, 10)],
              [("bench_window", 0, 100), ("fleet_step", 0, 60),
               ("serve_decode", 30, 20)])
    doc = trace.reduce(tr, ("fleet_step", "serve_decode"))
    assert doc["busy_s"] == pytest.approx(20e-9)
    assert doc["window_s"] == pytest.approx(100e-9)
    idle = dict(doc["idle_gaps"])
    # idle: [0,10) and [20,30) under fleet_step, [30,50) under
    # serve_decode, [60,100) under nothing
    assert idle["fleet_step"] == pytest.approx(20e-9)
    assert idle["serve_decode"] == pytest.approx(20e-9)
    assert idle["no activity"] == pytest.approx(40e-9)
    assert dict(doc["device_ops"]) == {"a": pytest.approx(10e-9),
                                      "b": pytest.approx(10e-9)}


def test_decode_roofline_by_hand():
    """Two decode calls, each a 10 ms program in its model-call span, each
    needing 4.095 GB at 819 GB/s (5 ms): a 50% share."""
    class S:
        def __init__(self, op, nbytes):
            self.op, self.nbytes, self.wall_s = op, nbytes, 0.0
    ms = 1_000_000
    cfg = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "intermediate_size": 128, "num_hidden_layers": 1,
           "vocab_size": 256}
    from bench.harness import flops, registry
    from bench.tests.tiny import REPO
    kv = (int(4.095e9) - flops.weight_bytes(cfg)) // flops.kv_bytes_per_token(
        cfg) * flops.kv_bytes_per_token(cfg)
    tr = make([], [("bench_window", 0, 100 * ms),
                   ("paged_attn", 10 * ms, 12 * ms),
                   ("paged_attn", 50 * ms, 12 * ms)],
              mods=[("assemble", 9 * ms, 3 * ms), ("decode", 12 * ms, 10 * ms),
                    ("decode", 51 * ms, 10 * ms)])
    rec = {"trace": tr, "cfg": cfg, "peaks": {"hbm_bytes_per_s": 819e9},
           "family": registry.Registry(REPO).family("dense"),
           "prof": [S("serve_decode", kv), S("serve_decode", kv)]}
    assert reader("decode.hbm_roofline")(rec) == pytest.approx(50.0, rel=1e-6)


def test_readers_say_nothing_without_a_trace():
    for name in ("decode.hbm_roofline", "device.idle_share.serve"):
        assert reader(name)({"peaks": None}) is None


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "trace-*.json"))), ids=os.path.basename)
def test_recorded_chip_trace(path):
    tr = trace.Trace.load(path)
    assert tr.devices()
    doc = trace.reduce(tr, ("fleet_step", "serve_decode", "paged_attn"))
    assert 0 < doc["busy_s"] <= doc["window_s"]
    assert len(doc["device_ops"]) <= 10 and len(doc["idle_gaps"]) <= 10
    idle = sum(v for _, v in doc["idle_gaps"])
    assert idle == pytest.approx(doc["window_s"] - doc["busy_s"], rel=1e-6)
