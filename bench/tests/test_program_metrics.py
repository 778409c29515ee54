"""The readers of the program's heap-store and host-read counters, its host
spans and its named decode program: on hand-made records, on records of a
program that has none of them, on a trace recorded on the chip, and in a
traced run of the whole harness on the CPU."""
import os

import pytest

from bench.harness import session, trace
from bench.tests import tiny
from bench.tests.test_trace import DATA, make, reader
from repro.obs.prof import ProfSample

#: the readers of the program's counters, spans and program names
NEW = ("heap.store_mb_per_decode_step", "heap.stores_per_decode_step",
       "sched.host_reads_per_decode_step", "sched.host_ms_per_decode_step",
       "kvpool.assemble_host_ms", "kvpool.writeback_host_ms",
       "engine.decode_device_ms")
MS = 1_000_000


def spans(*pairs):
    """Profiler samples of host spans, ``(op, milliseconds)`` each."""
    return [ProfSample(op=op, nbytes=0, path="host", tier="local",
                       work_items=1, step=0, wall_s=ms / 1e3, model_s=0.0)
            for op, ms in pairs]


#: two scheduler steps with a decode call each: 143 ms under the phases,
#: 83 ms of it in the work handed to the chip; the spans nested in the
#: decode call and the token reads are no work of their own
STEPS = spans(
    ("kv_stage", 5), ("serve_prefill", 10), ("stream_flush", 8),
    ("sched_recover", 1), ("sched_prefill", 40), ("sched_admit", 1),
    ("sched_resume", 1), ("kv_assemble", 4), ("paged_attn", 20),
    ("kv_writeback", 7), ("serve_decode", 30), ("token_read", 3),
    ("sched_decode", 50),
    ("sched_recover", 1), ("sched_prefill", 2), ("sched_admit", 1),
    ("sched_resume", 1), ("kv_assemble", 6), ("paged_attn", 19),
    ("kv_writeback", 9), ("serve_decode", 30), ("token_read", 2),
    ("sched_decode", 45))


@pytest.mark.parametrize("name,rec,want", [
    # 8 GB of pool rebuilt over 4 decode steps
    ("heap.store_mb_per_decode_step",
     {"sched": {"decode_steps": 4, "heap_store_bytes": 8_000_000_000}},
     2000.0),
    # 10 whole-pool stores over 4 decode steps
    ("heap.stores_per_decode_step",
     {"sched": {"decode_steps": 4, "heap_stores": 10}}, 2.5),
    # 14 reads over 4 decode steps
    ("sched.host_reads_per_decode_step",
     {"sched": {"decode_steps": 4, "host_reads": 14}}, 3.5),
    # (143 - 83) ms over 2 decode steps
    ("sched.host_ms_per_decode_step",
     {"sched": {"decode_steps": 2}, "prof": STEPS}, 30.0),
    ("kvpool.assemble_host_ms",
     {"prof": STEPS + spans(("kv_assemble", 5))}, 5.0),
    ("kvpool.writeback_host_ms",
     {"prof": STEPS + spans(("kv_writeback", 8))}, 8.0),
    # three decode calls inside the window (12, 14 and 20 ms); the one that
    # ends after the close and the prefill do not count
    ("engine.decode_device_ms", {"trace": make([], [
        ("bench_window", 0, 100 * MS)], mods=[
        ("jit_decode_step(7)", 10 * MS, 12 * MS),
        ("jit_prefill(3)", 20 * MS, 50 * MS),
        ("jit_decode_step(7)", 40 * MS, 14 * MS),
        ("jit_decode_step(7)", 70 * MS, 20 * MS),
        ("jit_decode_step(7)", 95 * MS, 10 * MS)])}, 14.0),
])
def test_reader_by_hand(name, rec, want):
    assert reader(name)(rec) == pytest.approx(want)


def test_readers_say_nothing_without_their_inputs():
    """A program without the counters, spans or program names (the older
    recorded trace has no named modules; its profiler has only the older
    scopes) reads as no metric, not as zero."""
    old = {"trace": trace.Trace.load(os.path.join(
               DATA, "trace-qwen3-4b.prompt-heavy.json")),
           "sched": {"decode_steps": 5, "decode_tokens": 5},
           "prof": spans(("serve_decode", 60), ("paged_attn", 25))}
    for name in NEW:
        assert reader(name)({"peaks": None}) is None
        assert reader(name)(old) is None
    assert reader("heap.store_mb_per_decode_step")(
        {"sched": {"decode_steps": 0, "heap_store_bytes": 1}}) is None
    assert reader("sched.host_ms_per_decode_step")(
        {"sched": {"decode_steps": 0}, "prof": STEPS}) is None


def test_decode_module_on_a_recorded_trace_of_the_named_program():
    """A slice of one fleet step recorded on the chip with the program's
    named modules: the decode module is found by name."""
    tr = trace.Trace.load(os.path.join(
        DATA, "trace-qwen3-4b.prompt-heavy-spans.json"))
    assert reader("engine.decode_device_ms")(
        {"trace": tr}) == pytest.approx(12.227487)


def test_traced_run_reads_the_program_counters_and_spans(tmp_path, no_cache,
                                                         cpu_chips):
    """The program's counters and host spans reach their readers in a
    traced run; the reader of the device's modules finds no chip there and
    leaves its metric out of the line."""
    root, bench = tiny.make_tree(tmp_path)
    doc = session.run(["--workload", "tiny.open", "--seed", "5",
                       "--seconds", "2", "--trace", "1"], root,
                      chips=cpu_chips, bench=bench)
    m = doc["metrics"]
    assert m["heap.store_mb_per_decode_step"]["value"] > 0
    assert m["heap.stores_per_decode_step"]["value"] > 0
    assert m["sched.host_reads_per_decode_step"]["value"] >= 2
    for name in ("sched.host_ms_per_decode_step", "kvpool.assemble_host_ms",
                 "kvpool.writeback_host_ms"):
        assert m[name]["value"] > 0
    assert "engine.decode_device_ms" not in m
