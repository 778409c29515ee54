"""The reader of the paged-decode assembly's device time: on a hand-made
trace, and on recorded traces of a program that has no such module."""
import os

import pytest

from bench.harness import trace
from bench.tests.test_trace import DATA, make, reader

NAME = "kvpool.assemble_device_ms"
MS = 1_000_000


def test_assembly_module_by_hand():
    """Three assemblies inside the window (0.8, 1.1 and 2.5 ms); the one
    that ends after the close and the decode step do not count."""
    tr = make([], [("bench_window", 0, 100 * MS)], mods=[
        ("jit_assemble_pages(12)", 10 * MS, 0.8 * MS),
        ("jit_decode_step(7)", 11 * MS, 12 * MS),
        ("jit_assemble_pages(12)", 40 * MS, 1.1 * MS),
        ("jit_assemble_pages(12)", 70 * MS, 2.5 * MS),
        ("jit_assemble_pages(12)", 99 * MS, 2 * MS)])
    assert reader(NAME)({"trace": tr}) == pytest.approx(1.1)


@pytest.mark.parametrize("fixture", [
    "trace-qwen3-4b.prompt-heavy.json",
    "trace-qwen3-4b.prompt-heavy-spans.json"])
def test_a_program_without_the_module_reads_nothing(fixture):
    """The recorded traces come from programs that assembled with eager
    ops (the second names its decode module): no metric, not zero."""
    tr = trace.Trace.load(os.path.join(DATA, fixture))
    assert reader(NAME)({"trace": tr}) is None
    assert reader(NAME)({"peaks": None}) is None
