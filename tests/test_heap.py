import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import heap as heap_mod


def test_malloc_alignment_and_symmetry():
    h = heap_mod.create(npes=4)
    a = h.malloc((100,), "float32")
    b = h.malloc((3, 5), "float32")
    assert a.offset % heap_mod.ALIGN == 0
    assert b.offset % heap_mod.ALIGN == 0
    assert b.offset >= a.offset + 128          # no overlap
    assert a.shape == (100,) and b.shape == (3, 5)
    # symmetric: same ptr valid at every PE
    h = h.write(a, 0, jnp.ones(100))
    h = h.write(a, 3, jnp.full(100, 2.0))
    assert float(h.read(a, 0)[0]) == 1.0
    assert float(h.read(a, 3)[0]) == 2.0
    assert float(h.read(a, 1)[0]) == 0.0       # other PEs untouched


def test_free_reuse():
    h = heap_mod.create(npes=2)
    a = h.malloc((256,), "float32")
    h.free(a)
    b = h.malloc((128,), "float32")
    assert b.offset == a.offset                # first-fit reuse


def test_calloc_zeroes_reused_region():
    """free -> write -> calloc regression: a recycled free-list block must
    not leak the freed buffer's bytes through calloc."""
    h = heap_mod.create(npes=2)
    a = h.malloc((256,), "float32")
    h = h.write(a, 1, jnp.full(256, 7.0))      # dirty the region at PE 1
    h.free(a)
    b = h.calloc((256,), "float32")
    assert b.offset == a.offset                # reuse really happened
    np.testing.assert_array_equal(np.asarray(h.read(b, 1)), 0.0)
    np.testing.assert_array_equal(np.asarray(h.read(b, 0)), 0.0)


def test_malloc_reuse_is_dirty_but_calloc_is_not():
    # documents the malloc contract the calloc fix is defined against
    h = heap_mod.create(npes=1)
    a = h.malloc((128,), "float32")
    h = h.write(a, 0, jnp.ones(128))
    h.free(a)
    c = h.malloc((128,), "float32")
    assert float(h.read(c, 0)[0]) == 1.0       # malloc: undefined (dirty)


def test_free_coalesces_adjacent_extents():
    h = heap_mod.create(npes=1)
    ptrs = [h.malloc((128,), "float32") for _ in range(4)]
    keep = h.malloc((128,), "float32")         # guard after the freed run
    for p in (ptrs[0], ptrs[2], ptrs[1], ptrs[3]):   # out-of-order frees
        h.free(p)
    assert h._free["float32"] == [(ptrs[0].offset, 4 * 128)]
    # the coalesced extent satisfies an allocation bigger than any one piece
    big = h.malloc((512,), "float32")
    assert big.offset == ptrs[0].offset
    assert keep.offset >= 4 * 128


def test_heap_stats_accounting():
    h = heap_mod.create(npes=2)
    a = h.malloc((256,), "float32")
    b = h.malloc((128,), "int32")
    s = h.stats()
    assert s["bytes_in_use"] == 256 * 4 + 128 * 4
    assert s["bytes_free"] == 0
    assert s["pools"]["float32"]["fragmentation"] == 0.0
    h.free(a)
    s = h.stats()
    assert s["pools"]["float32"]["bytes_free"] == 256 * 4
    assert s["pools"]["float32"]["bytes_in_use"] == 0
    assert s["pools"]["int32"]["bytes_in_use"] == 128 * 4
    assert s["pools"]["float32"]["free_extents"] == 1
    # two non-adjacent free extents -> nonzero fragmentation
    h2 = heap_mod.create(npes=1)
    x = h2.malloc((128,), "float32")
    y = h2.malloc((128,), "float32")
    z = h2.malloc((128,), "float32")
    h2.free(x)
    h2.free(z)                                  # x and z are not adjacent
    st = h2.stats()["pools"]["float32"]
    assert st["free_extents"] == 2
    assert st["fragmentation"] == 0.5


def test_pool_growth():
    h = heap_mod.create(npes=2, words_per_pool=256)
    ptrs = [h.malloc((128,), "float32") for _ in range(8)]
    h = h.write(ptrs[-1], 1, jnp.arange(128))
    assert float(h.read(ptrs[-1], 1)[5]) == 5.0


def test_dtype_canonicalization():
    h = heap_mod.create(npes=2)
    p = h.malloc((), "int64")                  # narrows without x64
    assert p.dtype == "int32"


def test_read_all_write_all():
    h = heap_mod.create(npes=3)
    p = h.malloc((4,), "int32")
    h = h.write_all(p, jnp.arange(12).reshape(3, 4))
    np.testing.assert_array_equal(np.asarray(h.read_all(p)),
                                  np.arange(12).reshape(3, 4))


def test_ptr_index_bounds():
    h = heap_mod.create(npes=2)
    p = h.malloc((8,), "float32")
    assert p.index(7).offset == p.offset + 7
    with pytest.raises(IndexError):
        p.index(8)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 500),
                          st.sampled_from(["float32", "int32"])),
                min_size=1, max_size=20))
def test_allocations_never_overlap(allocs):
    h = heap_mod.create(npes=1)
    spans = {"float32": [], "int32": []}
    for n, dt in allocs:
        p = h.malloc((n,), dt)
        lo, hi = p.offset, p.offset + max(128, -(-n // 128) * 128)
        for (l2, h2) in spans[dt]:
            assert hi <= l2 or lo >= h2, "overlapping allocation"
        spans[dt].append((lo, hi))


def _filled(npes=3):
    """A heap whose pools of every dtype hold distinct values at every PE."""
    h = heap_mod.create(npes, 1024)
    ptrs = {}
    for dt in ("float32", "int32", "bfloat16"):
        ptrs[dt] = {"vec": h.malloc((5,), dt), "mat": h.malloc((2, 3), dt),
                    "scalar": h.malloc((), dt), "empty": h.malloc((0,), dt)}
        words = h.pools[dt].shape[1]
        h = h.write_all(heap_mod.SymPtr(dt, 0, (words,)),
                        np.arange(npes * words).reshape(npes, words) % 251)
    return h, ptrs


@pytest.mark.parametrize("dt", ["float32", "int32", "bfloat16"])
@pytest.mark.parametrize("kind", ["vec", "mat", "scalar", "empty"])
@pytest.mark.parametrize("pe", [0, 2, -1, -3])
@pytest.mark.parametrize("traced", [False, True])
def test_read_slices_the_pool_at_the_pe(dt, kind, pe, traced):
    """``read`` is the numpy slice of the PE's words, for Python-int,
    negative and traced PEs, and for scalar and zero-size pointers."""
    h, ptrs = _filled()
    ptr = ptrs[dt][kind]
    want = np.asarray(h.pools[dt])[pe, ptr.offset:ptr.offset + ptr.size]
    if traced:
        got = jax.jit(lambda q: h.read(ptr, q))(np.int32(pe))
    else:
        got = h.read(ptr, pe)
    assert got.shape == ptr.shape and got.dtype == h.pools[dt].dtype
    np.testing.assert_array_equal(np.asarray(got).reshape(-1), want)


def test_read_rejects_a_pe_out_of_range():
    h, ptrs = _filled()
    for pe in (3, -4, 7):
        with pytest.raises(IndexError):
            h.read(ptrs["float32"]["vec"], pe)
