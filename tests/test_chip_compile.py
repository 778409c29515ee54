"""Compile rehearsals for a TPU v5e that is described, not attached.

The chip's compiler refuses kernels that interpret mode accepts: a slice not
aligned to the (sublane, 128) tiling, a block that overruns VMEM, a program
that does not fit HBM.  Each test here compiles one kernel or step of the
main path for a described ``v5e:2x2`` host and asserts that the chip would
run a real Mosaic kernel (``tpu_custom_call``), not the interpreter.
Nothing executes.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture, never while a module is imported; keep
every such rehearsal in this one file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro import kernels
from repro.configs import base as cfgbase
from repro.core import heap as heap_mod
from repro.kernels import ishmem_device, ops
from repro.launch.mesh import make_mesh
from repro.models import model
from repro.serve import kvpool
from repro.serve.paged_attn import assemble_pages

NPES = 4
HBM_BYTES = 16e9            # one v5e chip
SERVE_MAX_LEN = 144         # 128-token prompts + 16 new tokens
SERVE_BLOCKS = 64
BENCH_MAX_LEN = 1056        # the benchmark's qwen3-4b pool: 66-block tables
BENCH_BLOCKS = 140          # over 140 blocks on each of 3 PEs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return make_mesh((NPES,), ("x",), devices=topo.devices[:NPES])


@pytest.fixture
def mosaic(monkeypatch):
    """Compile the Pallas kernels for the chip although the backend here is
    the CPU."""
    monkeypatch.setattr(kernels, "interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_gather_full_block_width(one_chip, mosaic):
    cfg = cfgbase.get_config("qwen3_4b")
    lay = kvpool.build_layout(cfg, SERVE_MAX_LEN, block_tokens=16)
    data = _sds((SERVE_BLOCKS + 1, lay.block_words), lay.kv_dtype, one_chip)
    table = _sds((2, lay.blocks_per_request), jnp.int32, one_chip)
    compiled = jax.jit(ishmem_device.paged_gather).lower(
        data, table).compile()
    _assert_kernel(compiled)


def _element_counts(hlo):
    """Element count of every array shape written in an HLO text."""
    return {int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(r"\b(?:bf16|f32|s32|pred)\[([\d,]*)\]",
                                   hlo)}


def _bench_pool(one_chip):
    cfg = cfgbase.get_config("qwen3_4b")
    lay = kvpool.build_layout(cfg, BENCH_MAX_LEN, block_tokens=16)
    pool = _sds((3, BENCH_BLOCKS * lay.block_words), lay.kv_dtype, one_chip)
    return lay, pool


def test_assemble_pages_reads_only_the_mapped_blocks(one_chip):
    """Paged-decode assembly at qwen3-4b width over the benchmark's pool:
    no array the size of a PE's pool row is made, and the program's
    temporary buffers stay under twice the 156 MB it gathers."""
    lay, pool = _bench_pool(one_chip)
    nb = lay.blocks_per_request
    table = _sds((1, nb), jnp.int32, one_chip)
    pe = _sds((), jnp.int32, one_chip)
    compiled = assemble_pages.lower(
        pool, pe, table, layout=lay, data_off=0,
        num_blocks=BENCH_BLOCKS).compile()
    counts = _element_counts(compiled.as_text())
    assert 3 * BENCH_BLOCKS * lay.block_words in counts     # the pool
    assert BENCH_BLOCKS * lay.block_words not in counts
    gathered = nb * lay.block_words * jnp.dtype(lay.kv_dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * gathered


def test_heap_read_of_a_block_slices_the_pool(one_chip):
    """``SymmetricHeap.read`` of one KV block from the benchmark's pool is
    one program that slices the 2-D pool and makes no row of it."""
    lay, pool = _bench_pool(one_chip)
    ptr = heap_mod.SymPtr(lay.kv_dtype, 7 * lay.block_words,
                          (lay.block_words,))
    pe = _sds((), jnp.int32, one_chip)
    read = jax.jit(lambda p, q: heap_mod.SymmetricHeap(
        3, {lay.kv_dtype: p}).read(ptr, q))
    compiled = read.lower(pool, pe).compile()
    counts = _element_counts(compiled.as_text())
    assert 3 * BENCH_BLOCKS * lay.block_words in counts     # the pool
    assert BENCH_BLOCKS * lay.block_words not in counts
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _ring_fns():
    kw = dict(axis_name="x", npes=NPES)
    return {
        "remote_put": (lambda v: ops.remote_put(v[0], work_items=8,
                                                **kw)[None], 2),
        "ring_allgather": (lambda v: ops.ring_allgather(v[0], **kw)[None],
                           3),
        "ring_reduce_scatter": (
            lambda v: ops.ring_reduce_scatter(v[0], **kw)[None], 2),
        "push_broadcast": (lambda v: ops.push_broadcast(v[0], root=1,
                                                        **kw)[None], 2),
    }


@pytest.mark.parametrize("name", sorted(_ring_fns()))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ring_kernels_on_four_chips(name, dtype, mesh4, mosaic):
    fn, out_rank = _ring_fns()[name]
    # one PE's message: a ragged size, so the tile padding is exercised
    shape = ((NPES, NPES, 3000) if name == "ring_reduce_scatter"
             else (NPES, 5000))
    x = _sds(shape, dtype,
             NamedSharding(mesh4, P("x", *([None] * (len(shape) - 1)))))
    out_spec = P("x", *([None] * (out_rank - 1)))
    f = jax.jit(jax.shard_map(fn, mesh=mesh4, in_specs=P("x"),
                              out_specs=out_spec, check_vma=False))
    _assert_kernel(f.lower(x).compile())


def test_flash_attention_4k(one_chip, mosaic):
    q = _sds((1, 4096, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 4096, 8, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(ops.flash_attention).lower(q, kv, kv).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_qwen3_4b_step_fits_one_chip(kind, one_chip):
    """The served steps at full published width, bf16, all 36 layers: a
    one-request prefill of 128 tokens, and decode of 2 slots, both over a
    144-token cache."""
    cfg = cfgbase.get_config("qwen3_4b")
    place = lambda t: jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip), t)
    params = place(jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), cfg)))
    if kind == "prefill":
        cache = place(cfgbase.cache_specs(cfg, 1, SERVE_MAX_LEN))
        batch = {"tokens": _sds((1, 128), jnp.int32, one_chip)}
        step = jax.jit(lambda p, b, c: model.prefill(p, cfg, b, c))
        lowered = step.lower(params, batch, cache)
    else:
        cache = place(cfgbase.cache_specs(cfg, 2, SERVE_MAX_LEN))
        tok = _sds((2, 1), jnp.int32, one_chip)
        pos = _sds((2,), jnp.int32, one_chip)
        step = jax.jit(lambda p, t, q, c: model.decode_step(p, cfg, t, q, c))
        lowered = step.lower(params, tok, pos, cache)
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 8e9      # the bf16 weights are there
    assert total < HBM_BYTES
