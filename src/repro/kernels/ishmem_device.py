"""Device-initiated kernels: fused paged-attention gather + ring attention.

Two consumers of the work-group-collaborative op layer (``core/device.py``):

- :func:`paged_gather` / :func:`fused_paged_attn` — the decode-side fusion.
  The gather kernel walks a slot block table and copies each mapped pool
  block into the assembled payload (scalar-prefetch grid: the table rides
  in SMEM and steers the block index map, exactly how a TPU paged-attention
  kernel addresses its pages).  ``fused_paged_attn`` runs the device-side
  admission protocol in front of it: per-block ``signal_wait_until`` calls
  consume migrated KV blocks *as their put_signal_nbi signals land*, then
  the gathered K/V feeds the same fused flash kernel the dense path uses —
  so the fused output is bitwise-identical to ``assemble`` + flash.
- :func:`ring_attention` — sequence-parallel attention: the KV sequence is
  sharded across simulated PEs and rotated ring-wise, each step computing a
  partial flash (unnormalized accumulator + running max/denominator)
  against the resident shard; partials merge by the standard online-softmax
  combination.  Device-side rotation issue and overlap pricing live in
  ``core.device`` / ``cutover.t_ring_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.kernels import LANE
from repro.core import device as device_mod
from repro.kernels.flash_attn import NEG_INF


# ---------------------------------------------------------------------------
# paged gather (table-steered block copy)
# ---------------------------------------------------------------------------


def _gather_kernel(table_ref, data_ref, o_ref):
    # one program copies one table-mapped block row; the index map already
    # pointed data_ref at row table[b, j]
    del table_ref
    o_ref[0, 0] = data_ref[0]


@jax.jit
def _paged_gather_pallas(data, table):
    """``data``: (R, W) with W a multiple of 128, viewed as (R, W/128, 128)
    so every block is whole (sublane, lane) tiles."""
    R, W = data.shape
    B, nb = table.shape
    rows = W // LANE
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nb),
            in_specs=[pl.BlockSpec((1, rows, LANE),
                                   lambda b, j, t: (t[b, j], 0, 0))],
            out_specs=pl.BlockSpec((1, 1, rows, LANE),
                                   lambda b, j, t: (b, j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, nb, rows, LANE), data.dtype),
        interpret=kernels.interpret(),
    )(table, data.reshape(R, rows, LANE))
    return out.reshape(B, nb, W)


def paged_gather(data, table):
    """Gather block rows through a block table: ``out[b, j] = data[table[b, j]]``.

    ``data``: (num_rows, block_words) — the pool row plus its trailing
    all-zeros page; ``table``: (num_slots, nb) int32.  Runs the scalar-
    prefetch Pallas kernel (a pure copy); rows are zero-padded to whole
    lanes and the padding dropped again."""
    W = data.shape[1]
    pad = (-W) % LANE
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    out = _paged_gather_pallas(data, jnp.asarray(table, jnp.int32))
    return out[:, :, :W] if pad else out


# ---------------------------------------------------------------------------
# fused paged attention
# ---------------------------------------------------------------------------


def _leaf_offsets(lay):
    offs = {}
    off = 0
    for leaf in lay.paged:
        offs[(leaf.unit_idx, leaf.key)] = off
        off += leaf.words_per_token * lay.block_tokens
    return offs


def _extract_leaf(pay, lay, leaf, num_slots, off):
    """Rebuild one paged leaf from the gathered payload — the EXACT
    ``PagedDecodeView.assemble`` slicing, so the result is bitwise what the
    dense rehydrate would hold."""
    T = lay.block_tokens
    nb = lay.blocks_per_request
    n = leaf.words_per_token * T
    out = pay[:, :, off:off + n].reshape(
        num_slots, nb, leaf.reps, T, leaf.nkv, leaf.hd)
    return out.transpose(2, 0, 1, 3, 4, 5).reshape(
        leaf.reps, num_slots, nb * T, leaf.nkv, leaf.hd)[:, :, :leaf.width]


def fused_paged_attn(wg, heap, view, q, *, unit_idx=None, layer: int = 0,
                     waits=(), dtype=None):
    """Device-initiated fused gather + attention over the paged KV pool.

    ``wg`` is the calling work-group (``core.device.work_group``), ``view``
    a ``serve.paged_attn.PagedDecodeView``.  ``waits`` is a sequence of
    ``(sig_ptr, expected)`` pairs consumed via device ``signal_wait_until``
    BEFORE any block byte is read — the fusion protocol's per-block gates.
    ``q``: (num_slots, W, nq, hd) queries against the assembled width.

    Returns ``(heap, out)`` with ``out`` bitwise-identical to gathering the
    same leaves through ``view.assemble`` and running ``ops.flash_attention``
    (the A/B the tests and ``bench_device`` assert).
    """
    from repro.kernels import ops

    for sig_ptr, expected in waits:
        heap, _, ok = device_mod.signal_wait_until(
            wg, heap, sig_ptr, view.pe, "ge", expected)
        if not ok:
            raise RuntimeError(
                "fused_paged_attn: signal can never satisfy its wait — "
                "reading a block here would observe pre-signal bytes")
    lay = view.pool.layout
    if not lay.paged:
        raise ValueError("fused_paged_attn requires a paged layout")
    if unit_idx is None:
        unit_idx = lay.paged[0].unit_idx
    k_leaf = next(p for p in lay.paged
                  if p.unit_idx == unit_idx and p.key == "k")
    v_leaf = next(p for p in lay.paged
                  if p.unit_idx == unit_idx and p.key == "v")
    # collaborative local load of the pool row (device_get telemetry at the
    # group's width), then the table-steered gather kernel
    data = device_mod.get(wg, heap, view.pool.data, view.pe).reshape(
        view.pool.num_blocks, lay.block_words)
    data = jnp.concatenate(
        [data, jnp.zeros((1, lay.block_words), data.dtype)], axis=0)
    pay = paged_gather(data, view.table())
    offs = _leaf_offsets(lay)
    k = _extract_leaf(pay, lay, k_leaf, view.num_slots,
                      offs[(unit_idx, "k")])[layer]
    v = _extract_leaf(pay, lay, v_leaf, view.num_slots,
                      offs[(unit_idx, "v")])[layer]
    if dtype is not None:
        k = k.astype(dtype)
        v = v.astype(dtype)
    return heap, ops.flash_attention(q, k, v)


# ---------------------------------------------------------------------------
# sequence-parallel ring attention
# ---------------------------------------------------------------------------


def _flash_partial_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                          bq, bk, scale, q_off, k_off):
    """Flash tile against ONE resident KV shard: emits the UNNORMALIZED
    accumulator plus running (max, denominator) so shard partials merge by
    the online-softmax combination.  ``q_off``/``k_off`` are the shards'
    absolute sequence positions — causality is global, not shard-local."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (bq, hd)
    Skv = k_ref.shape[1]
    nkb = pl.cdiv(Skv, bk)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bk, bk)].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * bk, bk)].astype(jnp.float32)
        s = q @ k.T
        qpos = q_off + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        kpos = k_off + j * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkb, body, (m0, l0, a0))
    o_ref[0] = acc
    m_ref[0] = m
    l_ref[0] = l


def flash_partial(q, k, v, *, q_off: int, k_off: int, block_q: int = 256,
                  block_k: int = 256):
    """One ring step's partial attention.  q: (B, Sq, H, hd) — the local
    query shard; k, v: (B, Skv, H, hd) — the KV shard currently resident.
    Returns (acc, m, l): unnormalized output (B, Sq, H, hd) f32 and the
    per-position softmax state (B, Sq, H) f32."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    bq = min(block_q, Sq)
    while Sq % bq:
        bq //= 2
    bk = min(block_k, Skv)
    while Skv % bk:
        bk //= 2
    scale = hd ** -0.5

    def flat(t, S):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    qf, kf, vf = flat(q, Sq), flat(k, Skv), flat(v, Skv)
    acc, m, l = pl.pallas_call(
        functools.partial(_flash_partial_kernel, bq=bq, bk=bk, scale=scale,
                          q_off=q_off, k_off=k_off),
        grid=(B * H, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Skv, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Skv, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i: (b, i)),
            pl.BlockSpec((1, bq), lambda b, i: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Sq), jnp.float32),
            jax.ShapeDtypeStruct((B * H, Sq), jnp.float32),
        ],
        interpret=kernels.interpret(),
    )(qf, kf, vf)

    def unflat(t, trail):
        return t.reshape((B, H, Sq) + trail).transpose(
            (0, 2, 1) + tuple(range(3, 3 + len(trail))))

    return unflat(acc, (hd,)), unflat(m, ()), unflat(l, ())


def merge_partials(parts):
    """Combine per-shard (acc, m, l) partials into the softmax-correct
    output: ``m* = max m_i``, ``l* = sum l_i e^{m_i - m*}``,
    ``o = sum acc_i e^{m_i - m*} / l*``."""
    ms = jnp.stack([m for _, m, _ in parts])          # (n, B, Sq, H)
    m_tot = ms.max(axis=0)
    w = jnp.exp(ms - m_tot[None])                     # (n, B, Sq, H)
    l_tot = jnp.stack([l for _, _, l in parts])
    l_tot = (l_tot * w).sum(axis=0)
    acc = jnp.stack([a for a, _, _ in parts])         # (n, B, Sq, H, hd)
    out = (acc * w[..., None]).sum(axis=0)
    return out / jnp.maximum(l_tot, 1e-30)[..., None]


def ring_attention(q, k, v, *, npes: int, block_q: int = 256,
                   block_k: int = 256):
    """Sequence-parallel causal attention: the sequence is sharded across
    ``npes`` ring positions (PE i holds q/k/v shard i), and KV shards rotate
    around the ring — at step t, shard i computes a partial against KV shard
    ``(i - t) mod npes``.  Causality means only shards j <= i contribute, so
    the schedule is exactly the device-initiated ring the overlap model
    (``cutover.t_ring_attention``) prices: issue next rotation, compute
    resident partial, merge.

    q, k, v: (B, S, H, hd) with S % npes == 0 (GQA: equal head counts —
    callers repeat KV heads first, like ``flash_attn.flash_attention``).
    Returns (B, S, H, hd) matching full-sequence causal attention up to
    float associativity (the partial merge reorders the softmax sums).
    """
    B, S, H, hd = q.shape
    assert S % npes == 0, "sequence must shard evenly over ring PEs"
    Sh = S // npes
    shards_q = [q[:, i * Sh:(i + 1) * Sh] for i in range(npes)]
    shards_k = [k[:, i * Sh:(i + 1) * Sh] for i in range(npes)]
    shards_v = [v[:, i * Sh:(i + 1) * Sh] for i in range(npes)]
    outs = []
    for i in range(npes):
        parts = []
        for t in range(npes):
            j = (i - t) % npes
            if j > i:                    # future shard: fully masked, skip
                continue
            parts.append(flash_partial(
                shards_q[i], shards_k[j], shards_v[j],
                q_off=i * Sh, k_off=j * Sh,
                block_q=block_q, block_k=block_k))
        outs.append(merge_partials(parts))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)
