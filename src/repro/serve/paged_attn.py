"""Paged decode attention: decode reads K/V straight from the block pool.

PR 3's migration engine parked a request's paged KV in the symmetric-heap
pool only long enough to rehydrate a dense per-slot cache (``kvxfer.gather``
+ ``kvpool.insert_blocks``); the decode step then ran against the dense
copy — a full-payload copy per admission and two live copies of every
resident request's KV.  This module removes the rehydrate: the decode PE's
pool row *is* the decode-side KV cache, indexed per slot through block
tables (DESIGN.md §9).

- **assemble** — one program per step (:func:`assemble_pages`) reads
  only the blocks the slots' block tables map, straight from the decode
  PE's row of the pool, never the row itself, and rebuilds every paged
  leaf ``(reps, B, W, nkv, hd)`` exactly as ``insert_blocks`` would have
  built it, so the decode computation is bitwise-identical to the dense
  path (``tests/test_disagg.py`` / ``tests/test_paged.py``).  Table slots
  past a request's resident blocks read zero (the virgin dense-cache
  value); positions beyond the decode cursor are masked by the attention
  validity rules either way.
- **writeback** — the step's freshly projected K/V token lands back in the
  owning block: a local store on the decode PE, exactly the cache write a
  decode kernel performs, just targeting pool pages instead of a dense
  buffer.  Dense caches grow into blocks pre-reserved at staging time
  (admission is the backpressure point — decode never stalls mid-flight on
  the pool); ring caches wrap in place; writes past the cache width are
  dropped like the dense path's out-of-bounds scatter.
- **copy-on-write** — a slot whose table maps blocks shared with another
  request (the scheduler's shared-prefix policy) never writes them: the
  first divergent write copies the shared payload into the privately
  reserved block, remaps the table entry (``KVPool.remap``), and drops the
  shared reference.  Shared payload rows therefore stay pristine at every
  PE, which is what makes skip-resident migration sound.

Non-paged state (SSM/recurrent tails, ring ``kpos``, cross/encoder KV)
keeps living in the slot bank's batched cache — per-request, never shared.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rma
from repro.serve.kvpool import KVLayout, KVPool


@functools.partial(jax.jit,
                   static_argnames=("layout", "data_off", "num_blocks"))
def assemble_pages(pool, pe, table, *, layout: KVLayout, data_off: int,
                   num_blocks: int):
    """Every paged leaf of the slots' decode cache, read from PE ``pe``'s
    row of the ``(npes, words)`` KV pool through ``table``
    (``(num_slots, blocks_per_request)`` block ids; an id of
    ``num_blocks`` or more reads zeros).

    One loop over the table's entries: each reads its block's words of
    every leaf, ``(1, n)`` windows at ``data_off + id * block_words``, and
    writes them straight into the leaves, so only the mapped blocks are
    read and neither the row nor a gathered copy of the blocks is ever
    materialised.  Returns the leaves in ``layout.paged`` order, each
    ``(reps, num_slots, width, nkv, hd)`` in the pool's dtype, bitwise
    what :func:`kvpool.insert_blocks` builds from the same blocks."""
    T, bw = layout.block_tokens, layout.block_words
    B, nb = table.shape
    ids = jnp.minimum(table, num_blocks - 1).reshape(-1)
    mapped = (table < num_blocks).reshape(-1)

    def block(k, leaves):
        b, j = k // nb, k % nb
        start = data_off + ids[k] * bw
        out = []
        for pl, leaf in zip(layout.paged, leaves):
            n = pl.words_per_token * T
            words = jax.lax.dynamic_slice(pool, (pe, start), (1, n))
            words = jnp.where(mapped[k], words, jnp.zeros((), pool.dtype))
            out.append(jax.lax.dynamic_update_slice(
                leaf, words.reshape(pl.reps, 1, T, pl.nkv, pl.hd),
                (0, b, j * T, 0, 0)))
            start = start + n
        return tuple(out)

    leaves = tuple(jnp.zeros((pl.reps, B, nb * T, pl.nkv, pl.hd), pool.dtype)
                   for pl in layout.paged)
    # two blocks a trip: 3.4 ms of wall time a call against 4.0 for one, at
    # qwen3-4b width on one TPU v5e (six a trip: 3.6)
    leaves = jax.lax.fori_loop(0, B * nb, block, leaves, unroll=2)
    return tuple(leaf[:, :, :pl.width]
                 for pl, leaf in zip(layout.paged, leaves))


@dataclasses.dataclass
class _SlotMap:
    """Host-side per-slot decode state: which request, which COW targets."""
    req_id: int
    cow: Dict[int, int]          # table index -> reserved private block id


class PagedDecodeView:
    """Per-decode-PE window onto the pool: block tables + COW bookkeeping.

    The view is control-plane only (host-side, like all pool metadata); the
    data plane is the decode PE's own row of the symmetric pool, touched
    exclusively through local loads/stores here.
    """

    def __init__(self, pool: KVPool, pe: int, num_slots: int):
        self.pool = pool
        self.pe = pe
        self.num_slots = num_slots
        self.slots: Dict[int, _SlotMap] = {}
        self.cow_copies = 0
        self.host_reads = 0      # blocking device-to-host reads (writeback)

    # ------------------------------------------------------------ lifecycle
    def attach(self, heap, slot: int, req_id: int, *,
               fresh_ids: List[int], cow: Dict[int, int]):
        """Arm a slot at admission: install its table mapping and zero the
        never-migrated growth blocks on this PE's row, so an assembled leaf
        is byte-identical to the virgin dense cache it replaces.  ``cow``
        maps table indices that decode will write but whose blocks are
        shared, to their pre-reserved private targets."""
        self.slots[slot] = _SlotMap(req_id=req_id, cow=dict(cow))
        for bid in fresh_ids:
            ptr = self.pool.block_ptr(bid)
            heap = heap.write(ptr, self.pe,
                              jnp.zeros((ptr.size,), jnp.dtype(ptr.dtype)))
        return heap

    def detach(self, slot: int) -> int:
        """Disarm a finished slot; releases COW reservations that never
        triggered (table references are the scheduler's to release).
        Returns the number of reserve blocks freed back to the pool."""
        sm = self.slots.pop(slot, None)
        if sm is None:
            return 0
        return self.pool.release_ids(list(sm.cow.values()))

    def detach_keep(self, slot: int) -> Dict[int, int]:
        """Disarm a *preempted* slot WITHOUT releasing its un-triggered COW
        reservations — the request keeps decoding later, so the reserves
        (and their references) travel with it and re-arm at resume via
        ``attach(cow=...)``.  Returns that surviving cow map."""
        sm = self.slots.pop(slot, None)
        return {} if sm is None else dict(sm.cow)

    def table_of(self, slot: int) -> List[int]:
        return self.pool.blocks_of(self.slots[slot].req_id)

    # ------------------------------------------------------------- assemble
    def table(self) -> np.ndarray:
        """The ``(num_slots, blocks_per_request)`` block table: each armed
        slot's block ids, ``num_blocks`` (read as zeros) everywhere else."""
        nb = self.pool.layout.blocks_per_request
        table = np.full((self.num_slots, nb), self.pool.num_blocks, np.int32)
        for s, sm in self.slots.items():
            ids = self.pool.blocks_of(sm.req_id)
            table[s, :len(ids)] = ids
        return table

    def assemble(self, heap, cache):
        """Rebuild every paged leaf of the batched decode cache from the
        pool row through the slot block tables, in one program
        (:func:`assemble_pages`).  Returns a new cache pytree; non-paged
        leaves pass through from ``cache`` untouched."""
        lay = self.pool.layout
        if not lay.paged:
            return cache
        leaves = assemble_pages(
            heap.pools[lay.kv_dtype], np.int32(self.pe), self.table(),
            layout=lay, data_off=self.pool.data.offset,
            num_blocks=self.pool.num_blocks)
        cache = dict(cache)
        blocks = [dict(e) for e in cache["blocks"]]
        for pl, leaf in zip(lay.paged, leaves):
            ref = blocks[pl.unit_idx][pl.key]
            blocks[pl.unit_idx][pl.key] = leaf.astype(ref.dtype)
        cache["blocks"] = blocks
        return cache

    def strip(self, cache):
        """Zero the paged leaves of a post-step cache: the pool row is the
        single source of truth, and the slot bank must never re-grow a
        dense copy (asserted by the tests)."""
        lay = self.pool.layout
        if not lay.paged:
            return cache
        cache = dict(cache)
        blocks = [dict(e) for e in cache["blocks"]]
        for pl in lay.paged:
            blocks[pl.unit_idx][pl.key] = jnp.zeros_like(
                blocks[pl.unit_idx][pl.key])
        cache["blocks"] = blocks
        return cache

    # ------------------------------------------------------------ writeback
    def writeback(self, ctx, heap, new_cache, pos, active):
        """Store each active slot's just-written K/V token column into its
        owning pool block.  ``pos`` is the PRE-step cursor (the position the
        decode step wrote).  Copy-on-write fires here, before the first
        store into a shared block."""
        lay = self.pool.layout
        if not lay.paged:
            return heap
        T, W = lay.block_tokens, lay.cache_width
        pos = np.asarray(pos)                  # blocking device-to-host read
        self.host_reads += 1
        for s in range(self.num_slots):
            if not active[s] or s not in self.slots:
                continue
            p = int(pos[s])
            idx = p % W if lay.ring else p
            if idx >= W:        # dense overrun: the scatter drops it
                continue
            b, t = idx // T, idx % T
            heap = self._cow(ctx, heap, s, b)
            bid = self.pool.blocks_of(self.slots[s].req_id)[b]
            ptr = self.pool.block_ptr(bid)
            payload = heap.read(ptr, self.pe)
            off = 0
            parts = []
            for pl in lay.paged:
                n = pl.words_per_token * T
                sl = payload[off:off + n].reshape(pl.reps, T, pl.nkv, pl.hd)
                col = new_cache["blocks"][pl.unit_idx][pl.key][:, s, idx]
                parts.append(sl.at[:, t].set(col.astype(sl.dtype))
                             .reshape(-1))
                off += n
            heap = heap.write(ptr, self.pe, jnp.concatenate(parts))
        return heap

    def _cow(self, ctx, heap, slot: int, b: int):
        """First divergent write into table index ``b``: copy the shared
        payload into the reserved private block (a local put on this PE,
        recorded on the ledger), remap the table, drop the shared ref."""
        sm = self.slots[slot]
        priv = sm.cow.pop(b, None)
        if priv is None:
            return heap
        src = self.pool.blocks_of(sm.req_id)[b]
        payload = heap.read(self.pool.block_ptr(src), self.pe)
        heap = rma.put(ctx, heap, self.pool.block_ptr(priv), payload,
                       self.pe, src_pe=self.pe)
        self.pool.remap(sm.req_id, b, priv)
        self.cow_copies += 1
        return heap
