"""Device-resident symmetric heap (paper §III-E).

The PGAS address space is modeled as per-dtype pools of shape
``(npes, words)``: every PE sees an identically laid-out region (symmetric),
and a ``SymPtr`` (dtype, offset, shape) is valid at *every* PE — exactly the
OpenSHMEM symmetric-heap contract.  Allocation metadata lives host-side (the
paper: "memory management APIs are host-only"); data updates are functional.

On real hardware the ``npes`` axis is the mesh: each PE owns its row, and the
kernels in ``repro.kernels`` move rows across chips.  On CPU the whole array
is materialized, which makes every op testable against a numpy oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

ALIGN = 128  # lane-aligned allocations (TPU minor dim = 128)


class SymPtr(NamedTuple):
    dtype: str
    offset: int
    shape: tuple

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape:
            n *= int(s)
        return n

    @property
    def nbytes(self) -> int:
        return self.size * jnp.dtype(self.dtype).itemsize

    def index(self, i: int) -> "SymPtr":
        """Pointer to element i of a flattened buffer (for AMOs)."""
        if not 0 <= i < self.size:
            raise IndexError(i)
        return SymPtr(self.dtype, self.offset + i, ())


@dataclasses.dataclass
class StoreTally:
    """Whole-pool rebuilds of one heap and every functional copy made from
    it: each store (``write``, ``write_all``, put, signal, flush) replaces a
    whole ``(npes, words)`` pool array in :meth:`SymmetricHeap.replace_pool`.
    Copies share the one object, so a reader takes deltas across a region."""
    stores: int = 0
    store_bytes: int = 0


@dataclasses.dataclass
class SymmetricHeap:
    """Functional symmetric heap.  Mutating ops return a new heap."""

    npes: int
    pools: dict                    # dtype str -> (npes, words) jnp array
    _cursor: dict = dataclasses.field(default_factory=dict)
    _free: dict = dataclasses.field(default_factory=dict)
    words_per_pool: int = 1 << 20
    tally: StoreTally = dataclasses.field(default_factory=StoreTally)

    # ----------------------------------------------------------- allocation
    def malloc(self, shape, dtype) -> SymPtr:
        """shmem_malloc: symmetric, collective over all PEs (host-only API).

        Contents of a reused free-list region are UNDEFINED (the OpenSHMEM
        contract); use :meth:`calloc` for guaranteed zeros."""
        # canonicalize (JAX without x64: 64-bit symmetric objects narrow to
        # 32-bit — documented TPU adaptation; TPUs natively prefer 32-bit)
        dt = jax.dtypes.canonicalize_dtype(jnp.dtype(dtype)).name
        shape = tuple(int(s) for s in shape)
        n = 1
        for s in shape:
            n *= s
        n_aligned = max(ALIGN, -(-n // ALIGN) * ALIGN)
        # first-fit over the free list
        for i, (off, sz) in enumerate(self._free.get(dt, [])):
            if sz >= n_aligned:
                self._free[dt].pop(i)
                if sz > n_aligned:
                    self._free[dt].append((off + n_aligned, sz - n_aligned))
                return SymPtr(dt, off, shape)
        cur = self._cursor.get(dt, 0)
        if dt not in self.pools:
            self.pools[dt] = jnp.zeros((self.npes, self.words_per_pool),
                                       jnp.dtype(dt))
        if cur + n_aligned > self.pools[dt].shape[1]:
            # grow the pool (doubling)
            new_words = max(self.pools[dt].shape[1] * 2,
                            cur + n_aligned)
            pad = jnp.zeros((self.npes, new_words - self.pools[dt].shape[1]),
                            jnp.dtype(dt))
            self.pools[dt] = jnp.concatenate([self.pools[dt], pad], axis=1)
        self._cursor[dt] = cur + n_aligned
        return SymPtr(dt, cur, shape)

    def calloc(self, shape, dtype) -> SymPtr:
        """shmem_calloc: like malloc but the region reads zero at every PE.

        malloc may hand back a reused free-list region still holding a freed
        buffer's bytes, so the whole aligned span is explicitly zeroed here.
        The pool update mutates this heap in place — allocation is a host-only
        collective, not a one-sided data op, so the functional-update rule for
        data movement does not apply (snapshots taken via replace_pool/write
        keep their own pools dict and are unaffected)."""
        ptr = self.malloc(shape, dtype)
        n_aligned = max(ALIGN, -(-ptr.size // ALIGN) * ALIGN)
        pool = self.pools[ptr.dtype]
        self.pools[ptr.dtype] = pool.at[
            :, ptr.offset:ptr.offset + n_aligned].set(0)
        return ptr

    def free(self, ptr: SymPtr) -> None:
        """Return the aligned span to the free list, coalescing with adjacent
        free entries so repeated alloc/free cycles don't fragment the pool."""
        n = max(ALIGN, -(-ptr.size // ALIGN) * ALIGN)
        entries = sorted(self._free.setdefault(ptr.dtype, [])
                         + [(ptr.offset, n)])
        merged = [entries[0]]
        for off, sz in entries[1:]:
            last_off, last_sz = merged[-1]
            if last_off + last_sz == off:
                merged[-1] = (last_off, last_sz + sz)
            else:
                merged.append((off, sz))
        self._free[ptr.dtype] = merged

    # ----------------------------------------------------------- accounting
    def stats(self) -> dict:
        """Allocator accounting: bytes in use / free / reserved plus a
        fragmentation index per dtype pool (0 = one free extent, ->1 = free
        space shattered across many extents).  Consumed by the paged KV pool
        and the serving benchmarks."""
        per_dtype = {}
        tot_used = tot_free = tot_reserved = 0
        for dt, pool in self.pools.items():
            item = jnp.dtype(dt).itemsize
            cursor = self._cursor.get(dt, 0)
            free_spans = self._free.get(dt, [])
            free_words = sum(sz for _, sz in free_spans)
            largest = max((sz for _, sz in free_spans), default=0)
            used_words = cursor - free_words
            frag = 1.0 - largest / free_words if free_words else 0.0
            per_dtype[dt] = {
                "bytes_in_use": used_words * item,
                "bytes_free": free_words * item,
                "bytes_reserved": cursor * item,
                "capacity_bytes": pool.shape[1] * item,
                "free_extents": len(free_spans),
                "largest_free_bytes": largest * item,
                "fragmentation": frag,
            }
            tot_used += used_words * item
            tot_free += free_words * item
            tot_reserved += cursor * item
        return {
            "npes": self.npes,
            "bytes_in_use": tot_used,
            "bytes_free": tot_free,
            "bytes_reserved": tot_reserved,
            "pools": per_dtype,
        }

    # ----------------------------------------------------------- access
    def read(self, ptr: SymPtr, pe) -> jnp.ndarray:
        """Local load of the buffer as seen at PE ``pe``: one slice of the
        ``(npes, words)`` pool, ``ptr.size`` words long; no PE row is copied
        out first.  A negative ``pe`` counts from the end; a Python int out
        of ``[-npes, npes)`` raises IndexError; a traced one is clamped."""
        pool = self.pools[ptr.dtype]
        if isinstance(pe, int) and not -pool.shape[0] <= pe < pool.shape[0]:
            raise IndexError(f"PE {pe} of {pool.shape[0]}")
        return _heap_read(pool, pe, ptr.offset, tuple(ptr.shape))

    def write(self, ptr: SymPtr, pe, value) -> "SymmetricHeap":
        value = jnp.asarray(value, jnp.dtype(ptr.dtype)).reshape((ptr.size,))
        pool = self.pools[ptr.dtype].at[pe, ptr.offset:ptr.offset + ptr.size] \
            .set(value)
        return self.replace_pool(ptr.dtype, pool)

    def read_all(self, ptr: SymPtr) -> jnp.ndarray:
        """(npes, *shape) view of the buffer across every PE."""
        flat = self.pools[ptr.dtype][:, ptr.offset:ptr.offset + ptr.size]
        return flat.reshape((self.npes,) + ptr.shape)

    def write_all(self, ptr: SymPtr, values) -> "SymmetricHeap":
        values = jnp.asarray(values, jnp.dtype(ptr.dtype)).reshape(
            (self.npes, ptr.size))
        pool = self.pools[ptr.dtype].at[:, ptr.offset:ptr.offset + ptr.size] \
            .set(values)
        return self.replace_pool(ptr.dtype, pool)

    def replace_pool(self, dt, pool) -> "SymmetricHeap":
        pools = dict(self.pools)
        pools[dt] = pool
        self.tally.stores += 1
        self.tally.store_bytes += pool.nbytes
        return SymmetricHeap(self.npes, pools, dict(self._cursor),
                             {k: list(v) for k, v in self._free.items()},
                             self.words_per_pool, self.tally)


@functools.partial(jax.jit, static_argnames=("shape",))
def _heap_read(pool, pe, offset, shape):
    size = math.prod(shape)
    # dynamic_slice wraps a negative start and clamps one out of range
    flat = jax.lax.dynamic_slice(pool, (pe, offset), (1, max(size, 1)))
    return flat[0, :size].reshape(shape)


def create(npes: int, words_per_pool: int = 1 << 20) -> SymmetricHeap:
    """shmemx_heap_create analogue: device-resident symmetric heap."""
    return SymmetricHeap(npes, {}, {}, {}, words_per_pool)
