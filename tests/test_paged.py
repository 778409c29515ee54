"""Paged decode + chunked streaming + shared-prefix policy (DESIGN.md §9).

Four guarantee families:

1. **streamed admission never reads ahead of its signal** — property-tested
   against the pending-queue oracle, chunk by chunk: blocks whose
   installment has not flushed read zero decode-side, the slot signal ramps
   monotonically with exactly the flushed installments, and the admission
   threshold gates until the stream closes.
2. **shared-prefix block mapping is refcount-correct** — two requests
   declaring the same prefix map the same physical blocks; copy-on-write
   fires before the first divergent write so shared payload rows stay
   pristine everywhere; eviction under pool starvation and mid-flight
   rotation never double-frees and never frees a block another live request
   still maps.
3. **the decode path really is paged** — assembled leaves come from the
   pool row (the slot banks never re-grow a dense K/V copy), and outputs
   stay bitwise-identical to the lockstep baseline throughout.
4. **assembly is one program over the mapped blocks** — byte-equal to the
   eager row-gather formula it replaced, compiled once for every table
   and decode PE.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import base as cfgbase
from repro.core import context, heap as heap_mod
from repro.models import kvcache, model
from repro.serve.engine import Engine, ServeConfig
from repro.serve.kvpool import KVPool
from repro.serve.paged_attn import PagedDecodeView, _SlotMap, assemble_pages
from repro.serve.kvxfer import EXTRA_SIGNALS, KVMigrator
from repro.serve.scheduler import DisaggScheduler

MAXLEN = 24


def _setup(arch="qwen3_4b", npes=4, num_blocks=32, max_slots=3,
           block_tokens=4):
    cfg = cfgbase.reduced(cfgbase.get_config(arch))
    params = model.init_params(jax.random.key(0), cfg)
    ctx, heap = context.init(npes=npes, node_size=npes)
    eng = Engine(cfg, params, max_len=MAXLEN)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=num_blocks,
                         max_slots=max_slots, block_tokens=block_tokens)
    return cfg, params, ctx, heap, eng, pool


def _sched(ctx, heap, eng, pool, *, decode_pes=(2, 3), num_slots=2, NEW=5,
           temperature=0.0, **kw):
    mig = KVMigrator(ctx, pool)
    return DisaggScheduler(
        ctx, heap, eng, pool, mig, prefill_pes=[0, 1],
        decode_pes=list(decode_pes), num_slots=num_slots,
        scfg=ServeConfig(max_new_tokens=NEW, temperature=temperature), **kw)


def _prompt(cfg, S=10, key=1):
    return jax.random.randint(jax.random.key(key), (1, S), 0, cfg.vocab_size)


# ---------------------------------------------------------------------------
# 1. streamed admission vs the pending-queue oracle
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(8, 14))
def test_stream_chunks_gate_on_signal(chunk, S):
    """Property: at every point of a chunked migration, (a) blocks whose
    installment has not flushed read zero at the decode PE, (b) the slot
    signal equals exactly the number of flushed wire blocks, and (c) the
    full admission threshold stays shut until the stream closes."""
    cfg, params, ctx, heap, eng, pool = _setup(max_slots=1)
    mig = KVMigrator(ctx, pool)
    tok, _, cache1 = eng.prefill_request({"tokens": _prompt(cfg, S)},
                                         jax.random.key(3))
    heap, ids = mig.stage(heap, 0, cache1, prompt_len=S, src_pe=0)
    stream = mig.open_stream(0, src_pe=0, dst_pe=1, slot=0, prompt_len=S,
                             first_token=tok)
    assert stream.pending == ids                 # everything staged travels
    sig = pool.sig_ptr(0)
    flushed = 0
    while stream.pending:
        heap = mig.stream_chunk(heap, stream, chunk)
        # issued but unflushed: nothing visible yet (pending-queue oracle)
        for bid in ids[flushed:]:
            np.testing.assert_array_equal(
                np.asarray(heap.read(pool.block_ptr(bid), 1)), 0.0)
        assert int(heap.read(sig, 1)) == flushed
        heap = mig.stream_flush(heap, stream)
        flushed = stream.sent
        # flushed installments landed, signal ramped to match...
        assert int(heap.read(sig, 1)) == flushed
        for bid in ids[:flushed]:
            np.testing.assert_array_equal(
                np.asarray(heap.read(pool.block_ptr(bid), 1)),
                np.asarray(heap.read(pool.block_ptr(bid), 0)))
        # ...and the admission threshold still gates (tail+header missing)
        heap, hdr = mig.try_admit(heap, 0, 1, stream.expected)
        assert hdr is None
    heap, rep = mig.stream_close(heap, stream)
    assert rep.expected_signal == len(ids) + EXTRA_SIGNALS
    heap, hdr = mig.try_admit(heap, 0, 1, rep.expected_signal)
    assert hdr == {"req_id": 0, "prompt_len": S, "first_token": tok,
                   "n_blocks": len(ids)}
    assert len(ctx.pending) == 0


def test_stream_flush_completes_only_this_slots_prefix():
    """flush_dependency semantics: draining one stream's chunk leaves ops
    submitted after its signal (another slot's traffic) on the queue."""
    cfg, params, ctx, heap, eng, pool = _setup(max_slots=2)
    mig = KVMigrator(ctx, pool)
    streams = []
    for rid in range(2):
        tok, _, c1 = eng.prefill_request({"tokens": _prompt(cfg, 8, key=rid)},
                                         jax.random.key(rid))
        heap, ids = mig.stage(heap, rid, c1, prompt_len=8, src_pe=0)
        streams.append(mig.open_stream(rid, src_pe=0, dst_pe=1, slot=rid,
                                       prompt_len=8, first_token=tok))
    heap = mig.stream_chunk(heap, streams[0], 1)
    heap = mig.stream_chunk(heap, streams[1], 1)   # queued after slot 0's
    heap = mig.stream_flush(heap, streams[0])
    assert int(heap.read(pool.sig_ptr(0), 1)) == 1
    # slot 1's chunk was submitted after slot 0's signal: still pending
    assert ctx.pending.pending_for(pool.sig_ptr(1), 1) is not None
    heap = mig.stream_flush(heap, streams[1])
    assert int(heap.read(pool.sig_ptr(1), 1)) == 1
    assert len(ctx.pending) == 0


# ---------------------------------------------------------------------------
# 2. shared prefix: mapping, copy-on-write, refcount-correct eviction
# ---------------------------------------------------------------------------


def test_shared_prefix_maps_same_blocks_bitwise():
    """Identical prompts declared as a whole-prompt prefix map the same
    physical blocks (one staging, one wire copy per decode PE) and still
    decode bitwise-identically to the lockstep baseline."""
    cfg, params, ctx, heap, eng, pool = _setup()
    NEW = 5
    # one decode PE so the second request lands where the prefix is resident
    sched = _sched(ctx, heap, eng, pool, decode_pes=[2], num_slots=3,
                   NEW=NEW, shared_prefix=True)
    p = _prompt(cfg, S=10)                       # 10 % 4 != 0: boundary COW
    for _ in range(3):
        sched.submit({"tokens": p}, prefix_len=10)
    outs = sched.run()
    st_ = sched.stats
    assert st_.prefix_hits == 2
    assert st_.blocks_prefix_shared == 2 * 3     # ceil(10/4) blocks each
    assert st_.bytes_wire_saved > 0              # resident blocks not re-sent
    assert st_.cow_copies == 3                   # every mapper COWs boundary
    base = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=NEW))
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(base[0]), outs[i])
    assert pool.stats()["blocks_in_use"] == 0    # refcounts fully unwound


def test_shared_prefix_with_divergent_suffixes():
    """Different prompts sharing only a declared prefix: full blocks inside
    the prefix are shared, the boundary is private, and each request still
    matches its own lockstep baseline."""
    cfg, params, ctx, heap, eng, pool = _setup()
    NEW = 4
    sched = _sched(ctx, heap, eng, pool, NEW=NEW, shared_prefix=True)
    P, S = 8, 12                                 # prefix = 2 full blocks (T=4)
    head = _prompt(cfg, S=P, key=5)
    prompts = []
    for i in range(3):
        tail = jax.random.randint(jax.random.key(20 + i), (1, S - P), 0,
                                  cfg.vocab_size)
        prompts.append(jnp.concatenate([head, tail], axis=1))
        sched.submit({"tokens": prompts[-1]}, prefix_len=P)
    outs = sched.run()
    assert sched.stats.prefix_hits == 2
    assert sched.stats.blocks_prefix_shared == 2 * (P // 4)
    assert sched.stats.cow_copies == 0           # boundary never shared here
    for i, p in enumerate(prompts):
        base = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=NEW))
        np.testing.assert_array_equal(np.asarray(base[0]), outs[i])
    assert pool.stats()["blocks_in_use"] == 0


def test_whole_prefix_after_partial_mapper_resends_boundary():
    """Regression: residency must be per (decode PE, block).  A non-whole
    mapper carries only the entry's full blocks to its decode PE; when a
    whole-prompt mapper lands on that PE afterwards, the boundary block is
    NOT resident there and must still travel — an all-or-nothing PE flag
    would skip it and decode against stale (zero) pool-row bytes."""
    cfg, params, ctx, heap, eng, pool = _setup()
    NEW = 4
    sched = _sched(ctx, heap, eng, pool, decode_pes=[2, 3], num_slots=2,
                   NEW=NEW, shared_prefix=True)
    P = 10                                       # 10 % 4 != 0: boundary block
    p = _prompt(cfg, S=P)
    tail = jax.random.randint(jax.random.key(33), (1, 4), 0, cfg.vocab_size)
    longer = jnp.concatenate([p, tail], axis=1)
    # round-robin slot pick: A->(2,0) registers the whole-prompt entry,
    # B->(3,0) maps only the 2 full blocks, C->(2,1) skips all 3, D->(3,1)
    # is whole-prompt on the PE where only B's partial set is resident
    sched.submit({"tokens": p}, prefix_len=P)        # A
    sched.submit({"tokens": longer}, prefix_len=P)   # B
    sched.submit({"tokens": p}, prefix_len=P)        # C
    sched.submit({"tokens": p}, prefix_len=P)        # D
    outs = sched.run()
    # C skipped 3 resident blocks; D skipped only B's 2 and re-sent the
    # boundary — 5 skips total (a PE-level flag would claim 6)
    assert (sched.stats.bytes_wire_saved
            == 5 * pool.layout.block_bytes)
    base_p = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=NEW))
    base_l = eng.generate({"tokens": longer}, ServeConfig(max_new_tokens=NEW))
    for rid, base in [(0, base_p), (1, base_l), (2, base_p), (3, base_p)]:
        np.testing.assert_array_equal(np.asarray(base[0]), outs[rid])
    assert pool.stats()["blocks_in_use"] == 0


def test_cow_keeps_shared_payload_pristine_under_divergence():
    """Sampled decoding makes the mapped requests genuinely diverge; the
    shared prefix blocks' payload at the decode PE must read identical to
    the staged payload at the prefill PE after every step — only COW makes
    that hold once decode starts writing the boundary block."""
    cfg, params, ctx, heap, eng, pool = _setup()
    sched = _sched(ctx, heap, eng, pool, decode_pes=[2], num_slots=2, NEW=6,
                   temperature=0.7, shared_prefix=True)
    p = _prompt(cfg, S=10)
    for _ in range(2):
        sched.submit({"tokens": p}, prefix_len=10)
    entry_blocks = None
    guard = 0
    while not sched.done():
        sched.step()
        guard += 1
        assert guard < 200
        if entry_blocks is None and sched.prefix_index:
            entry = next(iter(sched.prefix_index.values()))
            entry_blocks = (list(entry.block_ids), entry.home_pe)
        if entry_blocks is not None:
            ids, home = entry_blocks
            for bid in ids:
                if pool.refcount(bid) == 0:
                    continue                    # entry fully unwound
                np.testing.assert_array_equal(
                    np.asarray(sched.heap.read(pool.block_ptr(bid), 2)),
                    np.asarray(sched.heap.read(pool.block_ptr(bid), home)))
    assert sched.stats.cow_copies >= 1
    assert pool.stats()["blocks_in_use"] == 0


def _refcount_invariant(sched, pool):
    """Every mapped block has refcount == (#tables mapping it) + (#live COW
    reservations holding it) + (#prefix entries owning it); free-listed
    blocks are mapped by nobody."""
    expect = [0] * pool.num_blocks
    for ids in pool.block_tables.values():
        for i in ids:
            expect[i] += 1
    for view in sched.views.values():
        for sm in view.slots.values():
            for bid in sm.cow.values():
                expect[bid] += 1
    for req in sched.requests.values():
        for bid in req.cow_plan.values():
            expect[bid] += 1                    # reserved, not yet admitted
    for entry in sched.prefix_index.values():
        for bid in entry.block_ids:
            expect[bid] += 1
    for i in range(pool.num_blocks):
        assert pool.refcount(i) == expect[i], \
            f"block {i}: refcount {pool.refcount(i)} != mappers {expect[i]}"
        if pool.refcount(i) == 0:
            assert i in pool._free


def test_refcount_eviction_under_starvation_and_rotation():
    """The satellite guarantee: a pool sized so shared-prefix requests must
    wait for earlier evictions, driven through mid-flight rotation — no
    double-free (pool.release raises), no freeing a block another request
    still maps (invariant checked after every step), and the pool drains
    to empty with every stream matching the baseline."""
    cfg, params, ctx, heap, eng, pool = _setup(num_blocks=10, max_slots=2,
                                               block_tokens=4)
    NEW = 4
    sched = _sched(ctx, heap, eng, pool, decode_pes=[2, 3], num_slots=2,
                   NEW=NEW, shared_prefix=True, stream_chunks=1)
    p = _prompt(cfg, S=10)                       # 3 prompt blocks + COW
    other = _prompt(cfg, S=9, key=9)
    for i in range(6):
        if i % 2 == 0:
            sched.submit({"tokens": p}, prefix_len=10)
        else:
            sched.submit({"tokens": other})
    guard = 0
    while not sched.done():
        sched.step()
        _refcount_invariant(sched, pool)
        guard += 1
        assert guard < 300
    outs = {r: np.asarray(sched.requests[r].out, np.int32)
            for r in sched.requests}
    assert sched.stats.stalled_on_pool > 0 or sched.stats.stalled_on_slots > 0
    assert pool.stats()["blocks_in_use"] == 0
    base_p = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=NEW))
    base_o = eng.generate({"tokens": other}, ServeConfig(max_new_tokens=NEW))
    for i in range(6):
        base = base_p if i % 2 == 0 else base_o
        np.testing.assert_array_equal(np.asarray(base[0]), outs[i])


def test_pool_sharing_api_refcounts():
    """Unit semantics of the new pool surface: alloc_with_prefix increfs,
    reserve holds blocks outside tables, remap transfers the reservation in
    and drops the shared ref, release frees only at refcount zero."""
    cfg, params, ctx, heap, eng, pool = _setup(num_blocks=8)
    a = pool.alloc(1, 3)
    assert pool.free_blocks() == 5
    b = pool.alloc_with_prefix(2, a[:2], 4)
    assert b[:2] == a[:2] and len(b) == 4
    assert pool.refcount(a[0]) == 2 and pool.refcount(a[2]) == 1
    res = pool.reserve(1)
    assert pool.free_blocks() == 8 - 3 - 2 - 1
    # COW: request 2 swaps its view of a[1] for the reserve
    old = pool.remap(2, 1, res[0])
    assert old == a[1] and pool.refcount(a[1]) == 1
    assert pool.blocks_of(2)[1] == res[0] and pool.refcount(res[0]) == 1
    assert pool.release(1) == 2                  # a[1], a[2] free; a[0] shared
    assert pool.refcount(a[0]) == 1              # still mapped by request 2
    assert pool.release(2) == 4
    assert pool.free_blocks() == 8
    with pytest.raises(ValueError):
        pool.incref([a[0]])                      # incref on a free block
    assert pool.release_ids([]) == 0


def test_prefix_plan_refuses_multimodal_batches():
    """Sharability rule 4: a batch carrying non-token inputs never maps or
    registers a prefix — the token-keyed index cannot see the embeds that
    condition K/V via cross-attention."""
    from repro.serve.scheduler import Request
    cfg, params, ctx, heap, eng, pool = _setup()
    sched = _sched(ctx, heap, eng, pool, shared_prefix=True)
    tok = _prompt(cfg, S=8)
    mm = Request(rid=0, batch={"tokens": tok,
                               "audio_embeds": jnp.zeros((1, 4, 8))},
                 max_new=4, prefix_len=8)
    assert sched._prefix_plan(mm) == ([], None, 0)
    plain = Request(rid=1, batch={"tokens": tok}, max_new=4, prefix_len=8)
    ids, key, n = sched._prefix_plan(plain)
    assert key is not None and n == 2            # 8 tokens = 2 full blocks


def test_submit_rejects_unschedulable_cow_request():
    """A whole-prompt unaligned prefix needs table + 1 blocks (the COW
    reserve); a pool of exactly table-many blocks must reject the request
    upfront instead of wedging the scheduler re-queueing it forever."""
    NEW = 4
    cfg, params, ctx, heap, eng, pool = _setup(num_blocks=4, block_tokens=4)
    assert pool.layout.blocks_for_decode(10, NEW) == 4
    sched = _sched(ctx, heap, eng, pool, NEW=NEW, shared_prefix=True)
    p = _prompt(cfg, S=10)                       # 10 % 4 != 0: boundary COW
    with pytest.raises(ValueError):
        sched.submit({"tokens": p}, prefix_len=10)
    # a multimodal batch never shares (rule 4), so no reserve is demanded
    # and the same-sized request must stay schedulable
    sched2 = _sched(ctx, heap, eng, pool, NEW=NEW, shared_prefix=True)
    sched2.submit({"tokens": p, "audio_embeds": jnp.zeros((1, 2, 4))},
                  prefix_len=10)
    sched.submit({"tokens": p})                  # no prefix: fits exactly
    sched.run()


def test_blocks_for_decode_growth():
    cfg, params, ctx, heap, eng, pool = _setup(block_tokens=4)
    lay = pool.layout
    assert not lay.ring
    assert lay.blocks_for_decode(10, 0) == lay.blocks_for_prompt(10) == 3
    assert lay.blocks_for_decode(10, 6) == 4     # writes reach pos 14
    # the final sampled token is never written back: 9 + 4 tokens end the
    # last write at pos 11, squarely inside block 2 — no dead fourth block
    assert lay.blocks_for_decode(9, 4) == 3
    assert lay.blocks_for_decode(10, 100) == lay.blocks_per_request  # capped


# ---------------------------------------------------------------------------
# 3. the decode path really is paged
# ---------------------------------------------------------------------------


def test_assembled_leaves_equal_dense_rehydrate():
    """The bitwise-identity mechanism itself: after admission, the paged
    view's assembled cache equals what insert_blocks would have rehydrated
    — byte for byte."""
    from repro.serve import kvpool as kvpool_mod
    cfg, params, ctx, heap, eng, pool = _setup()
    mig = KVMigrator(ctx, pool)
    sched = _sched(ctx, heap, eng, pool, decode_pes=[2], num_slots=2, NEW=5)
    p = _prompt(cfg, S=10)
    sched.submit({"tokens": p})
    guard = 0
    while not sched.stats.admissions and guard < 50:
        sched.step()
        guard += 1
    view = sched.views[2]
    bank = sched.banks[2]
    assembled = view.assemble(sched.heap, bank.cache)
    rid = next(iter(pool.block_tables))
    payloads, tail = mig.gather(sched.heap, rid, 0, 2)
    dense = kvpool_mod.insert_blocks(pool.layout, bank.cache, 0, payloads)
    for pl in pool.layout.paged:
        np.testing.assert_array_equal(
            np.asarray(assembled["blocks"][pl.unit_idx][pl.key][:, 0]),
            np.asarray(dense["blocks"][pl.unit_idx][pl.key][:, 0]))
    sched.run()


def test_growth_blocks_receive_decode_writes():
    """A prompt whose generation crosses a block boundary writes generated
    K/V into growth blocks that were never migrated — decode output still
    matches the baseline, and the growth blocks end up non-zero."""
    cfg, params, ctx, heap, eng, pool = _setup(block_tokens=4)
    NEW = 7                                      # writes pos 10..15: block 3
                                                 # is pure growth
    sched = _sched(ctx, heap, eng, pool, decode_pes=[2], num_slots=1,
                   NEW=NEW)
    p = _prompt(cfg, S=10)
    sched.submit({"tokens": p})
    touched = {}
    guard = 0
    while not sched.done():
        sched.step()
        guard += 1
        assert guard < 100
        for rid, ids in pool.block_tables.items():
            grown = [i for i in ids if pool.home_of(i) is None]
            for bid in grown:
                val = np.abs(np.asarray(
                    sched.heap.read(pool.block_ptr(bid), 2),
                    np.float32)).max()
                touched[bid] = max(touched.get(bid, 0.0), float(val))
    assert touched and max(touched.values()) > 0
    base = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=NEW))
    np.testing.assert_array_equal(np.asarray(base[0]),
                                  np.asarray(sched.requests[0].out))


# ---------------------------------------------------------------------------
# 4. assembly reads the pool in place, in one program
# ---------------------------------------------------------------------------


def _eager_assemble(view, heap, cache):
    """The eager assembly formula the jitted program replaced, in numpy:
    the PE's whole pool row as ``(num_blocks, block_words)``, a trailing
    zero page for unmapped entries, a row gather through the table, then
    each leaf cut, transposed and reshaped."""
    pool, lay = view.pool, view.pool.layout
    row = np.asarray(heap.pools[lay.kv_dtype])[view.pe]
    data = row[pool.data.offset:pool.data.offset
               + pool.num_blocks * lay.block_words].reshape(
                   pool.num_blocks, lay.block_words)
    data = np.concatenate([data, np.zeros((1, lay.block_words), data.dtype)])
    nb, T = lay.blocks_per_request, lay.block_tokens
    table = np.full((view.num_slots, nb), pool.num_blocks, np.int32)
    for s, sm in view.slots.items():
        ids = pool.blocks_of(sm.req_id)
        table[s, :len(ids)] = ids
    pay = data[table]
    blocks = [dict(e) for e in cache["blocks"]]
    off = 0
    for pl in lay.paged:
        n = pl.words_per_token * T
        leaf = pay[:, :, off:off + n].reshape(
            view.num_slots, nb, pl.reps, T, pl.nkv, pl.hd)
        off += n
        leaf = leaf.transpose(2, 0, 1, 3, 4, 5).reshape(
            pl.reps, view.num_slots, nb * T, pl.nkv, pl.hd)[:, :, :pl.width]
        ref = blocks[pl.unit_idx][pl.key]
        blocks[pl.unit_idx][pl.key] = leaf.astype(ref.dtype)
    return blocks


def _assert_same_bytes(view, heap, cache):
    got = view.assemble(heap, cache)["blocks"]
    want = _eager_assemble(view, heap, cache)
    for pl in view.pool.layout.paged:
        g = np.asarray(got[pl.unit_idx][pl.key])
        w = want[pl.unit_idx][pl.key]
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))


def _filled_view(attention, block_tokens, num_slots, pe=2, num_blocks=23):
    """A decode view over a pool whose every word is random, with slots
    armed on tables of several lengths (slot 1 of three left unarmed)."""
    cfg = cfgbase.reduced(cfgbase.get_config("qwen3_4b"))
    if attention == "swa":
        cfg = dataclasses.replace(cfg, attention="swa", window=10)
    ctx, heap = context.init(npes=4, node_size=4)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=num_blocks,
                         max_slots=num_slots, block_tokens=block_tokens)
    assert pool.layout.ring == (attention == "swa")
    dt = pool.layout.kv_dtype
    heap = heap.write_all(heap_mod.SymPtr(dt, 0, heap.pools[dt].shape[1:]),
                          jax.random.normal(jax.random.key(3),
                                            heap.pools[dt].shape))
    view = PagedDecodeView(pool, pe, num_slots)
    nb = pool.layout.blocks_per_request
    for s in range(num_slots):
        if num_slots == 3 and s == 1:
            continue                              # an unarmed slot: all zeros
        n = max(1, nb - 2 * s)                    # short tables leave zeros
        pool.alloc(100 + s, n)
        view.slots[s] = _SlotMap(req_id=100 + s, cow={})
    cache = kvcache.init_cache(cfg, num_slots, MAXLEN)
    return view, heap, cache


@pytest.mark.parametrize("num_slots", [1, 3])
@pytest.mark.parametrize("attention,block_tokens", [
    ("full", 4), ("full", 5), ("swa", 4)])
def test_assemble_is_byte_equal_to_the_eager_formula(attention, block_tokens,
                                                     num_slots):
    """Dense and ring layouts, a ragged last block (5 does not divide the
    24-token cache), unmapped table entries and an unarmed slot: the one
    program gives the eager formula's bytes."""
    view, heap, cache = _filled_view(attention, block_tokens, num_slots)
    _assert_same_bytes(view, heap, cache)


def test_assemble_is_byte_equal_around_copy_on_write():
    """A shared-prefix run with diverging sampled decodes: at every step,
    before and after the copy-on-write remaps a table entry, the assembled
    cache of every decode PE equals the eager formula's."""
    cfg, params, ctx, heap, eng, pool = _setup()
    sched = _sched(ctx, heap, eng, pool, decode_pes=[2], num_slots=2, NEW=6,
                   temperature=0.7, shared_prefix=True)
    p = _prompt(cfg, S=10)
    for _ in range(2):
        sched.submit({"tokens": p}, prefix_len=10)
    shared = cowed = 0
    guard = 0
    while not sched.done():
        sched.step()
        guard += 1
        assert guard < 200
        view = sched.views[2]
        tables = [pool.blocks_of(sm.req_id) for sm in view.slots.values()]
        if len(tables) == 2 and set(tables[0]) & set(tables[1]):
            shared += 1
        cowed += view.cow_copies > 0 and len(tables) > 0
        _assert_same_bytes(view, sched.heap, sched.banks[2].cache)
    assert shared and cowed and sched.stats.cow_copies >= 1


def test_assemble_pages_compiles_once_across_tables_and_pes():
    """Changing tables and both decode PEs reuse one program: the PE and
    the table are traced, only the layout and the pool's place are
    static."""
    view, heap, cache = _filled_view("full", 4, 3, num_blocks=29)
    before = assemble_pages._cache_size()
    for step in range(4):
        for pe in (2, 3):
            view.pe = pe
            _assert_same_bytes(view, heap, cache)
        view.pool.release(view.slots[0].req_id)      # slot 0 re-admits
        view.pool.alloc(200 + step, 1 + step)
        view.slots[0] = _SlotMap(req_id=200 + step, cow={})
    assert assemble_pages._cache_size() - before == 1
