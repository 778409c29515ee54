"""Spans and counters on the serve step's host path.

- the heap's store tally: every store rebuilds a whole pool, counted once
  in ``SymmetricHeap.replace_pool`` and shared by every functional copy;
- the scheduler's counters: its own steps' heap stores (pods sharing one
  heap never count a store twice) and its blocking host reads on the
  decode path;
- the profiler spans of a scheduler step, their names and nesting;
- profiling off: the shared no-op scope, no label computed, and the same
  tokens and counters as profiling on;
- the jitted prefill and decode carry stable program names;
- the decode scope's size label comes from the host, not a device read,
  and host spans stay out of the calibration reports.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.core import context, rma
from repro.models import kvcache, model
from repro.obs import calibrate
from repro.obs import prof as prof_mod
from repro.obs.prof import ProfClock, Profiler
from repro.serve.engine import Engine, ServeConfig
from repro.serve.frontend import (Fleet, FleetConfig, SLOPolicy, TenantSpec,
                                  TrafficEngine)
from repro.serve.kvpool import KVPool
from repro.serve.kvxfer import KVMigrator
from repro.serve.scheduler import DisaggScheduler
from repro.tune import telemetry

MAXLEN = 24
NEW = 4
PHASES = ("sched_recover", "sched_prefill", "sched_admit", "sched_resume",
          "sched_decode")


@functools.lru_cache(maxsize=1)
def _engine():
    cfg = cfgbase.reduced(cfgbase.get_config("qwen3_4b"))
    params = model.init_params(jax.random.key(0), cfg)
    return cfg, Engine(cfg, params, max_len=MAXLEN)


def _sched(*, stream_chunks=0, prof=None):
    """One pod of 2 prefill + 2 decode PEs, 1 slot each, three requests."""
    cfg, eng = _engine()
    ctx, heap = context.init(npes=4, node_size=4)
    if prof is not None:
        prof.attach(ctx)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=48, max_slots=1,
                         block_tokens=4)
    sched = DisaggScheduler(ctx, heap, eng, pool, KVMigrator(ctx, pool),
                            prefill_pes=[0, 1], decode_pes=[2, 3],
                            num_slots=1, stream_chunks=stream_chunks,
                            scfg=ServeConfig(max_new_tokens=NEW))
    for i, S in enumerate((8, 12, 8)):
        sched.submit({"tokens": jax.random.randint(
            jax.random.key(i), (1, S), 0, cfg.vocab_size)})
    return sched


# ---------------------------------------------------------------------------
# heap store tally
# ---------------------------------------------------------------------------


def test_put_adds_one_store_of_the_pool_bytes():
    ctx, heap = context.init(npes=2, node_size=2)
    ptr = heap.malloc((256,), jnp.float32)
    pool_bytes = heap.pools["float32"].nbytes
    t = heap.tally
    stores, nbytes = t.stores, t.store_bytes
    new = rma.put(ctx, heap, ptr, jnp.ones((256,), jnp.float32), 1)
    assert new is not heap and new.tally is t
    assert (t.stores - stores, t.store_bytes - nbytes) == (1, pool_bytes)
    np.testing.assert_array_equal(np.asarray(new.read(ptr, 1)), 1.0)


def test_tally_is_shared_across_functional_copies():
    """Stores through any copy, in any order, land in the one tally: the
    copies are successive versions of the same memory."""
    _, heap = context.init(npes=2, node_size=2)
    f = heap.malloc((128,), jnp.float32)
    i = heap.malloc((128,), jnp.int32)
    t = heap.tally
    stores, nbytes = t.stores, t.store_bytes
    a = heap.write(f, 0, jnp.zeros((128,), jnp.float32))
    b = a.write_all(i, jnp.ones((2, 128), jnp.int32))
    c = heap.write(f, 1, jnp.ones((128,), jnp.float32))   # an older version
    assert a.tally is b.tally is c.tally is t
    assert t.stores - stores == 3
    assert t.store_bytes - nbytes == (2 * heap.pools["float32"].nbytes
                                      + heap.pools["int32"].nbytes)


# ---------------------------------------------------------------------------
# scheduler counters
# ---------------------------------------------------------------------------


def test_sched_counts_its_stores_and_host_reads(monkeypatch):
    """``host_reads`` is one token read per decoded token plus one
    position read per paged decode call; ``heap_stores`` is every store
    the run made into the heap."""
    sched = _sched()
    calls = []
    real = sched.engine.decode_slots_paged

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(sched.engine, "decode_slots_paged", counted)
    t = sched.heap.tally
    stores, nbytes = t.stores, t.store_bytes
    sched.run()
    st = sched.stats
    assert st.decode_tokens > 0 and len(calls) > 0
    assert st.host_reads == st.decode_tokens + len(calls)
    assert st.heap_stores == t.stores - stores > 0
    assert st.heap_store_bytes == t.store_bytes - nbytes


def test_pods_sharing_a_heap_count_each_store_once():
    cfg, engine = _engine()
    fleet = Fleet(FleetConfig(
        n_pods=2, prefill_per_pod=1, decode_per_pod=2, num_slots=2,
        kv_blocks=96, block_tokens=4, max_len=MAXLEN, max_new=NEW,
        stream_chunks=1, admission="slo", router="round_robin", seed=11),
        engine=engine)
    traffic = TrafficEngine(
        [TenantSpec("chat", prompt_lens=(8, 12), max_new=(NEW,))],
        rate=1.0, vocab=cfg.vocab_size, seed=3)
    t = fleet.heap.tally
    stores, nbytes = t.stores, t.store_bytes
    fleet.run(traffic.schedule(6), max_steps=500)
    per_pod = [p.sched.stats.heap_stores for p in fleet.pods]
    assert all(n > 0 for n in per_pod)
    assert sum(per_pod) == t.stores - stores
    assert sum(p.sched.stats.heap_store_bytes
               for p in fleet.pods) == t.store_bytes - nbytes


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class _Ticks(ProfClock):
    """A clock that advances one tick per reading."""

    def __init__(self):
        self.t = 0

    def now(self):
        self.t += 1
        return float(self.t)


class _Intervals(Profiler):
    """Keeps each closed scope's (op, start, end) on the fake clock."""

    def __init__(self):
        super().__init__(clock=_Ticks(), sink_records=False)
        self.spans = []

    def _close(self, sc, wall_s, model_s):
        self.spans.append((sc.op, sc._t0, sc._t0 + wall_s))
        super()._close(sc, wall_s, model_s)


def _inside(spans, child, parent):
    """Every ``child`` span lies inside some ``parent`` span."""
    outer = [(s, e) for op, s, e in spans if op == parent]
    kids = [(s, e) for op, s, e in spans if op == child]
    return kids and all(any(ps < s and e < pe for ps, pe in outer)
                        for s, e in kids)


def test_span_names_and_nesting():
    pf = _Intervals()
    sched = _sched(stream_chunks=1, prof=pf)
    sched.run()
    spans = pf.spans
    names = {op for op, _, _ in spans}
    assert set(PHASES) | {"serve_prefill", "stream_flush", "kv_stage",
                          "serve_decode", "paged_attn", "kv_assemble",
                          "kv_writeback", "token_read",
                          "sched_evict"} <= names
    # one step: the five phases, in order, back to back and never nested
    phases = [(op, s, e) for op, s, e in spans if op in PHASES]
    assert len(phases) == len(PHASES) * sched._step
    for k in range(0, len(phases), len(PHASES)):
        step = phases[k:k + len(PHASES)]
        assert tuple(op for op, _, _ in step) == PHASES
        assert all(a[2] < b[1] for a, b in zip(step, step[1:]))
    # kv_assemble and kv_writeback are siblings of paged_attn in decode
    for child in ("kv_assemble", "kv_writeback", "paged_attn"):
        assert _inside(spans, child, "serve_decode")
    for child in ("serve_decode", "token_read"):
        assert _inside(spans, child, "sched_decode")
    for child in ("serve_prefill", "stream_flush", "kv_stage"):
        assert _inside(spans, child, "sched_prefill")
    assert _inside(spans, "sched_evict", "token_read")
    asm = [s for op, s, _ in spans if op == "kv_assemble"]
    att = [s for op, s, _ in spans if op == "paged_attn"]
    wb = [s for op, s, _ in spans if op == "kv_writeback"]
    assert all(a < b < c for a, b, c in zip(asm, att, wb))
    # host spans carry the host labels; the decode scope its KV bytes
    by_op = {s.op: s for s in pf.samples}
    assert (by_op["kv_assemble"].path, by_op["kv_assemble"].nbytes) == (
        "host", 0)
    assert by_op["serve_decode"].nbytes > 0


def test_scope_off_is_the_null_scope_and_computes_no_label():
    ctx, _ = context.init(npes=2, node_size=2)

    def label():
        raise AssertionError("a label was computed with profiling off")

    assert ctx.prof is None
    sc = prof_mod.scope(ctx, "serve_decode", label)
    assert sc is prof_mod.NULL_PROF.scope("x", nbytes=0)
    with sc as ps:
        obj = object()
        assert ps(obj) is obj


def test_profiling_off_changes_no_token_and_no_counter():
    off = _sched(stream_chunks=1)
    pf = Profiler(sink_records=False)
    on = _sched(stream_chunks=1, prof=pf)
    outs_off, outs_on = off.run(), on.run()
    assert outs_off.keys() == outs_on.keys()
    for rid in outs_off:
        np.testing.assert_array_equal(outs_off[rid], outs_on[rid])
    assert off.stats == on.stats
    assert off.ctx.prof is None and pf.samples


# ---------------------------------------------------------------------------
# program names
# ---------------------------------------------------------------------------


def test_jitted_prefill_and_decode_have_stable_names():
    cfg, eng = _engine()
    cache = kvcache.init_cache(cfg, 2, MAXLEN)
    dec = eng._decode.lower(eng.params, jnp.zeros((2, 1), jnp.int32),
                            jnp.zeros((2,), jnp.int32), cache).as_text()
    pre = eng._prefill.lower(eng.params,
                             {"tokens": jnp.zeros((1, 8), jnp.int32)},
                             kvcache.init_cache(cfg, 1, MAXLEN)).as_text()
    assert "module @jit_decode_step" in dec
    assert "module @jit_prefill" in pre
    assert "jit__lambda" not in dec + pre


# ---------------------------------------------------------------------------
# labels from the host, host spans out of the calibration reports
# ---------------------------------------------------------------------------


def _preempting_sched(prof):
    """Two batch requests hold both 1-slot decode PEs; a later interactive
    request preempts one of them, which resumes at its saved cursor."""
    cfg, eng = _engine()
    ctx, heap = context.init(npes=4, node_size=4)
    prof.attach(ctx)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=48, max_slots=2,
                         block_tokens=4)
    sched = DisaggScheduler(ctx, heap, eng, pool, KVMigrator(ctx, pool),
                            prefill_pes=[0, 1], decode_pes=[2, 3],
                            num_slots=1, policy=SLOPolicy(queue_bound=64),
                            scfg=ServeConfig(max_new_tokens=12))
    prompts = [jax.random.randint(jax.random.key(k), (1, 10), 0,
                                  cfg.vocab_size) for k in range(3)]
    for p in prompts[:2]:
        sched.submit({"tokens": p}, max_new=12, slo="batch")
    for _ in range(4):
        sched.step()
    sched.submit({"tokens": prompts[2]}, max_new=4, slo="interactive")
    sched.run()
    assert sched.stats.preempts >= 1


def _faulted_fleet(prof):
    """A decode PE dies mid-stream: its requests re-migrate or recompute
    and replay their decoded-so-far tokens."""
    cfg, engine = _engine()
    fleet = Fleet(FleetConfig(
        n_pods=2, prefill_per_pod=1, decode_per_pod=2, num_slots=2,
        kv_blocks=96, block_tokens=4, max_len=MAXLEN, max_new=NEW,
        stream_chunks=1, admission="fcfs", router="affinity", seed=11,
        queue_bound=64), engine=engine, fault_plan="kill_pe=4@5")
    prof.attach(fleet.ctx)
    mix = [TenantSpec("chat", weight=2.0, prompt_lens=(8,), max_new=(NEW,)),
           TenantSpec("scan", weight=1.0, prompt_lens=(12,),
                      max_new=(NEW,), shared_prefix_prob=0.5,
                      prefix_groups=1)]
    fleet.run(TrafficEngine(mix, rate=1.0, vocab=cfg.vocab_size,
                            seed=11).schedule(6))
    replayed = sum(p.sched.stats.replayed_tokens
                   for p in fleet.pods + fleet.dead_pods)
    assert replayed > 0


@pytest.mark.parametrize("run", (
    lambda pf: _sched(stream_chunks=1, prof=pf).run(),
    _preempting_sched, _faulted_fleet), ids=("plain", "preempt", "replay"))
def test_decode_label_counts_positions_on_the_host(run, monkeypatch):
    """The ``serve_decode`` label's context tokens come from each slot
    request's ``decode_pos``, and equal the device's slot positions through
    admission, preemption and resume, and a fault's replay."""
    _, eng = _engine()
    on_device = []
    real = eng.decode_slots_paged

    def decode(slots, *a, **kw):
        on_device.append(int(slots.pos[slots.active].sum()))
        return real(slots, *a, **kw)

    monkeypatch.setattr(eng, "decode_slots_paged", decode)
    pf = Profiler(sink_records=False)
    run(pf)
    tokens = [s.nbytes for s in pf.samples if s.op == "serve_decode"]
    per_token = tokens[0] // on_device[0]
    assert len(tokens) == len(on_device) > 0
    assert tokens == [n * per_token for n in on_device]


def test_host_spans_leave_the_calibration_reports_unchanged():
    """A run's host spans enclose its priced scopes; the divergence report,
    the measured overlay and the wall-clock telemetry read as if the host
    spans were not there."""
    pf = Profiler()
    sched = _sched(stream_chunks=1, prof=pf)
    sched.run()
    hosts = [s for s in pf.samples if s.path == prof_mod.HOST_PATH]
    others = [s for s in pf.samples if s.path != prof_mod.HOST_PATH]
    assert hosts and others
    assert any(s.model_s > 0 for s in hosts)
    assert (calibrate.report_from_samples(pf.samples)
            == calibrate.report_from_samples(others))
    assert (calibrate.measured_overlay(pf.samples)
            == calibrate.measured_overlay(others))
    wall = sched.ctx.telemetry.sources[telemetry.WALLCLOCK_SOURCE]
    assert {op for op, *_ in wall} == {s.op for s in others}
