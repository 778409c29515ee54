"""Cluster frontend: traffic engine, router, SLO admission, preemption.

The load-bearing guarantee rides along from the disagg stack: *whatever*
schedule the frontend produces — randomized routing, priority reordering,
mid-decode preemption and resume, parked slot-less streams — every
completed request's decode output stays bitwise-identical to the single-PE
``Engine.generate`` baseline (greedy decoding).  The frontend only decides
WHAT runs next; the migration protocol decides WHAT the bytes are.
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.core import context
from repro.models import model
from repro.serve.engine import Engine, ServeConfig
from repro.serve.frontend import (Fleet, FleetConfig, SLOPolicy, TenantSpec,
                                  TrafficEngine, load_fleet_env, percentile)
from repro.serve.frontend import slo as slo_mod
from repro.serve.kvpool import KVPool
from repro.serve.kvxfer import KVMigrator
from repro.serve.scheduler import (DECODING, FINISHED, SHED, DisaggScheduler,
                                   Request)

MAXLEN = 24
NEW = 4


@functools.lru_cache(maxsize=1)
def _engine():
    """One engine (and one set of jitted closures) for the whole module."""
    cfg = cfgbase.reduced(cfgbase.get_config("qwen3_4b"))
    params = model.init_params(jax.random.key(0), cfg)
    return cfg, Engine(cfg, params, max_len=MAXLEN)


def _fleet(**over):
    cfg, engine = _engine()
    kw = dict(n_pods=2, prefill_per_pod=1, decode_per_pod=2, num_slots=2,
              kv_blocks=96, block_tokens=4, max_len=MAXLEN, max_new=NEW,
              stream_chunks=1, admission="slo", router="affinity", seed=11)
    kw.update(over)
    return Fleet(FleetConfig(**kw), engine=engine)


MIX = (TenantSpec("chat", weight=2.0, prompt_lens=(8,), max_new=(NEW,),
                  slo="interactive"),
       TenantSpec("scan", weight=1.0, prompt_lens=(12,), max_new=(NEW,),
                  slo="batch", shared_prefix_prob=0.5, prefix_groups=1))


# ---------------------------------------------------------------------------
# traffic engine
# ---------------------------------------------------------------------------


def test_traffic_schedule_is_deterministic():
    """Identical (seed, tenants, rate) tuples produce bitwise-identical
    schedules — including the shared prefix-group prompts — and different
    seeds genuinely differ."""
    cfg, _ = _engine()
    a = TrafficEngine(list(MIX), rate=1.0, vocab=cfg.vocab_size, seed=5)
    b = TrafficEngine(list(MIX), rate=1.0, vocab=cfg.vocab_size, seed=5)
    sa, sb = a.schedule(16), b.schedule(16)
    assert len(sa) == len(sb) > 0
    for x, y in zip(sa, sb):
        assert (x.step, x.tenant, x.slo, x.max_new, x.prefix_len) == \
            (y.step, y.tenant, y.slo, y.max_new, y.prefix_len)
        np.testing.assert_array_equal(x.tokens, y.tokens)
    c = TrafficEngine(list(MIX), rate=1.0, vocab=cfg.vocab_size, seed=6)
    sc = c.schedule(16)
    assert [s.step for s in sc] != [s.step for s in sa] or \
        any(not np.array_equal(x.tokens, y.tokens)
            for x, y in zip(sa, sc))


def test_traffic_bursty_and_mix_accounting():
    """Bursty arrivals cluster (higher variance than poisson at the same
    mean-ish rate); offered_load tallies tenants/classes; shared-prefix
    requests re-use the group prompt with a whole-prompt prefix."""
    cfg, _ = _engine()
    eng = TrafficEngine(list(MIX), rate=1.0, vocab=cfg.vocab_size, seed=9,
                        process="bursty", burst_len=4, burst_factor=4.0)
    specs = eng.schedule(64)
    counts = np.bincount([s.step for s in specs], minlength=64)
    assert counts.var() > counts.mean()          # overdispersed vs poisson
    load = eng.offered_load(specs)
    assert load["requests"] == len(specs)
    assert set(load["by_slo"]) <= {"interactive", "batch"}
    shared = [s for s in specs if s.prefix_len > 0]
    assert shared and all(s.prefix_len == s.prompt_len for s in shared)
    # every shared spec of the one group is the identical prompt
    keys = {s.prefix_key() for s in shared}
    assert len(keys) == 1


def test_fleet_env_knobs():
    env = {"ISHMEM_FLEET_PODS": "3", "ISHMEM_FLEET_ROUTER": "least_loaded",
           "ISHMEM_FLEET_ADMISSION": "fcfs",
           "ISHMEM_FLEET_QUEUE_BOUND": "7", "ISHMEM_FLEET_SEED": "2"}
    cfg = load_fleet_env(env)
    assert (cfg.pods, cfg.router, cfg.admission, cfg.queue_bound,
            cfg.seed) == (3, "least_loaded", "fcfs", 7, 2)
    assert load_fleet_env({}).router == "affinity"
    with pytest.raises(ValueError):
        load_fleet_env({"ISHMEM_FLEET_ROUTER": "psychic"})
    with pytest.raises(ValueError):
        load_fleet_env({"ISHMEM_FLEET_QUEUE_BOUND": "0"})


# ---------------------------------------------------------------------------
# SLO policy units (no model, no heap)
# ---------------------------------------------------------------------------


def _req(rid, slo, arrival=0, out_len=0, state=DECODING, preempts=0):
    r = Request(rid=rid, batch={"tokens": np.zeros((1, 4), np.int32)},
                max_new=NEW, slo=slo)
    r.arrival_step = arrival
    r.out = [1] * out_len
    r.state = state
    r.preemptions = preempts
    return r


def test_slo_policy_orders_sheds_and_preempts():
    pol = SLOPolicy(queue_bound=2)
    # priority beats FIFO; deadline breaks ties inside a class
    q = [_req(0, "batch", arrival=0), _req(1, "interactive", arrival=5),
         _req(2, "interactive", arrival=3)]
    assert pol.select(q) == 2
    # shed: best-effort past queue_bound, everything past the hard bound
    assert not pol.admit(_req(3, "batch"), queue_len=2)
    assert pol.admit(_req(3, "interactive"), queue_len=2)
    assert not pol.admit(_req(3, "interactive"), queue_len=4)
    # preemption: only over-budget best-effort victims, most progress first
    decoding = [_req(4, "batch", out_len=3), _req(5, "batch", out_len=1),
                _req(6, "interactive", out_len=9)]
    victim = pol.preempt_victim(_req(7, "interactive"), decoding)
    assert victim.rid == 4
    # best effort never preempts; exhausted victims are immune
    assert pol.preempt_victim(_req(8, "batch"), decoding) is None
    immune = [_req(9, "batch", out_len=3, preempts=pol.max_preemptions)]
    assert pol.preempt_victim(_req(10, "interactive"), immune) is None
    # unknown class names resolve to the default, not an error
    assert slo_mod.resolve("no-such-class").name == slo_mod.DEFAULT_CLASS


# ---------------------------------------------------------------------------
# preemption: bitwise resume on one scheduler
# ---------------------------------------------------------------------------


def _sched(ctx, heap, eng, pool, **kw):
    mig = KVMigrator(ctx, pool)
    kw.setdefault("prefill_pes", [0, 1])
    kw.setdefault("decode_pes", [2, 3])
    kw.setdefault("num_slots", 1)
    kw.setdefault("scfg", ServeConfig(max_new_tokens=NEW))
    return DisaggScheduler(ctx, heap, eng, pool, mig, **kw)


def test_preemption_resume_is_bitwise():
    """A batch request is preempted mid-decode by a later interactive
    request (1 slot/PE forces the contention) and resumes on the same PE;
    BOTH streams match their uninterrupted Engine.generate baselines."""
    cfg, eng = _engine()
    ctx, heap = context.init(npes=4, node_size=4)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=48, max_slots=2,
                         block_tokens=4)
    sched = _sched(ctx, heap, eng, pool,
                   scfg=ServeConfig(max_new_tokens=12),
                   policy=SLOPolicy(queue_bound=64))
    prompts = [jax.random.randint(jax.random.key(k), (1, 10), 0,
                                  cfg.vocab_size) for k in range(3)]
    sched.submit({"tokens": prompts[0]}, max_new=12, slo="batch")
    sched.submit({"tokens": prompts[1]}, max_new=12, slo="batch")
    # let the batch requests occupy both decode slots and generate a bit
    for _ in range(4):
        sched.step()
    assert all(r.state == DECODING for r in sched.requests.values())
    sched.submit({"tokens": prompts[2]}, max_new=4, slo="interactive")
    outs = sched.run()
    assert sched.stats.preempts >= 1
    assert sched.stats.resumes == sched.stats.preempts
    preempted = [r for r in sched.requests.values() if r.preemptions]
    assert preempted and all(r.state == FINISHED for r in preempted)
    for rid, (p, n) in enumerate([(prompts[0], 12), (prompts[1], 12),
                                  (prompts[2], 4)]):
        base = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=n))
        np.testing.assert_array_equal(np.asarray(base[0]), outs[rid])
    assert pool.stats()["blocks_in_use"] == 0


def test_preemption_with_shared_prefix_and_cow():
    """Preempting a mapper of a shared prefix keeps its un-triggered COW
    reservation alive across the park (refcounts stay exact), and resumed
    decode still matches the baseline — the COW fires post-resume."""
    cfg, eng = _engine()
    ctx, heap = context.init(npes=4, node_size=4)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=48, max_slots=2,
                         block_tokens=4)
    sched = _sched(ctx, heap, eng, pool, decode_pes=[2],
                   scfg=ServeConfig(max_new_tokens=10), shared_prefix=True,
                   policy=SLOPolicy(queue_bound=64))
    p = jax.random.randint(jax.random.key(1), (1, 10), 0, cfg.vocab_size)
    sched.submit({"tokens": p}, max_new=10, prefix_len=10, slo="batch")
    for _ in range(3):
        sched.step()
    batch_req = sched.requests[0]
    assert batch_req.state == DECODING
    sched.submit({"tokens": p}, max_new=4, prefix_len=10, slo="interactive")
    outs = sched.run()
    assert sched.stats.preempts >= 1
    base10 = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=10))
    base4 = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=4))
    np.testing.assert_array_equal(np.asarray(base10[0]), outs[0])
    np.testing.assert_array_equal(np.asarray(base4[0]), outs[1])
    assert pool.stats()["blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# parked slot-less streams
# ---------------------------------------------------------------------------


def test_parked_stream_beats_whole_prefill_at_one_slot():
    """The ROADMAP open item: with ONE slot per decode PE, streamed blocks
    park in the pool (no slot held while draining) and the slot binds only
    at close — so the admission wire window still shrinks vs whole-prefill
    hand-off, where the old slot-bound streams used to tie."""
    cfg, eng = _engine()

    def run(stream):
        ctx, heap = context.init(npes=4, node_size=4)
        pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=64, max_slots=2,
                             block_tokens=4)
        sched = _sched(ctx, heap, eng, pool, num_slots=1,
                       stream_chunks=stream, admit_delay_steps=1)
        for k in range(4):
            sched.submit({"tokens": jax.random.randint(
                jax.random.key(k), (1, 12), 0, cfg.vocab_size)})
        outs = sched.run()
        return sched, outs

    s_whole, outs_w = run(0)
    s_stream, outs_s = run(1)
    for rid in outs_w:
        np.testing.assert_array_equal(outs_w[rid], outs_s[rid])
    whole = np.mean(s_whole.stats.ttfd_model_s)
    stream = np.mean(s_stream.stats.ttfd_model_s)
    assert stream < whole
    # stream signal words were all recycled and zeroed
    assert len(s_stream.pool._stream_free) == s_stream.pool.max_streams
    for i in range(s_stream.pool.max_streams):
        for pe in (2, 3):
            assert int(s_stream.heap.read(
                s_stream.pool.stream_sig_ptr(i), pe)) == 0


def test_stream_signal_exhaustion_backpressures():
    """A pool with ONE stream-signal word serializes streams: staging
    stalls (stalled_on_streams) instead of wedging, and every request
    still completes bitwise-correct."""
    cfg, eng = _engine()
    ctx, heap = context.init(npes=4, node_size=4)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=64, max_slots=2,
                         block_tokens=4, max_streams=1)
    sched = _sched(ctx, heap, eng, pool, num_slots=1, stream_chunks=1)
    prompts = [jax.random.randint(jax.random.key(k), (1, 12), 0,
                                  cfg.vocab_size) for k in range(4)]
    for p in prompts:
        sched.submit({"tokens": p})
    outs = sched.run()
    assert sched.stats.stalled_on_streams > 0
    for i, p in enumerate(prompts):
        base = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=NEW))
        np.testing.assert_array_equal(np.asarray(base[0]), outs[i])


# ---------------------------------------------------------------------------
# queue-delay accounting (the t_arrival satellite)
# ---------------------------------------------------------------------------


def test_arrival_time_threads_into_latency_stats():
    """A request submitted with an arrival_step in the past reports its
    end-to-end steps from ARRIVAL (queue time included)."""
    cfg, eng = _engine()
    ctx, heap = context.init(npes=4, node_size=4)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=48, max_slots=2,
                         block_tokens=4)
    sched = _sched(ctx, heap, eng, pool)
    p = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab_size)
    sched.submit({"tokens": p}, arrival_step=-5, t_arrival=-1.0)
    sched.run()
    st = sched.stats
    req = sched.requests[0]
    assert req.finish_step >= req.admit_step >= req.prefill_step
    assert st.e2e_steps[0] == req.finish_step + 5


# ---------------------------------------------------------------------------
# fleet end-to-end
# ---------------------------------------------------------------------------


def _baseline(eng, spec):
    base = eng.generate({"tokens": spec.tokens},
                        ServeConfig(max_new_tokens=spec.max_new))
    return np.asarray(base[0])


@pytest.mark.parametrize("router,admission,seed",
                         [("random", "slo", 11), ("round_robin", "fcfs", 13),
                          ("affinity", "slo", 17)])
def test_fleet_outputs_bitwise_under_any_routing(router, admission, seed):
    """The acceptance property: random/rr/affinity routing x FCFS/SLO
    admission (with preemption and shared prefixes in play) — every
    completed request equals its single-PE baseline bitwise."""
    cfg, eng = _engine()
    fleet = _fleet(router=router, admission=admission, seed=seed,
                   num_slots=1, queue_bound=64)
    traffic = TrafficEngine(list(MIX), rate=1.0, vocab=cfg.vocab_size,
                            seed=seed)
    specs = traffic.schedule(10)
    rep = fleet.run(specs, max_steps=1500)
    assert rep["completed"] == rep["offered"] == len(specs) > 0
    outs = fleet.outputs()
    for spec in specs:
        np.testing.assert_array_equal(_baseline(eng, spec),
                                      np.asarray(outs[spec.idx], np.int32))
    # the shared pool fully unwinds across all pods
    assert fleet.pool.stats()["blocks_in_use"] == 0


def test_fleet_slo_beats_fcfs_and_sheds_past_bound():
    """Same overloaded schedule twice: SLO strictly improves interactive
    p99 TTFD-from-arrival, and with a tight queue bound sheds fire and
    terminate as SHED (not wedged)."""
    cfg, eng = _engine()
    heavy = (TenantSpec("chat", prompt_lens=(8,), max_new=(NEW,),
                        slo="interactive"),
             TenantSpec("scan", prompt_lens=(12,), max_new=(12,),
                        slo="batch"))
    reports = {}
    for admission in ("fcfs", "slo"):
        fleet = _fleet(admission=admission, router="least_loaded",
                       num_slots=1, queue_bound=3, kv_blocks=128,
                       stream_chunks=2, max_new=NEW)
        traffic = TrafficEngine(list(heavy), rate=3.0,
                                vocab=cfg.vocab_size, seed=23)
        reports[admission] = fleet.run(traffic.schedule(16), max_steps=2500)
        if admission == "slo":
            sheds = [r for pod in fleet.pods
                     for r in pod.sched.requests.values()
                     if r.state == SHED]
            assert len(sheds) == reports["slo"]["shed"]
    slo_p99 = reports["slo"]["by_class"]["interactive"]["ttfd_p99_steps"]
    fcfs_p99 = reports["fcfs"]["by_class"]["interactive"]["ttfd_p99_steps"]
    assert slo_p99 < fcfs_p99
    assert reports["slo"]["shed"] > 0
    assert reports["slo"]["preempts"] >= 1


def test_fleet_affinity_reduces_cross_pod_bytes():
    """Prefix-affinity routing vs seeded-random routing on a shared-prefix
    workload: the affinity arm pulls fewer bytes across the pod boundary
    (the proxy ring carries the difference)."""
    cfg, eng = _engine()
    tenants = (TenantSpec("samples", prompt_lens=(12,), max_new=(NEW,),
                          slo="standard", shared_prefix_prob=0.8,
                          prefix_groups=1),)
    bytes_x = {}
    for router in ("random", "affinity"):
        fleet = _fleet(router=router, seed=5)
        traffic = TrafficEngine(list(tenants), rate=0.6,
                                vocab=cfg.vocab_size, seed=5)
        rep = fleet.run(traffic.schedule(20), max_steps=1500)
        bytes_x[router] = rep["wire"]["bytes_cross_pod"]
        assert rep["completed"] == rep["offered"]
    assert bytes_x["random"] > 0
    assert bytes_x["affinity"] < bytes_x["random"]


# ---------------------------------------------------------------------------
# proxy-ring saturation (cross-pod migration storms)
# ---------------------------------------------------------------------------


def test_migration_storm_backpressures_bounded_ring():
    """A cross-pod migration storm through a tiny (2-slot) host-proxy ring
    must backpressure — the flush drains the ring mid-run instead of
    wedging or dropping — and every stream still decodes bitwise-correct.
    Write-combining is disabled (`ISHMEM_NBI_COALESCE=0` A/B mode) so every
    block is its own ring message: a run of 3 blocks posts 3 consecutive
    puts, which is guaranteed to fill 2 slots mid-flush.  (With coalescing
    on, a run is ONE message and the data-before-flag rule drains the ring
    before each signal — the ring can never saturate, by design.)"""
    import dataclasses as _dc
    from repro.core.proxy import HostProxy
    from repro.core import teams
    cfg, eng = _engine()
    ctx, heap = context.init(npes=4, node_size=2)   # decode PEs in pod 2
    ctx.tuning = _dc.replace(ctx.tuning, nbi_coalesce=False)
    pool = KVPool.create(heap, cfg, MAXLEN, num_blocks=64, max_slots=3,
                         block_tokens=4)
    proxy = HostProxy(ctx, slots=2)
    mig = KVMigrator(ctx, pool, proxy=proxy)
    pre, dec = teams.disagg_partition(teams.world(4), 2)
    sched = DisaggScheduler(ctx, heap, eng, pool, mig,
                            prefill_pes=pre.pes(), decode_pes=dec.pes(),
                            num_slots=3, scfg=ServeConfig(max_new_tokens=NEW),
                            admit_delay_steps=1)
    prompts = [jax.random.randint(jax.random.key(k), (1, 12), 0,
                                  cfg.vocab_size) for k in range(6)]
    for p in prompts:                   # 6 x (3 blocks + tail + header) puts
        sched.submit({"tokens": p})
    outs = sched.run()
    assert proxy.backpressure > 0       # the ring filled and drained mid-run
    assert proxy.ring.overwrite_errors == 0
    assert len(proxy.ring.delivered) == len(set(
        i for i, _ in proxy.ring.delivered))        # exactly-once
    for i, p in enumerate(prompts):
        base = eng.generate({"tokens": p}, ServeConfig(max_new_tokens=NEW))
        np.testing.assert_array_equal(np.asarray(base[0]), outs[i])


def test_percentile_helper():
    assert percentile([], 99) == 0.0
    assert percentile([3.0], 50) == 3.0
    xs = list(range(1, 101))
    assert percentile(xs, 50) == pytest.approx(50.5)
    assert percentile(xs, 99) == pytest.approx(99.01)
    assert percentile(xs, 0) == 1.0 and percentile(xs, 100) == 100.0
