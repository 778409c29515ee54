"""Serve a cell's traffic through the program's ``Fleet`` on the wall clock.

Set-up builds the configuration at its stated widths with the benchmark's
seeded weights (one jitted call on the device), the program's ``Engine`` and
``Fleet`` with the cell's pod layout and pool, and drains a warm-up schedule
through that same fleet so that every program the window will run is
compiled or loaded before it opens.

The window drives ``Fleet.step``: each step submits the arrivals that have
fallen due, then every output token that reached the host in that step is
stamped with the step's end.  Times run from when each request was due, so
the wait a slow step imposes on later requests counts.

After the window: the peak device memory is read, a seeded sample of the
finished requests (the longest among them) is kept on the host, the
program's state is dropped, and the plain reference of the configuration's
model family scores each served token by how far its logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import statistics
import time

import numpy as np

from . import traffic as traffic_mod, weights
from .trace import annotate
from .session import Outcome, log, peak_memory

#: host spans the traced run records (outermost first): the harness's own,
#: then the program's wall-clock profiler scopes; the configuration's
#: family appends its own (``SPANS``)
ACTIVITIES = ("fleet_step", "arrivals_wait", "stamp", "serve_prefill",
              "stream_flush", "serve_decode", "paged_attn")


class _Annotated:
    """A profiler scope that is also a ``TraceAnnotation`` of its name, so
    the program's scopes land on the device trace's clock."""

    def __init__(self, scope, name):
        import jax
        self._scope = scope
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self._scope.__enter__()
        return self._scope

    def __exit__(self, *exc):
        r = self._scope.__exit__(*exc)
        self._ann.__exit__(*exc)
        return r


def traced_profiler():
    from repro.obs.prof import Profiler

    class TracedProfiler(Profiler):
        def scope(self, op, **kw):
            return _Annotated(super().scope(op, **kw), op)

    return TracedProfiler(sink_records=False)


class Serving:
    """Set-up, window and check of one serving cell."""

    def __init__(self, sess):
        self.sess = sess
        self.cfg = sess.config
        self.family = sess.family
        self.fl = sess.workload["fleet"]

    # -------------------------------------------------------------- set-up
    def setup(self):
        import jax
        from repro.models import model
        from repro.serve.engine import Engine
        from repro.serve.frontend import Fleet, FleetConfig
        sess, watch = self.sess, self.sess.watch
        self.arch = sess.arch
        t0, m0 = time.monotonic(), watch.mark()
        shapes = jax.eval_shape(
            lambda: model.init_params(jax.random.key(0), self.arch))
        params = jax.block_until_ready(weights.make_params(shapes,
                                                           sess.seed))
        self._phase("weights (one jitted call)", t0, m0)
        fl = self.fl
        fcfg = FleetConfig(
            arch=self.arch.name, n_pods=fl.get("n_pods", 1),
            prefill_per_pod=fl["prefill_per_pod"],
            decode_per_pod=fl["decode_per_pod"], num_slots=fl["num_slots"],
            kv_blocks=fl["kv_blocks"], block_tokens=fl["block_tokens"],
            max_len=fl["max_len"], max_new=max(sess.traffic["output_lens"]),
            stream_chunks=fl.get("stream_chunks", 1),
            admission=fl.get("admission", "slo"))
        t0, m0 = time.monotonic(), watch.mark()
        self.engine = Engine(self.arch, params, max_len=fl["max_len"])
        self.fleet = Fleet(fcfg, engine=self.engine)
        self._phase("engine + fleet (pool on the heap)", t0, m0)
        slots = fl["num_slots"] * fl["decode_per_pod"] * fl.get("n_pods", 1)
        warm = traffic_mod.warmup(sess.traffic, sess.seed,
                                  self.cfg["vocab_size"], at_least=slots + 1)
        # the traced run's profiler scopes sync and read counts on the
        # host: let them compile in set-up too
        self.prof = (traced_profiler().attach(self.fleet.ctx)
                     if sess.trace else None)
        t0, m0 = time.monotonic(), watch.mark()
        self._drain(warm)
        self._phase(f"warm-up: {len(warm)} requests drained", t0, m0)
        t0, m0 = time.monotonic(), watch.mark()
        k = self._warm_transfers()
        self._phase(f"warm-up: coalesced transfers of 1..{k} blocks", t0, m0)

    def _warm_transfers(self) -> int:
        """Compile the heap transfers a completion-queue flush can build.
        The queue merges queue-adjacent puts to contiguous blocks into one
        transfer, so a flush in the window may move any run of 1 up to a
        request's whole table of blocks, each length its own program.  Put
        every length once, with the library's own ``put_nbi`` and
        ``quiet``, into free blocks of a decode PE's pool row."""
        import jax.numpy as jnp
        from repro.core import rma
        fleet = self.fleet
        pool, lay = fleet.pool, fleet.pool.layout
        if pool.free_blocks() != pool.num_blocks:
            raise RuntimeError("the pool must be empty after the warm-up")
        k_max = min(lay.blocks_per_request, pool.num_blocks)
        sched = fleet.pods[0].sched
        src, dst = sched.prefill_pes[0], sched.decode_pes[0]
        zero = jnp.zeros((lay.block_words,), jnp.dtype(lay.kv_dtype))
        heap = fleet.heap
        for k in range(1, k_max + 1):
            for b in range(k):
                heap = rma.put_nbi(fleet.ctx, heap, pool.block_ptr(b), zero,
                                   dst, src_pe=src)
            heap = rma.quiet(fleet.ctx, heap)
        fleet.heap = heap
        for pod in fleet.pods:
            pod.sched.heap = heap
        return k_max

    def _phase(self, what, t0, m0):
        d = self.sess.watch.mark().since(m0)
        log(f"set-up: {what}: {time.monotonic() - t0:.3f} s, "
            f"{d.compiles} compile(s) {d.compile_s:.3f} s, cache "
            f"{d.hits} hit(s) {d.misses} miss(es), {d.traces} trace(s)")
        return d

    def _specs(self, batch):
        from repro.serve.frontend.traffic import RequestSpec
        slo = self.fl.get("slo", "standard")
        return [RequestSpec(idx=a.idx, step=self.fleet.elapsed_steps,
                            tenant="bench", slo=slo, tokens=a.tokens,
                            max_new=a.max_new, prefix_len=0)
                for a in batch]

    def _drain(self, batch):
        fleet = self.fleet
        base = fleet.placements.copy()
        fleet.step(self._specs(batch))
        while not fleet.done():
            fleet.step()
        fleet.placements = base

    def _request(self, idx):
        pod, rid = self.fleet.placements[idx]
        return next(p for p in self.fleet.pods
                    if p.name == pod).sched.requests[rid]

    # -------------------------------------------------------------- window
    def window(self, arrivals):
        sess, fleet = self.sess, self.fleet
        traced = sess.trace
        if traced:
            self.prof.samples.clear()
        stats0 = self._stats()
        step0 = fleet.elapsed_steps
        step_t = {}
        stamps = {a.idx: [] for a in arrivals}
        late, live, reqs = [], [], {}
        n, i = len(arrivals), 0
        m0 = sess.watch.mark()
        sess.window_opens()
        t0 = time.monotonic()
        while True:
            t = time.monotonic() - t0
            if t >= sess.seconds:
                break
            batch = []
            while i < n and arrivals[i].due_s <= t:
                batch.append(arrivals[i])
                i += 1
            if not batch and fleet.done():
                nxt = arrivals[i].due_s if i < n else sess.seconds
                with annotate("arrivals_wait", traced):
                    time.sleep(max(0.0, min(nxt, sess.seconds) - t))
                continue
            late += [t - a.due_s for a in batch]
            step_t[fleet.elapsed_steps] = t
            with annotate("fleet_step", traced):
                fleet.step(self._specs(batch))
            t_end = time.monotonic() - t0
            with annotate("stamp", traced):
                for a in batch:
                    reqs[a.idx] = self._request(a.idx)
                    live.append(a.idx)
                still = []
                for idx in live:
                    req = reqs[idx]
                    got = len(req.out) - len(stamps[idx])
                    stamps[idx] += [t_end] * got
                    if req.state not in ("finished", "shed"):
                        still.append(idx)
                live = still
        t_close = time.monotonic() - t0
        d = sess.watch.mark().since(m0)
        self.window_marks = d
        if traced:
            fleet.ctx.prof = None
        lat = sorted(late) or [0.0]
        log(f"window: {t_close:.3f} s, {fleet.elapsed_steps - step0} fleet "
            f"steps, {i}/{n} arrivals submitted; compiles inside the window: "
            f"{d.in_window} ({d.compiles} compile(s), {d.hits} cache "
            f"hit(s), {d.traces} trace(s))")
        log(f"open-loop generator lateness: median "
            f"{statistics.median(lat) * 1e3:.3f} ms, max "
            f"{lat[-1] * 1e3:.3f} ms over {len(late)} submissions")
        return dict(t_close=t_close, stamps=stamps, reqs=reqs,
                    step_t=step_t, step0=step0, stats0=stats0,
                    stats1=self._stats(),
                    prof=list(self.prof.samples) if traced else [])

    def _stats(self) -> dict:
        tot = {}
        for pod in self.fleet.pods:
            for k, v in vars(pod.sched.stats).items():
                if isinstance(v, int):
                    tot[k] = tot.get(k, 0) + v
        return tot

    # ------------------------------------------------------------ results
    def end_to_end(self, arrivals, w) -> dict:
        t_close, stamps = w["t_close"], w["stamps"]
        ttft, gaps, tokens = [], [], 0
        for a in arrivals:
            s = stamps[a.idx]
            ttft.append((s[0] if s else t_close) - a.due_s)
            gaps += [b - c for b, c in zip(s[1:], s[:-1])]
            tokens += len(s)
        out = {"output_tokens_per_s": tokens / t_close}
        if gaps:
            out["itl_p95_ms"] = pct(gaps, 95) * 1e3
        if ttft:
            out["ttft_p50_ms"] = pct(ttft, 50) * 1e3
        first = sum(1 for a in arrivals if stamps[a.idx])
        done = sum(1 for r in w["reqs"].values() if r.state == "finished")
        log(f"requests due {len(arrivals)}, with a first token {first}, "
            f"finished {done}, shed {self.failed(w)}; {tokens} output "
            f"tokens, {len(gaps)} gaps")
        log("end to end: " + ", ".join(f"{k} {v:.6g}" for k, v in
                                       sorted(out.items())))
        return out

    def finish_started(self, w) -> None:
        """After the close, untimed: step the fleet until every request
        that had its first token by the close has finished, so that the
        check can read whole answers of long requests too."""
        started = [r for idx, r in w["reqs"].items() if w["stamps"][idx]]
        t0, steps = time.monotonic(), 0
        while any(r.state not in ("finished", "shed") for r in started):
            self.fleet.step()
            steps += 1
        log(f"after the close: {steps} untimed steps "
            f"({time.monotonic() - t0:.3f} s) finished the "
            f"{len(started)} requests the window had started")

    def failed(self, w) -> int:
        return sum(1 for r in w["reqs"].values() if r.state == "shed")

    def records(self, arrivals, w) -> dict:
        """What the per-layer readers read (traced run).  Work is counted
        by the configuration's family, which the records carry for the
        readers' own counts."""
        cfg, fam, step0 = self.cfg, self.family, w["step0"]
        s0, s1 = w["stats0"], w["stats1"]
        by_idx = {a.idx: a for a in arrivals}
        waits, fl = [], 0.0
        for idx, req in w["reqs"].items():
            a = by_idx[idx]
            if req.prefill_step >= step0 and req.prefill_step in w["step_t"]:
                waits.append(w["step_t"][req.prefill_step] - a.due_s)
                fl += fam.prefill_flops(cfg, a.tokens.shape[1])
            # each token but the first (which the prefill made) is a decode
            # step at context prompt + k - 1
            S = a.tokens.shape[1]
            for k in range(1, len(w["stamps"][idx])):
                fl += fam.token_flops(cfg, S + k - 1)
        delta = {k: s1.get(k, 0) - s0.get(k, 0) for k in s1}
        return dict(kind="serve", cfg=cfg, family=fam, fleet=self.fl,
                    window_s=w["t_close"], sched=delta, queue_wait_s=waits,
                    model_flops=fl, prof=w["prof"], peaks=self.sess.peaks)

    # -------------------------------------------------------------- check
    def sample(self, w):
        """Seeded sample of finished requests, the longest among them, up
        to ``check.sample_tokens`` served tokens."""
        chk = self.sess.workload["check"]
        done = [(idx, r) for idx, r in sorted(w["reqs"].items())
                if r.state == "finished"]
        if not done:
            return []
        longest = max(done, key=lambda x: (x[1].prompt_len + len(x[1].out),
                                           -x[0]))
        rng = np.random.default_rng([self.sess.seed, 7])
        order = [longest] + [done[j] for j in rng.permutation(len(done))
                             if done[j] is not longest]
        out, n = [], 0
        for idx, r in order:
            if n >= chk["sample_tokens"]:
                break
            prompt = np.asarray(r.batch["tokens"])[0]
            served = np.asarray(r.out, np.int32)
            out.append((idx, prompt, served))
            n += len(served)
        return out

    def release(self):
        """Drop the program's state so that the reference has the chip."""
        self.fleet = self.engine = None
        gc.collect()

    def check(self, sample, *, control: bool = False) -> dict:
        """The widest gap of a served token's logit below the reference's
        best, over the sample (infinite when nothing finished), by the
        reference of the configuration's family.  With ``control``, also
        the widest gap of the fp8 control's top tokens at the same
        positions (``control_max_logit_gap``)."""
        chk = self.sess.workload["check"]
        limit = chk["max_logit_gap"]
        if not sample:
            return {"max_logit_gap": (float("inf"), limit)}
        ref = self.family.Reference(self.cfg, self.sess.seed,
                                    rows=chk.get("rows", 4))
        t0 = time.monotonic()
        seqs = [np.concatenate([p, s[:-1]]) for _, p, s in sample]
        served = [s for _, _, s in sample]
        if control:
            gaps, ctrl = ref.gaps(seqs, served, control=True)
        else:
            gaps = ref.gaps(seqs, served)
        worst = max(float(g.max()) for g in gaps)
        log(f"check: reference over {len(sample)} requests, "
            f"{sum(len(s) for s in served)} served tokens, "
            f"{time.monotonic() - t0:.3f} s")
        out = {"max_logit_gap": (worst, limit)}
        if control:
            out["control_max_logit_gap"] = (
                max(float(g.max()) for g in ctrl), limit)
        return out


def pct(xs, q) -> float:
    """The q-th percentile, the nearest rank at or above q% of the sorted
    values."""
    xs = sorted(xs)
    k = max(0, min(len(xs) - 1, int(np.ceil(q / 100 * len(xs))) - 1))
    return float(xs[k])


def serve(sess, arrivals, *, control: bool = False) -> Outcome:
    """A whole serving run: set-up, window, (traced) reduction, check.
    ``control`` also scores the fp8 control on the same sample."""
    from . import trace as trace_mod
    s = Serving(sess)
    s.setup()
    if sess.trace:
        with trace_mod.Capture(sess.scratch(sess.name)) as cap:
            with annotate(trace_mod.WINDOW, True):
                w = s.window(arrivals)
    else:
        w = s.window(arrivals)
    e2e = s.end_to_end(arrivals, w)
    failed = s.failed(w)
    s.finish_started(w)
    mem = peak_memory(sess.devices)
    sample = s.sample(w)
    records = s.records(arrivals, w)
    records["e2e"] = e2e
    s.release()
    w = None
    trace_doc = None
    if sess.trace:
        t0 = time.monotonic()
        activities = ACTIVITIES + sess.family.SPANS
        tr = cap.trace(activities)
        trace_doc = trace_mod.reduce(tr, activities)
        records["trace"] = tr
        log(f"trace: busy {trace_doc['busy_s']:.6f} s of "
            f"{trace_doc['window_s']:.6f} s, read in "
            f"{time.monotonic() - t0:.3f} s")
    checks = s.check(sample, control=control)
    return Outcome(attempted=len(arrivals), failed=failed, e2e=e2e,
                   records=records, checks=checks, memory_peak_bytes=mem,
                   trace=trace_doc)
