"""With the timed path broken underneath, a run comes out not correct.

Each test skips only the harness's look for a chip (CPU devices stand in)
and drives the rest of a run of a throwaway CPU-size cell, with one fault
planted in the program: a token altered where it is sampled; the KV
handoff between PEs left out; decode's state (the KV it writes back)
left unchanged.  A sound run of the same cell is correct, and its fp8
control is not.
"""
import jax.numpy as jnp

from bench.harness import session
from bench.tests import tiny


def run(tmp_path, cell, chips, seed=11, seconds="1.5", control=False):
    root, bench = tiny.make_tree(tmp_path)
    sess = session.open_session(session.parse(
        ["--workload", cell, "--seed", str(seed), "--seconds", seconds]),
        root, chips=chips, bench=bench)
    return sess.registry.driver(sess.workload["driver"]).run(
        sess, control=control)


def test_sound_serving_run_and_its_control(tmp_path, no_cache, cpu_chips):
    out = run(tmp_path, "tiny.open", cpu_chips, control=True)
    gap, limit = out.checks["max_logit_gap"]
    ctrl, _ = out.checks["control_max_logit_gap"]
    assert gap <= limit < ctrl


def test_token_altered(tmp_path, no_cache, cpu_chips, monkeypatch):
    from repro.serve.engine import Engine
    real = Engine._sample

    def bad(self, logits, key, temperature):
        return (real(self, logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(Engine, "_sample", bad)
    out = run(tmp_path, "tiny.open", cpu_chips)
    assert not out.correct


def test_kv_handoff_left_out(tmp_path, no_cache, cpu_chips, monkeypatch):
    """Block payloads never reach the decode PE; signals, tail and header
    still do, so admission goes ahead on whatever the pool row held."""
    from repro.serve import kvxfer
    real = kvxfer.signal_mod.put_signal_nbi
    real_put = kvxfer.rma.put_nbi

    def no_block_puts(ctx, heap, dest, *a, **k):
        if dest.dtype == "bfloat16":
            return heap
        return real_put(ctx, heap, dest, *a, **k)

    def signal_only(ctx, heap, dest, value, *a, **k):
        if dest.dtype == "bfloat16":
            value = jnp.zeros_like(value)
        return real(ctx, heap, dest, value, *a, **k)

    monkeypatch.setattr(kvxfer.rma, "put_nbi", no_block_puts)
    monkeypatch.setattr(kvxfer.signal_mod, "put_signal_nbi", signal_only)
    out = run(tmp_path, "tiny.open", cpu_chips)
    assert not out.correct


def test_decode_state_unchanged(tmp_path, no_cache, cpu_chips, monkeypatch):
    """Decode never writes its new K/V back to the pool."""
    from repro.serve.paged_attn import PagedDecodeView
    monkeypatch.setattr(PagedDecodeView, "writeback",
                        lambda self, ctx, heap, *a, **k: heap)
    out = run(tmp_path, "tiny.open", cpu_chips)
    assert not out.correct

