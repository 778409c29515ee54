"""A configuration names its model family, and the harness takes the
program's ``ArchConfig``, the reference, the work counts and the extra span
names from that family's file and from nowhere else.

The ``dense`` family gives, for qwen3-4b, exactly what the harness built
before families existed (frozen below); a family that exists only as a new
file in a throwaway tree runs a cell end to end, and each of its pieces
shows in the result; the seeded weights fill a mixture-of-experts program
tree unchanged."""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from bench.harness import registry, session, weights
from bench.harness.reference import DenseReference
from bench.tests import tiny

REG = registry.Registry(tiny.REPO)
DENSE = REG.family("dense")
with open(os.path.join(tiny.REPO, "bench", "configs", "qwen3-4b.json")) as _f:
    QWEN3_4B = json.load(_f)

#: the ``ArchConfig`` the harness built for qwen3-4b before a configuration
#: named its family
QWEN3_4B_ARCH = {
    "name": "qwen3-4b", "family": "dense", "num_layers": 36,
    "d_model": 2560, "num_heads": 32, "num_kv_heads": 8, "d_ff": 9728,
    "vocab_size": 151936, "head_dim": 128,
    "source": "https://huggingface.co/Qwen/Qwen3-4B (config.json: "
              "Qwen3ForCausalLM)",
    "attention": "full", "window": 4096, "qk_norm": True,
    "rope_theta": 1000000.0, "mlp_type": "swiglu", "tie_embeddings": True,
    "num_experts": 0, "experts_per_token": 0, "moe_dense_ff": 0,
    "capacity_factor": 1.25, "router_aux_weight": 0.01, "ssm_state": 0,
    "ssm_expand": 2, "ssm_conv": 4, "hybrid_period": 0, "xlstm_pattern": (),
    "encoder_layers": 0, "encoder_seq": 1500, "cross_attn_every": 0,
    "image_tokens": 0, "dtype": "bfloat16", "param_dtype": "bfloat16",
    "optimizer": "adamw", "remat": False}

#: the counts the harness took for qwen3-4b before a configuration named
#: its family: (function, argument, value)
QWEN3_4B_COUNTS = [
    ("prefill_flops", 1, 8045520896.0),
    ("prefill_flops", 256, 1880537563136.0),
    ("prefill_flops", 512, 3798951919616.0),
    ("prefill_flops", 1024, 7751744749568.0),
    ("prefill_flops", 1056, 8003928129536.0),
    ("token_flops", 0, 8045520896.0),
    ("token_flops", 255, 8195926016.0),
    ("token_flops", 1000, 8635344896.0),
    ("token_flops", 1055, 8667785216.0),
    ("token_flops", 4095, 10460850176.0),
    ("decode_bytes", 0, 8044936192),
    ("decode_bytes", 1056, 8200649728),
    ("decode_bytes", 2112, 8356363264),
]


def test_dense_arch_config_is_unchanged():
    got = dataclasses.asdict(DENSE.arch_config(QWEN3_4B))
    assert set(got) == set(QWEN3_4B_ARCH)
    for field, want in QWEN3_4B_ARCH.items():
        assert got[field] == want, field


@pytest.mark.parametrize("fn,arg,want", QWEN3_4B_COUNTS,
                         ids=lambda v: str(v))
def test_dense_counts_are_unchanged(fn, arg, want):
    assert getattr(DENSE, fn)(QWEN3_4B, arg) == want


def test_dense_kv_row_reference_and_spans():
    assert DENSE.kv_bytes_per_token(QWEN3_4B) == 147456
    assert DENSE.Reference is DenseReference
    assert DENSE.SPANS == ()


def test_dense_keeps_the_programs_rmsnorm_eps():
    """The program's RMSNorm fixes eps at 1e-6: the dense family refuses a
    configuration that states another."""
    with pytest.raises(ValueError, match="eps 1e-6"):
        DENSE.arch_config(dict(QWEN3_4B, rms_norm_eps=1e-5))


#: a family that exists only in the throwaway tree: the dense family's
#: program, a reference that puts every served token 1.0 further below
#: its best, twice the dense FLOPs per decoded token, a span of its own,
#: and a log of every call the harness makes
OFFSET = '''
from bench.families import dense

CALLS = []
SPANS = ("sched_decode",)


def arch_config(cfg):
    CALLS.append(("arch_config", cfg["name"]))
    return dense.arch_config(cfg)


class Reference(dense.Reference):
    def gaps(self, seqs, served, *, control=False):
        CALLS.append(("gaps", len(seqs)))
        out = super().gaps(seqs, served, control=control)
        if control:
            return [g + 1.0 for g in out[0]], out[1]
        return [g + 1.0 for g in out]


def prefill_flops(cfg, prompt_len):
    v = dense.prefill_flops(cfg, prompt_len)
    CALLS.append(("prefill_flops", prompt_len, v))
    return v


def token_flops(cfg, context):
    v = 2 * dense.token_flops(cfg, context)
    CALLS.append(("token_flops", context, v))
    return v


kv_bytes_per_token = dense.kv_bytes_per_token
decode_bytes = dense.decode_bytes
'''


def test_family_added_as_a_file_runs_a_cell(tmp_path, no_cache, cpu_chips):
    """A configuration naming a family that is one new file runs a traced
    cell end to end; the run used that family's ``ArchConfig``, reference,
    counts and spans."""
    root, bench = tiny.make_tree(
        tmp_path, families={"offset": OFFSET},
        configs={"tiny-offset": dict(tiny.TINY, name="tiny-offset",
                                     family="offset")},
        cells={"tiny-offset.open": dict(tiny.CELLS["tiny.open"],
                                        config="tiny-offset")})
    sess = session.open_session(session.parse(
        ["--workload", "tiny-offset.open", "--seed", str(2**33 + 5),
         "--seconds", "2", "--trace", "1"]), root, chips=cpu_chips,
        bench=bench)
    out = sess.registry.driver(sess.workload["driver"]).run(sess)
    fam = out.records["family"]
    assert fam.__file__ == os.path.join(bench, "families", "offset.py")
    calls = fam.CALLS
    assert ("arch_config", "tiny-offset") in calls
    # the offset reference judged the served tokens: not correct
    assert any(c[0] == "gaps" for c in calls)
    gap, limit = out.checks["max_logit_gap"]
    assert gap >= 1.0 > limit and not out.correct
    # the window's work is the family's count, in the harness's order
    counted = [c for c in calls if c[0] in ("prefill_flops", "token_flops")]
    assert any(c[0] == "token_flops" for c in counted)
    total = 0.0
    for name, n, v in counted:
        total += v
        if name == "token_flops":
            assert v == 2 * DENSE.token_flops(tiny.TINY, n)
    assert out.records["model_flops"] == total
    mfu = registry.Registry(root, bench).reader("mfu.serve").read(
        dict(out.records, peaks={"bf16_flops": 1e12}))
    assert mfu == pytest.approx(100.0 * total
                                / (out.records["window_s"] * 1e12))
    # the family's span entered the traced run's host activities
    assert out.records["trace"].host_spans("sched_decode")


def test_seeded_weights_fill_a_moe_tree():
    """The benchmark's weights need no change for a non-dense family: they
    fill a mixture-of-experts program tree, and a leaf made again for one
    layer equals that layer of the stacked leaf."""
    from repro.configs.base import ArchConfig
    from repro.models import model
    arch = ArchConfig(name="tiny-moe", family="moe", num_layers=3,
                      d_model=32, num_heads=4, num_kv_heads=2, d_ff=16,
                      vocab_size=64, num_experts=4, experts_per_token=2,
                      dtype="float32", param_dtype="float32", remat=False)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0),
                                                      arch))
    params = weights.make_params(shapes, 2**33 + 9)
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert a.shape == b.shape and a.dtype == b.dtype
    moe = params["blocks"][0]["moe"]
    for leaf in ("router", "w_gate", "w_down"):
        stacked = moe[leaf]
        assert stacked.shape[0] == 3
        one = weights.leaf(2**33 + 9, f"blocks/0/moe/{leaf}",
                           stacked.shape[1:], "float32", 2)
        np.testing.assert_array_equal(np.asarray(stacked[2]),
                                      np.asarray(one))
