"""The plain float32 reference against the program's model at reduced
size on the CPU, on the benchmark's seeded weights: qk-norm with a tied
head (Qwen3-like) and head_dim 120 with a separate head (Danube-like), the
program built as the ``dense`` family builds it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import registry, weights
from bench.harness.reference import DenseReference
from bench.tests.tiny import REPO

DENSE = registry.Registry(REPO).family("dense")

QWEN_LIKE = {"name": "qwen-like", "hidden_size": 64, "intermediate_size": 96,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 200,
             "rms_norm_eps": 1e-6, "rope_theta": 1e6, "qk_norm": True,
             "tie_word_embeddings": True, "torch_dtype": "float32"}
DANUBE_LIKE = {"name": "danube-like", "hidden_size": 240,
               "intermediate_size": 160, "num_hidden_layers": 2,
               "num_attention_heads": 2, "num_key_value_heads": 1,
               "head_dim": 120, "vocab_size": 150, "rms_norm_eps": 1e-6,
               "rope_theta": 1e4, "qk_norm": False, "sliding_window": 4096,
               "tie_word_embeddings": False, "torch_dtype": "float32"}


@pytest.mark.parametrize("cfg", [QWEN_LIKE, DANUBE_LIKE],
                         ids=lambda c: c["name"])
def test_reference_matches_program(cfg):
    from repro.models import kvcache, model
    arch = DENSE.arch_config(cfg)
    seed = 2**33 + 17
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0),
                                                      arch))
    params = weights.make_params(shapes, seed)
    rng = np.random.default_rng(0)
    S, n, max_len = 20, 3, 32
    toks = rng.integers(0, cfg["vocab_size"], S).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, cache = model.prefill(params, arch,
                                      {"tokens": jnp.asarray(toks)[None]},
                                      kvcache.init_cache(arch, 1, max_len))
        got = [np.asarray(logits[0])]
        seq = list(toks)
        for i in range(n):
            t = int(np.argmax(got[-1]))
            seq.append(t)
            logits, cache = model.decode_step(
                params, arch, jnp.asarray([[t]], jnp.int32),
                jnp.asarray([S + i], jnp.int32), cache)
            got.append(np.asarray(logits[0]))
    ref = DenseReference(cfg, seed).logits(np.asarray(seq),
                                           np.arange(S - 1, S + n))
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.stack(got), ref, rtol=0,
                               atol=2e-5 * scale)


def test_weights_are_the_programs_tree():
    """The benchmark fills exactly the program's parameter tree, and a leaf
    made again for one layer equals that layer of the stacked leaf."""
    from repro.models import model
    arch = DENSE.arch_config(QWEN_LIKE)
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0),
                                                      arch))
    params = weights.make_params(shapes, 5)
    assert (jax.tree.structure(params) == jax.tree.structure(shapes))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert a.shape == b.shape and a.dtype == b.dtype
    wq = params["blocks"][0]["attn"]["wq"]
    one = weights.leaf(5, "blocks/0/attn/wq", wq.shape[1:], "float32", 1)
    np.testing.assert_array_equal(np.asarray(wq[1]), np.asarray(one))
    other = weights.make_params(shapes, 6)["blocks"][0]["attn"]["wq"]
    assert not np.array_equal(np.asarray(wq), np.asarray(other))


def test_control_departs_from_reference():
    """Greedy tokens of the reference itself lie 0 below its best; the fp8
    control's top tokens at the same positions do not."""
    cfg = dict(QWEN_LIKE, hidden_size=128, intermediate_size=256,
               vocab_size=512)
    ref = DenseReference(cfg, 11)
    seq = list(np.random.default_rng(1).integers(0, 512, 16))
    for _ in range(6):
        seq.append(int(ref.logits(np.asarray(seq), [len(seq) - 1])[0]
                       .argmax()))
    served = np.asarray(seq[16:], np.int32)
    gaps, ctrl = ref.gaps([np.asarray(seq[:-1], np.int32)], [served],
                          control=True)
    assert float(gaps[0].max()) < 1e-5
    assert float(ctrl[0].max()) > 1e-3
