"""The operation and byte functions against counts made by hand."""
import json
import os

from bench.harness import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_qwen3_4b_layer_and_model():
    c = cfg("qwen3-4b")
    # wq 2560x4096, wk and wv 2560x1024, wo 4096x2560, MLP 3 x 2560x9728,
    # two norms of 2560 and the q/k norms of 128
    layer = (2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728
             + 2 * 2560 + 2 * 128)
    assert layer == 100_930_816
    assert flops.layer_params(c) == layer
    # 36 layers and the tied LM head (151936 x 2560): 4.02 B weights
    assert flops.matmul_params(c) == 36 * layer + 151936 * 2560
    assert flops.weight_bytes(c) == 2 * (36 * layer + 151936 * 2560 + 2560)


#: h2o-danube3-4b's widths (huggingface.co/h2oai/h2o-danube3-4b-base):
#: head_dim 120, a separate LM head
DANUBE = {"hidden_size": 3840, "intermediate_size": 10240,
          "num_hidden_layers": 24, "num_attention_heads": 32,
          "num_key_value_heads": 8, "head_dim": 120, "vocab_size": 32000,
          "tie_word_embeddings": False}


def test_danube_kv_row_and_weights():
    c = DANUBE
    assert flops.kv_bytes_per_token(c) == 24 * 2 * 8 * 120 * 2 == 92_160
    layer = (2 * 3840 * 3840 + 2 * 3840 * 960 + 3 * 3840 * 10240
             + 2 * 3840)
    assert flops.layer_params(c) == layer
    # untied: the LM head (3840 x 32000) is multiplied, the embedding is not
    assert flops.matmul_params(c) == 24 * layer + 3840 * 32000


def test_token_prefill_and_decode_flops():
    c = cfg("qwen3-4b")
    P = flops.matmul_params(c)
    # a token at position 9 attends to 10 keys: QK^T and PV, 2 FLOPs each
    # per head dimension, 32 heads of 128, 36 layers
    assert flops.token_flops(c, 9) == 2 * P + 4 * 36 * 32 * 128 * 10
    # a prefill of 4 tokens: every layer weight per token, causal attention
    # over 1 + 2 + 3 + 4 keys, and the LM head once
    layers = 2 * 36 * flops.layer_params(c)
    want = layers * 4 + 4 * 36 * 32 * 128 * 10 + 2 * 2560 * 151936
    assert flops.prefill_flops(c, 4) == want
    assert flops.decode_bytes(c, 100) == (flops.weight_bytes(c)
                                          + 100 * 147_456)

