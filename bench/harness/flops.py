"""Operations and bytes the work of a dense decoder needs, computed from
shapes: the counts of the ``dense`` model family (``families/dense.py``).

These are the algorithm's counts, not the program's: a later change that
does less work than this (or more) is measured against the same yardstick.
``cfg`` is a configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // nq
    return dict(d=d, nq=nq, nkv=cfg["num_key_value_heads"], hd=hd,
                ff=cfg["intermediate_size"], L=cfg["num_hidden_layers"],
                V=cfg["vocab_size"])


def layer_params(cfg: dict) -> int:
    """Weights of one decoder layer: projections, MLP and norms."""
    m = dims(cfg)
    d, nq, nkv, hd, ff = m["d"], m["nq"], m["nkv"], m["hd"], m["ff"]
    n = d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * ff + 2 * d
    if cfg.get("qk_norm"):
        n += 2 * hd
    return n


def matmul_params(cfg: dict) -> int:
    """Weights that every token multiplies: all layers and the LM head (the
    embedding lookup is a gather, not a product)."""
    m = dims(cfg)
    return m["L"] * layer_params(cfg) + m["d"] * m["V"]


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes a decode step must read once: every weight it multiplies with,
    plus the final norm; the tied or separate embedding rows it gathers are
    negligible and left out."""
    return (matmul_params(cfg) + dims(cfg)["d"]) * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    m = dims(cfg)
    return m["L"] * 2 * m["nkv"] * m["hd"] * itemsize


def token_flops(cfg: dict, context: int) -> float:
    """FLOPs of one token at position ``context`` (it attends to
    ``context + 1`` keys): 2 per weight it multiplies, plus QK^T and PV."""
    m = dims(cfg)
    attn = 4 * m["L"] * m["nq"] * m["hd"] * (context + 1)
    return 2.0 * matmul_params(cfg) + attn


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A causal prefill of ``prompt_len`` tokens.  Only the last position
    needs the LM head, which the served path computes alone."""
    m = dims(cfg)
    per_tok = 2.0 * m["L"] * layer_params(cfg)
    attn = 4.0 * m["L"] * m["nq"] * m["hd"] * prompt_len * (prompt_len + 1) / 2
    return per_tok * prompt_len + attn + 2.0 * m["d"] * m["V"]


def decode_bytes(cfg: dict, context_tokens: int, itemsize: int = 2) -> int:
    """Least HBM traffic of a decode step: weights once, plus the KV of the
    active contexts (``context_tokens`` summed over slots)."""
    return weight_bytes(cfg, itemsize) + context_tokens * kv_bytes_per_token(
        cfg, itemsize)

