"""Model family ``dense``: a Llama/Qwen3-style decoder, every layer grouped-
query attention and a SwiGLU MLP, RMSNorm throughout.

A configuration file names it with ``"family": "dense"``.  The harness
takes from here, and from nowhere else, the program's ``ArchConfig``, the
plain reference that decides ``correct``, the work counted from shapes,
and the program's span names this family adds to a traced run
(``harness/registry.py`` ``FAMILY``).
"""
from __future__ import annotations

from bench.harness.flops import (decode_bytes, kv_bytes_per_token,
                                 prefill_flops, token_flops)
from bench.harness.reference import DenseReference as Reference

__all__ = ["arch_config", "Reference", "prefill_flops", "token_flops",
           "kv_bytes_per_token", "decode_bytes", "SPANS"]

#: the harness's own host activities cover every span of a dense decoder
SPANS = ()


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a dense decoder configuration file
    (Hugging Face key names)."""
    from repro.configs.base import ArchConfig
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) MLPs are served")
    if float(cfg["rms_norm_eps"]) != 1e-6:
        raise ValueError("the program's RMSNorm uses eps 1e-6; the "
                         f"configuration states {cfg['rms_norm_eps']}")
    window = cfg.get("sliding_window")
    return ArchConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg.get("head_dim") or 0,
        attention="swa" if window else "full", window=window or 4096,
        qk_norm=bool(cfg.get("qk_norm", False)),
        rope_theta=float(cfg["rope_theta"]), mlp_type="swiglu",
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"],
        remat=False, source=cfg.get("source", ""))
