"""A throwaway benchmark tree at CPU size, built from files only.

``make_tree`` writes a checkout-like directory: ``BENCHMARK.json``, ``src``
(a link to the program), and ``bench/`` holding links to the real drivers,
metric readers and model families plus tiny configuration, traffic and cell
files, and any family module given as source text.  That a cell runs from
it shows a cell, a configuration, a traffic mix and a model family are
added by adding files.
"""
from __future__ import annotations

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "name": "tiny", "source": "a CPU-size dense decoder for tests",
    "family": "dense",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "torch_dtype": "bfloat16", "qk_norm": True, "reduced": [],
}

FLEET = {"n_pods": 1, "prefill_per_pod": 1, "decode_per_pod": 2,
         "num_slots": 2, "kv_blocks": 24, "block_tokens": 8,
         "max_len": 40, "admission": "slo", "slo": "standard"}

CELLS = {
    "tiny.open": dict(
        config="tiny", traffic="tiny-poisson", chips=1,
        driver="serve_open_loop", fleet=FLEET,
        check={"sample_tokens": 64, "max_logit_gap": 0.08, "rows": 2},
        why="test"),
}

TRAFFIC = {
    "tiny-poisson": {"process": "poisson", "rate_per_s": 8.0,
                     "prompt_lens": [16, 32], "prompt_weights": [0.5, 0.5],
                     "output_lens": [4, 8], "output_weights": [0.5, 0.5]},
}


def _write(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def make_tree(tmp, *, configs=None, traffic=None, cells=None,
              families=None):
    """Write the tree under ``tmp``; returns (root, bench dir).
    ``families`` maps a family name to its module's source."""
    configs = dict({"tiny": TINY}, **(configs or {}))
    traffic = dict(TRAFFIC, **(traffic or {}))
    cells = dict(CELLS, **(cells or {}))
    root = str(tmp)
    bench = os.path.join(root, "bench")
    os.makedirs(bench, exist_ok=True)
    if not os.path.exists(os.path.join(root, "src")):
        os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    for sub in ("drivers", "metrics", "families"):
        d = os.path.join(bench, sub)
        os.makedirs(d, exist_ok=True)
        for f in os.listdir(os.path.join(REPO, "bench", sub)):
            if f.endswith(".py") and not os.path.exists(os.path.join(d, f)):
                os.symlink(os.path.join(REPO, "bench", sub, f),
                           os.path.join(d, f))
    for name, doc in configs.items():
        _write(os.path.join(bench, "configs", f"{name}.json"), doc)
    for name, doc in traffic.items():
        _write(os.path.join(bench, "traffic", f"{name}.json"), doc)
    for name, doc in cells.items():
        _write(os.path.join(bench, "workloads", f"{name}.json"), doc)
    for name, text in (families or {}).items():
        with open(os.path.join(bench, "families", f"{name}.py"), "w") as f:
            f.write(text)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": n, "source": "test",
                        "file": f"bench/configs/{n}.json", "reduced": [],
                        "why": "test"} for n in configs]
    spec["workloads"] = [{"name": n, "config": c["config"],
                          "traffic": c["traffic"], "chips": c["chips"],
                          "why": "test"} for n, c in cells.items()]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(cells)
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    return root, bench
