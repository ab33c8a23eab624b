"""A checkout-shaped directory with a tiny cell, for the harness's tests.

``make_root(tmp)`` copies ``bench/`` beside a link to the program's
``src/`` and writes a ``BENCHMARK.json`` whose one cell, ``tiny.chat``,
serves a two-layer, 64-wide qwen2-shaped model on the CPU in seconds.
"""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY_CONFIG = {
    "name": "tiny", "source": "test", "family": "dense_gqa",
    "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 1000, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16", "qkv_bias": True, "reduced": [],
    "program": {"arch": "qwen2-0.5b", "overrides": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "d_ff": 128, "vocab_size": 1000, "head_dim": 16}},
    "engine": {"max_len": 128, "max_batch": 4},
    "check": {"rows": 2, "limits": {"logit_gap_max": 0.02}},
}

TINY_TRAFFIC = {
    "arrival": "poisson", "order": "fixed",
    "prompt": {"median": 16, "sigma": 0.6, "min": 4, "max": 60},
    "output": {"median": 8, "sigma": 0.6, "min": 2, "max": 24},
    "shape_seed": 7, "warmup_s": 0.5, "drain_s": 10.0,
    "check": {"max_requests": 4, "min_tokens": 24},
}


def make_root(tmp: str, rate: float = 4.0) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(b, "traffic", "tiny-chat.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(b, "cells", "tiny.chat.json"), "w") as f:
        json.dump({"rate_per_s": rate}, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"] = [{"name": "tiny", "source": "test",
                       "file": "bench/configs/tiny.json", "reduced": [],
                       "why": "test"}]
    doc["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                         "traffic": "tiny-chat", "chips": 1, "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            if "workloads" in m:
                m["workloads"] = ["tiny.chat"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root
