"""A model family planted as new files only: a family module, a
configuration that names it, a cell and its entries in ``BENCHMARK.json``.
The toy family (``data/local_gqa.py``) alternates sliding-window and global
attention layers, so the program serves it on another path than the dense
cells, and its reference masks the window.  A whole run on the CPU is
``correct``; with a served token altered, it is not."""
import json
import os
import shutil

import pytest

import tiny
from test_correct import SEED, altered_token, run_cell
from test_discovery import _digests

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "tiny-local.chat"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("planted")))
    before = _digests(root)
    b = os.path.join(root, "bench")
    shutil.copy(os.path.join(DATA, "local_gqa.py"),
                os.path.join(b, "families", "local_gqa.py"))
    over = dict(tiny.TINY_CONFIG["program"]["overrides"],
                block_pattern=["local", "attn"], local_window=16)
    cfg = dict(tiny.TINY_CONFIG, name="tiny-local", family="local_gqa",
               sliding_window=16,
               program={"arch": "qwen2-0.5b", "overrides": over})
    with open(os.path.join(b, "configs", "tiny-local.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "cells", f"{CELL}.json"), "w") as f:
        json.dump({"rate_per_s": 4.0}, f)
    doc_path = os.path.join(root, "BENCHMARK.json")
    doc = json.load(open(doc_path))
    doc["configs"].append({"name": "tiny-local", "source": "test",
                           "file": "bench/configs/tiny-local.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": CELL, "config": "tiny-local",
                             "traffic": "tiny-chat", "chips": 1,
                             "why": "test"})
    json.dump(doc, open(doc_path, "w"))
    after = _digests(root)
    assert all(after[p] == h for p, h in before.items())   # nothing edited
    return root


def test_planted_family_serves_correctly(root):
    out = run_cell(root, workload=CELL)
    assert out["correct"] is True
    c = out["compared"]["logit_gap_max"]
    assert c["value"] <= c["limit"]
    assert out["attempted"] > 0


def test_planted_family_fault_is_not_correct(root):
    out = run_cell(root, fault=altered_token, seed=SEED + 5, workload=CELL)
    assert out["correct"] is False
