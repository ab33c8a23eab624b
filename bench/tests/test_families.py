"""Model families found by name.

``dense_gqa`` holds the dense GQA code that ``weights.py``, ``reference.py``
and ``yardstick.py`` held before families existed: for the same seed it has
to give the same weights, bit for bit, the same reference gaps and the same
yardstick numbers.  ``data/dense_gqa_before.json`` holds what that code gave
on the CPU.
"""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import reference
import tiny
from cell import DecodeStep
from families import dense_gqa
from yardstick import peaks_for

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BEFORE = json.load(open(os.path.join(DATA, "dense_gqa_before.json")))

VARIANTS = {
    "tiny": tiny.TINY_CONFIG,
    "tiny_untied": dict(
        tiny.TINY_CONFIG, tie_word_embeddings=False, qkv_bias=False,
        program={"arch": "qwen2-0.5b", "overrides": dict(
            tiny.TINY_CONFIG["program"]["overrides"], tie_embeddings=False,
            qkv_bias=False)}),
}


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _requests(vocab, seed=5):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, plen, dtype=np.int32),
             rng.integers(0, vocab, glen, dtype=np.int32))
            for plen, glen in ((12, 9), (40, 24), (5, 3))]


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_dense_gqa_weights_and_gaps_are_as_before(name):
    from cell import build_model
    cfg = VARIANTS[name]
    seed = BEFORE["seed"]
    want = BEFORE["variants"][name]
    model = build_model(cfg, dense_gqa)
    params = dense_gqa.program_params(seed, cfg, model)
    assert _digest(params) == want["params_sha256"]
    got = reference.served_gaps(
        dense_gqa.reference(cfg, seed),
        _requests(dense_gqa.yardstick(cfg).vocab), 24,
        dense_gqa.reference(cfg, seed, "fp8"))
    assert got == want["gaps"]


def test_dense_gqa_yardstick_is_as_before():
    peaks = peaks_for("TPU v5 lite")
    ctxs = BEFORE["yardstick"]["contexts"]
    cfgs = dict(VARIANTS)
    for f in ("qwen2-0.5b", "mistral-nemo-12b-pp4"):
        cfgs[f] = json.load(open(os.path.join(tiny.BENCH, "configs",
                                              f"{f}.json")))
    for name, want in BEFORE["yardstick"]["configs"].items():
        y = dense_gqa.yardstick(cfgs[name])
        got = {
            "params": y.params(),
            "prefill_flops": [y.prefill_flops(n)
                              for n in (1, 32, 517, 1536)],
            "decode_flops": [y.decode_flops(x) for x in ctxs],
            "decode_least_time": [
                list(y.decode_least_time(DecodeStep(0.0, 1.0, x, []),
                                         peaks)) for x in ctxs],
            "kv_bytes": [y.kv_bytes(x) for x in ctxs],
        }
        assert got == want, name


def test_a_config_without_a_family_is_refused(tmp_path):
    import spec as spec_lib
    root = tiny.make_root(str(tmp_path))
    path = os.path.join(root, "bench", "configs", "tiny.json")
    cfg = json.load(open(path))
    del cfg["family"]
    json.dump(cfg, open(path, "w"))
    with pytest.raises(KeyError, match='"family"'):
        spec_lib.Spec(root, os.path.join(root, "bench")).config("tiny")
