"""Roofline machinery: analytic MODEL_FLOPS sanity + cell analysis."""
import pytest

from repro.configs import ARCHS, SHAPES, get_config
from repro.launch import roofline


@pytest.mark.parametrize("arch", ARCHS)
def test_model_flops_positive_and_ordered(arch):
    cfg = get_config(arch)
    train = roofline.model_flops(cfg, SHAPES["train_4k"])["model_flops"]
    prefill = roofline.model_flops(cfg, SHAPES["prefill_32k"])["model_flops"]
    decode = roofline.model_flops(cfg, SHAPES["decode_32k"])["model_flops"]
    assert train > 0 and prefill > 0 and decode > 0
    # training does fwd+bwd on 1M tokens; decode is one token per sequence
    assert train > prefill > decode


def test_dense_train_flops_close_to_6nd():
    """For a dense arch at short context, MODEL_FLOPS ~ 6*N*D."""
    cfg = get_config("mistral-nemo-12b")
    shape = SHAPES["train_4k"]
    mf = roofline.model_flops(cfg, shape)["model_flops"]
    n_params = 12.2e9                       # public figure
    six_nd = 6 * n_params * shape.global_batch * shape.seq_len
    assert 0.7 < mf / six_nd < 1.6          # attention + lm-head on top


def test_moe_uses_active_params_only():
    """qwen3 (30B total, ~3B active): train flops must track ACTIVE params."""
    cfg = get_config("qwen3-moe-30b-a3b")
    shape = SHAPES["train_4k"]
    mf = roofline.model_flops(cfg, shape)["model_flops"]
    tokens = shape.global_batch * shape.seq_len
    six_nd_total = 6 * 30e9 * tokens
    six_nd_active = 6 * 3e9 * tokens
    assert mf < 0.5 * six_nd_total          # nowhere near dense-total
    assert mf > 0.5 * six_nd_active


def test_subquadratic_decode_independent_of_context():
    cfg = get_config("mamba2-130m")
    d32 = roofline.model_flops(cfg, SHAPES["decode_32k"])
    d500 = roofline.model_flops(cfg, SHAPES["long_500k"])
    per_tok_32 = d32["model_flops"] / d32["tokens"]
    per_tok_500 = d500["model_flops"] / d500["tokens"]
    assert per_tok_500 == pytest.approx(per_tok_32, rel=0.01)


def test_attention_decode_scales_with_context():
    cfg = get_config("mistral-nemo-12b")
    d32 = roofline.model_flops(cfg, SHAPES["decode_32k"])
    per_tok = d32["model_flops"] / d32["tokens"]
    # attention over 32k context must be a visible share of per-token work
    attn = 40 * roofline._attn_score_flops(cfg, 32_768)
    assert attn > 0.2 * per_tok


def test_cell_analysis_roundtrip():
    meta = {
        "arch": "qwen2-0.5b", "shape": "train_4k", "mesh_tag": "single",
        "mesh": {"data": 16, "model": 16},
        "hlo": {"dot_flops": 1e14, "hbm_bytes": 1e13, "coll_bytes": 1e11},
    }
    cell = roofline.analyze_cell_json(meta)
    assert cell.chips == 256
    assert cell.dominant == "memory"
    assert cell.compute_s == pytest.approx(1e14 / cell.peaks.bf16_flops)
    assert 0 < cell.fraction < 1
    assert cell.step_bound_s == cell.memory_s


def test_table_formats():
    meta = {
        "arch": "qwen2-0.5b", "shape": "train_4k", "mesh_tag": "single",
        "mesh": {"data": 16, "model": 16},
        "hlo": {"dot_flops": 1e14, "hbm_bytes": 1e13, "coll_bytes": 1e11},
    }
    cells = [roofline.analyze_cell_json(meta)]
    md = roofline.table(cells)
    csv = roofline.table(cells, fmt="csv")
    assert "qwen2-0.5b" in md and "|" in md
    assert csv.splitlines()[0].startswith("arch,shape")
