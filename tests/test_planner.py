"""MemoryPlanner services: reports, VMEM budget, max-batch search, MIP export."""
import numpy as np
import pytest

from repro.core import MemoryPlanner, make_profile, to_lp
from repro.core.mip import num_variables
from repro.core.peaks import attached_peaks


def test_report_contains_baseline_comparison():
    prof = make_profile([(4096, 0, 4), (2048, 1, 3), (4096, 4, 8)])
    rep = MemoryPlanner().report(prof)
    assert rep.plan.peak <= rep.baselines["pool_peak"] + 512
    assert rep.baselines["naive_peak"] == prof.total_bytes
    assert rep.quality["lower_bound"] <= rep.plan.peak


def test_exact_solver_selectable():
    prof = make_profile([(512, 0, 3), (512, 1, 4), (1024, 2, 6)])
    rep = MemoryPlanner(solver="exact").report(prof)
    assert rep.plan.solver == "exact"


def test_unknown_solver_rejected():
    with pytest.raises(ValueError):
        MemoryPlanner(solver="magic")


def test_vmem_check():
    ok = MemoryPlanner.check_vmem([((128, 128), np.dtype("float32"))])
    assert ok["fits"]
    bad = MemoryPlanner.check_vmem([((4096, 4096), np.dtype("float32"))])
    assert not bad["fits"]
    assert bad["bytes"] == 2 * 4096 * 4096 * 4      # double-buffered


def test_max_feasible_batch_monotone():
    per_sample = 64 << 20           # 64 MB per sample
    fixed = 4 << 30                 # 4 GB of weights

    def bytes_at(b):
        return fixed + b * per_sample

    hbm = attached_peaks().hbm_bytes
    mp = MemoryPlanner()
    b = mp.max_feasible_batch(bytes_at, hbm_budget=hbm)
    assert bytes_at(b) <= hbm < bytes_at(b + 1)
    assert mp.max_feasible_batch(bytes_at) == b     # default: the chip's HBM
    assert mp.max_feasible_batch(lambda b: hbm * 2, hbm) == 0


def test_max_feasible_batch_monotone_in_budget():
    per_sample = 64 << 20
    bytes_at = lambda b: b * per_sample
    mp = MemoryPlanner()
    budgets = [1 << 30, 2 << 30, 4 << 30, 8 << 30]
    batches = [mp.max_feasible_batch(bytes_at, hbm_budget=h) for h in budgets]
    assert batches == sorted(batches)
    assert batches[-1] == 2 * batches[-2] == 4 * batches[-3]


def _profile_at_batch(b):
    """Synthetic training profile: activations scale with batch, one fat
    long-lived residual the eviction search can profitably stub out."""
    per = 8 << 20
    spec = [(b * per, 0, 100)]
    spec += [(per, t, t + 4) for t in range(1, 93, 4)]
    prof = make_profile(spec)
    prof.retained_bytes = 32 << 20
    return prof


def test_max_feasible_batch_planned_consistent_with_and_without_remat():
    mp = MemoryPlanner()
    budget = 128 << 20
    plain = mp.max_feasible_batch_planned(_profile_at_batch, budget, hi=64)
    for remat in (True, object()):   # bool and policy-like both enable
        planned = mp.max_feasible_batch_planned(_profile_at_batch, budget,
                                                hi=64, remat=remat)
        assert planned >= plain
    # remat=False / mode="none" must match the plain path exactly
    class _NonePolicy:
        mode = "none"
    assert mp.max_feasible_batch_planned(_profile_at_batch, budget, hi=64,
                                         remat=False) == plain
    assert mp.max_feasible_batch_planned(_profile_at_batch, budget, hi=64,
                                         remat=_NonePolicy()) == plain
    # eviction actually buys batch here: the fat block dominates the packing
    assert mp.max_feasible_batch_planned(_profile_at_batch, budget, hi=64,
                                         remat=True) > plain


def test_max_feasible_batch_planned_respects_policy_constraints():
    # a compiled policy constrains eviction to its own primitive sets; the
    # synthetic blocks are untagged, so nothing is evictable under it
    class _Pol:
        mode = "policy"
        recompute_prims = frozenset({"dot_general"})
        offload_prims = frozenset()

    mp = MemoryPlanner()
    budget = 128 << 20
    plain = mp.max_feasible_batch_planned(_profile_at_batch, budget, hi=64)
    constrained = mp.max_feasible_batch_planned(_profile_at_batch, budget,
                                                hi=64, remat=_Pol())
    assert constrained == plain


def test_plan_with_remat_reports_baseline_and_target():
    mp = MemoryPlanner()
    ev = mp.plan_with_remat(_profile_at_batch(4), target_ratio=0.8)
    assert ev.peak <= ev.baseline_peak
    assert ev.target_peak == int(ev.baseline_peak * 0.8)


def test_lp_export_structure():
    prof = make_profile([(512, 0, 3), (1024, 1, 4), (512, 5, 7)])
    lp = to_lp(prof, max_memory=1 << 20)
    assert lp.startswith("\\ DSA MIP")
    assert "Minimize" in lp and "Subject To" in lp and "Binaries" in lp
    nv = num_variables(prof)
    assert nv["x"] == 3 and nv["z"] == 1            # one colliding pair
    # every colliding pair yields two no-overlap rows
    assert lp.count("no_ov_a") == nv["z"]
    assert lp.count("no_ov_b") == nv["z"]


def test_peaks_table_keyed_by_device_kind(monkeypatch):
    import jax

    from repro.core.peaks import PLANNING_TARGET, peaks_for
    from repro.launch import roofline
    row = peaks_for(PLANNING_TARGET)
    assert row.device_kind == PLANNING_TARGET and row.source
    # a CPU host plans for the planning target
    assert attached_peaks() == row
    assert MemoryPlanner.check_vmem([((8, 128), np.dtype("float32"))])[
        "budget"] == row.vmem_bytes
    meta = {"arch": "qwen2-0.5b", "shape": "train_4k", "mesh_tag": "single",
            "mesh": {"data": 1}, "device_kind": row.device_kind,
            "hlo": {"dot_flops": 1e14, "hbm_bytes": 1e13, "coll_bytes": 1e11}}
    cell = roofline.analyze_cell_json(meta)
    assert cell.peaks == row
    assert cell.memory_s == pytest.approx(1e13 / row.hbm_bw)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")

    class Chip:                                 # an attached accelerator
        platform = "tpu"
        device_kind = PLANNING_TARGET
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    assert attached_peaks() == row
    # one the table does not know raises through the planner's budgets and
    # the roofline, never falls back to the planning target
    Chip.device_kind = "TPU v99"
    with pytest.raises(KeyError, match="TPU v99"):
        MemoryPlanner.check_vmem([((8, 128), np.dtype("float32"))])
    with pytest.raises(KeyError, match="TPU v99"):
        MemoryPlanner().max_feasible_batch(lambda b: b)
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.analyze_cell_json(dict(meta, device_kind="TPU v99"))
