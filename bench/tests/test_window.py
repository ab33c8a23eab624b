"""Window accounting: every request due in the window counts, tails are
taken over all samples, and work outside the window is left out."""
import importlib.util
import os

import pytest

from cell import ReqRecord, RunRecord
from stats import percentile
from families.dense_gqa import Dims
from yardstick import peaks_for

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def make_run(requests, prefills=(), window=(10.0, 20.0), steps=None):
    dims = Dims(layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
                d_ff=16, vocab=100, padded_vocab=256, tied=True,
                qkv_bias=False)
    return RunRecord(
        yardstick=dims, peaks=peaks_for("TPU v5 lite"), setup_s=5.0,
        setup_stages=[], window=window, requests=requests,
        prefills=list(prefills),
        decodes=[], steps=steps or [(window[1] - 1, window[1] + 3)],
        decode_counter=((0.0, 0), (1.0, 100)), kv_held_bytes=0, live_kv=[],
        tracer_events=[], tracer_offset=0.0, device_trace=None,
        memory_peak_bytes=0, compiles_in_window=0, served={}, prompts={})


def req(rid, due, first, phase="window", tokens=()):
    return ReqRecord(rid=rid, due=due, prompt_len=4, gen_len=4, phase=phase,
                     sent=due, first=first,
                     token_times=[first, *tokens] if first else [])


def test_percentile_is_nearest_rank_over_all_samples():
    v = list(range(1, 101))
    assert percentile(v, 95) == 95
    assert percentile(v, 50) == 50
    assert percentile([3.0], 95) == 3.0
    assert percentile([], 95) is None
    assert percentile(list(range(1, 21)), 95) == 19


def test_ttft_counts_every_window_request():
    # 19 fast requests and one that never got a token: the run ends at
    # 23.0 (the last step), so it counts as 23.0 - 15.0 = 8 s, and with 20
    # samples the p95 is the 19th smallest.
    reqs = [req(i, 10.0 + i * 0.1, 10.0 + i * 0.1 + 0.05) for i in range(19)]
    reqs.append(req(99, 15.0, None))
    reqs.append(req(100, 5.0, 9.0, phase="warmup"))     # not in the window
    run = make_run(reqs)
    assert len(run.window_requests()) == 20
    assert sorted(run.ttft_ms()) == pytest.approx([50.0] * 19 + [8000.0])
    assert percentile(run.ttft_ms(), 95) == pytest.approx(50.0)
    assert reader("ttft_p50_ms")(run) == pytest.approx(50.0)
    reqs[0].first = None
    reqs[0].token_times = []
    # two misses now: 13 s and 8 s; the 19th smallest of 20 is the 8 s
    assert percentile(make_run(reqs).ttft_ms(), 95) == pytest.approx(
        (23.0 - 15.0) * 1e3)


def test_itl_and_tokens_keep_to_the_window():
    r1 = req(1, 9.0, 9.5, phase="warmup", tokens=(10.5, 11.0, 20.5))
    r2 = req(2, 12.0, 12.2, tokens=(12.4,))
    run = make_run([r1, r2], prefills=[(9.4, 9.5, 7), (12.1, 12.2, 5)])
    # gaps whose later token lies in [10, 20): 1.0, 0.5 (r1), 0.2 (r2)
    assert reader("itl_p95_ms")(run) == pytest.approx(1000.0)
    # tokens in the window: 10.5, 11.0, 12.2, 12.4; prompt tokens: 5
    assert reader("tokens_per_s")(run) == pytest.approx((4 + 5) / 10.0)


def test_generator_lag_and_decode_clock():
    reqs = [req(i, 10.0 + i, 10.5 + i) for i in range(5)]
    for i, r in enumerate(reqs):
        r.sent = r.due + 0.001 * i
    run = make_run(reqs)
    assert reader("gen_lag_p95_ms")(run) == pytest.approx(4.0)
    assert reader("decode_step_ms")(run) == pytest.approx(10.0)


def test_device_metrics_need_a_trace():
    run = make_run([req(1, 11.0, 11.5)])
    for name in ("prefill_mfu", "decode_mfu", "decode_roofline",
                 "device_idle_pct", "queue_wait_p95_ms", "replan_ms"):
        assert reader(name)(run) is None
