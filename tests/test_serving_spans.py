"""Spans inside the serving engine: how they nest, the first-token stamps,
the profiler's host plane, and the named scopes of the compiled decode step."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import Transformer
from repro.obs.trace import PH_COMPLETE, PH_INSTANT, Tracer, disable, enable
from repro.runtime.serve_lib import Request
from repro.serving import GenRequest, ServeEngine


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("qwen2-0.5b").smoke()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(tiny_model):
    cfg, model, params = tiny_model
    trace = [Request(rid=i, prompt_len=6 + i, gen_len=5, arrival=i)
             for i in range(1, 5)]
    live = [GenRequest(rid=r.rid,
                       prompt=jax.random.randint(jax.random.PRNGKey(r.rid),
                                                 (r.prompt_len,), 0,
                                                 cfg.vocab_size),
                       gen_len=r.gen_len, arrival=r.arrival)
            for r in trace]
    eng = ServeEngine(model, params, sample_trace=trace, max_len=32,
                      max_batch=4, page_tokens=4)
    return eng, live


def _enclosing(ev, by_id, name):
    """The nearest span called ``name`` among ``ev``'s ancestors."""
    p = by_id.get(ev.parent_id)
    while p is not None and p.name != name:
        p = by_id.get(p.parent_id)
    return p


def test_engine_spans_nest_under_step(tiny_model):
    eng, live = _engine(tiny_model)
    eng.warmup()
    tracer = enable(Tracer())
    try:
        summary = eng.run(live)
    finally:
        disable()
    assert summary["n_completed"] == len(live)
    assert summary["n_preemptions"] == 0
    events = tracer.events()
    spans = [e for e in events if e.ph == PH_COMPLETE and e.span_id]
    by_id = {e.span_id: e for e in spans}
    names = {e.name for e in spans}
    assert {"step", "prefill", "decode", "epoch", "runner.put",
            "runner.launch", "runner.readback", "prefill.pad",
            "prefill.launch", "prefill.merge", "prefill.sync",
            "prefill.pick"} <= names
    assert not any(n.startswith("bench.") for n in names)
    steps = [e for e in spans if e.name == "step"]
    assert len(steps) == eng.step_count
    assert all(e.parent_id == 0 for e in steps)
    for e in spans:
        if e.name in ("prefill", "decode", "epoch"):
            assert by_id[e.parent_id].name == "step"
        elif e.name.startswith("runner."):
            assert by_id[e.parent_id].name == "decode"
            assert e.track == "runner"
        elif e.name.startswith("prefill."):
            assert by_id[e.parent_id].name == "prefill"
        if e.parent_id:                 # a child lies inside its parent
            p = by_id[e.parent_id]
            assert p.ts <= e.ts and e.ts + e.dur <= p.ts + p.dur + 1e-3
    decodes = [e for e in spans if e.name == "decode"]
    assert len(decodes) == eng.decode_steps
    assert all(1 <= e.args["rows"] <= e.args["bucket"] <= 4 for e in decodes)
    # the per-step decode instant is gone: "decode" is only ever a span
    assert not any(e.name == "decode" and e.ph == PH_INSTANT for e in events)
    # one first-token per finished request, inside the step of its prefill
    firsts = [e for e in events if e.name == "first-token"]
    assert sorted(e.args["rid"] for e in firsts) == sorted(eng.completed)
    prefill_step = {e.args["rid"]: _enclosing(e, by_id, "step").span_id
                    for e in spans if e.name == "prefill"}
    for e in firsts:
        assert e.ph == PH_INSTANT
        assert by_id[e.parent_id].name == "step"
        assert e.parent_id == prefill_step[e.args["rid"]]


def test_profiler_host_plane_holds_program_spans(tiny_model, tmp_path):
    eng, live = _engine(tiny_model)
    eng.warmup()
    enable(Tracer())
    try:
        with jax.profiler.trace(str(tmp_path)):
            eng.run(live[:2])
    finally:
        disable()
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"serving.step", "serving.decode", "serving.prefill",
            "serving.runner.launch"} <= names


def test_decode_step_hlo_carries_named_scopes(tiny_model):
    cfg, model, params = tiny_model
    from repro.serving.runner import DecodeRunner
    runner = DecodeRunner(model, max_batch=2)
    cache = model.init_cache(2, 16)
    tokens = jnp.zeros((2,), jnp.int32)
    hlo = runner._ensure_compiled(2, params, cache, tokens).as_text()
    for scope in ("embed", "attn", "mlp", "head", "gather", "scatter"):
        assert f"/{scope}/" in hlo, scope
    assert "_step_fn" in hlo
