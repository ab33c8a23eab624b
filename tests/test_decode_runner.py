"""DecodeRunner + engine execution exactness: staggered-admission parity,
the zero-retrace invariant, the prefill length ladder, and sustained-load
epoch closing."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import Transformer
from repro.obs.metrics import MetricsRegistry, use_registry
from repro.obs.trace import Tracer, use_tracer
from repro.runtime.serve_lib import Request
from repro.serving import DecodeRunner, GenRequest, ServeEngine, bucket_ladder


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("qwen2-0.5b").smoke()
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, rid, n):
    return jax.random.randint(jax.random.PRNGKey(rid), (n,), 0, cfg.vocab_size)


def _greedy_reference(model, params, prompt, gen_len, max_len):
    """Isolated single-request greedy decode: the ground truth an engine
    batch row must reproduce token for token."""
    logits, cache = model.prefill(params, {"tokens": prompt[None, :]},
                                  max_len=max_len)
    tok = jnp.argmax(logits[0]).astype(jnp.int32)
    out = [int(tok)]
    for _ in range(gen_len - 1):
        logits, cache = model.decode_step(params, cache, tok[None])
        tok = jnp.argmax(logits[0]).astype(jnp.int32)
        out.append(int(tok))
    return out


# ---------------------------------------------------------------------------
# ladder mechanics
# ---------------------------------------------------------------------------


def test_bucket_ladder_shape():
    assert bucket_ladder(1) == (1,)
    assert bucket_ladder(8) == (1, 2, 4, 8)
    assert bucket_ladder(6) == (1, 2, 4, 6)     # non-pow2 max_batch included


def test_bucket_for_picks_smallest_fit(tiny_model):
    _, model, _ = tiny_model
    runner = DecodeRunner(model, max_batch=8)
    assert [runner.bucket_for(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    with pytest.raises(ValueError):
        runner.bucket_for(9)


# ---------------------------------------------------------------------------
# the headline bugfix: staggered unequal-prompt admissions decode exactly
# ---------------------------------------------------------------------------


def test_staggered_admission_parity(tiny_model):
    """Mid-stream admissions with unequal prompts must produce the same
    tokens as isolated single-request decode (per-slot position vector:
    the old scalar clock skewed every already-running request)."""
    cfg, model, params = tiny_model
    shapes = [(1, 5, 0), (2, 11, 1), (3, 17, 3), (4, 7, 5)]
    trace = [Request(rid=r, prompt_len=n, gen_len=8, arrival=a)
             for r, n, a in shapes]
    live = [GenRequest(rid=r, prompt=_prompt(cfg, r, n), gen_len=8, arrival=a)
            for r, n, a in shapes]
    eng = ServeEngine(model, params, sample_trace=trace, max_len=64,
                      max_batch=4, page_tokens=8)
    summary = eng.run(live)
    assert summary["n_completed"] == 4
    assert summary["max_concurrent"] >= 2           # genuinely batched
    for r in live:
        ref = _greedy_reference(model, params, r.prompt, 8, 64)
        assert eng.completed[r.rid] == ref, f"rid={r.rid}"


def test_runner_logits_match_isolated_rows(tiny_model):
    """Runner padding (repeat-last-slot) must not perturb real rows."""
    cfg, model, params = tiny_model
    max_batch, s = 4, 10
    tokens = jax.random.randint(jax.random.PRNGKey(7), (max_batch, s),
                                0, cfg.vocab_size)
    _, cache = model.prefill(params, {"tokens": tokens}, max_len=16)
    runner = DecodeRunner(model, max_batch=max_batch)
    tok_vec = tokens[:, -1]
    ref_logits, _ = model.decode_step(params, cache, tok_vec)
    for n in (1, 3):                                # 3 pads up to bucket 4
        logits, _ = runner.step(params, cache, tok_vec, list(range(n)))
        assert logits.shape[0] == n
        assert float(jnp.abs(logits - ref_logits[:n]).max()) < 1e-5


# ---------------------------------------------------------------------------
# zero-retrace invariant
# ---------------------------------------------------------------------------


def test_zero_recompiles_after_warmup(tiny_model):
    """>=100 steady-state steps of admission/finish churn: the runner compile
    count (and the runner_compile_total registry counter) stay flat."""
    cfg, model, params = tiny_model
    trace = [Request(rid=i + 1, prompt_len=8, gen_len=6, arrival=3 * i)
             for i in range(40)]
    live = [GenRequest(rid=r.rid, prompt=_prompt(cfg, r.rid, r.prompt_len),
                       gen_len=r.gen_len, arrival=r.arrival) for r in trace]
    eng = ServeEngine(model, params, sample_trace=trace, max_len=32,
                      max_batch=4, page_tokens=8)
    reg = MetricsRegistry()
    with use_registry(reg):
        eng.warmup()
        warm = eng.runner.n_compiles
        warm_counter = reg.counter("runner_compile_total").value
        summary = eng.run(live)
    assert warm == len(eng.runner.buckets)          # one AOT compile per bucket
    assert eng.step_count >= 100
    assert summary["n_completed"] == 40
    assert eng.runner.n_compiles == warm            # flat across the whole run
    assert reg.counter("runner_compile_total").value == warm_counter


def test_warmup_precompiles_prefill_ladder(tiny_model):
    """warmup() walks the whole prompt ladder, so a warmed engine performs
    zero prefill retraces at serving time (not just zero decode retraces)."""
    cfg, model, params = tiny_model
    lengths = [5, 6, 7, 9, 11, 13, 17, 23]
    trace = [Request(rid=i + 1, prompt_len=n, gen_len=2, arrival=2 * i)
             for i, n in enumerate(lengths)]
    live = [GenRequest(rid=r.rid, prompt=_prompt(cfg, r.rid, r.prompt_len),
                       gen_len=r.gen_len, arrival=r.arrival) for r in trace]
    eng = ServeEngine(model, params, sample_trace=trace, max_len=32,
                      max_batch=4, page_tokens=8)
    eng.warmup()
    assert eng.prefill_compiles == 3                # buckets {8, 16, 32}
    warm = eng.prefill_compiles
    summary = eng.run(live)
    assert summary["n_completed"] == len(lengths)
    assert eng.prefill_compiles == warm             # flat: ladder pre-warmed


# ---------------------------------------------------------------------------
# the paged execution path: token-exactness and the zero-retrace invariant
# ---------------------------------------------------------------------------


def _churn_workload(cfg, n=24):
    """Profile says short generations; live traffic runs much longer, so the
    pool is undersized and decode-outrun preemptions churn the batch."""
    trace = [Request(rid=i + 1, prompt_len=5 + (3 * i) % 12, gen_len=4,
                     arrival=2 * i) for i in range(n)]
    live = [GenRequest(rid=r.rid, prompt=_prompt(cfg, r.rid, r.prompt_len),
                       gen_len=10 + r.rid % 7, arrival=r.arrival)
            for r in trace]
    return trace, live


def _run_mode(model, params, trace, live, attn_mode):
    eng = ServeEngine(model, params, sample_trace=trace, max_len=64,
                      max_batch=4, page_tokens=8, attn_mode=attn_mode)
    reg = MetricsRegistry()
    with use_registry(reg), use_tracer(Tracer()) as tracer:
        eng.warmup()
        warm_runner = eng.runner.n_compiles
        warm_prefill = eng.prefill_compiles
        summary = eng.run(live)
    assert eng.runner.n_compiles == warm_runner     # zero decode retraces
    assert eng.prefill_compiles == warm_prefill     # zero prefill retraces
    summary["decode_slots"] = [(ev.step, ev.args["slots"])
                               for ev in tracer.events()
                               if ev.name == "decode"]
    return eng, summary


def test_paged_token_parity_under_preemption_churn(tiny_model):
    """The whole PR's gate: the paged kernel path must be token-exact
    against the legacy gather path across a run with real preemption churn
    (restarts, page recycling, table-row rewrites), with the runner compile
    counters flat in both modes."""
    cfg, model, params = tiny_model
    trace, live = _churn_workload(cfg)
    gather, s_g = _run_mode(model, params, trace, live, "gather")
    paged, s_p = _run_mode(model, params, trace, live, "paged")
    assert s_g["n_completed"] == s_p["n_completed"] == len(live)
    assert s_p["n_preemptions"] == s_g["n_preemptions"] > 0  # genuine churn
    assert paged.completed == gather.completed      # token-exact, every rid
    assert paged.step_count >= 100                  # sustained churn window
    # the decode trace names each step's slots, and both engines run the
    # same schedule
    assert s_p["decode_slots"] == s_g["decode_slots"]
    assert len(s_g["decode_slots"]) == gather.decode_steps
    assert all(0 < len(sl) <= 4 and sl == sorted(set(sl)) and
               set(sl) <= set(range(4)) for _, sl in s_g["decode_slots"])


def test_paged_staggered_admissions_match_isolated_decode(tiny_model):
    """Paged rows must also reproduce isolated single-request greedy decode
    (same oracle as the gather-path staggered test)."""
    cfg, model, params = tiny_model
    shapes = [(1, 5, 0), (2, 11, 1), (3, 17, 3), (4, 7, 5)]
    trace = [Request(rid=r, prompt_len=n, gen_len=8, arrival=a)
             for r, n, a in shapes]
    live = [GenRequest(rid=r, prompt=_prompt(cfg, r, n), gen_len=8, arrival=a)
            for r, n, a in shapes]
    eng = ServeEngine(model, params, sample_trace=trace, max_len=64,
                      max_batch=4, page_tokens=8, attn_mode="paged")
    summary = eng.run(live)
    assert summary["n_completed"] == 4
    assert summary["max_concurrent"] >= 2
    for r in live:
        ref = _greedy_reference(model, params, r.prompt, 8, 64)
        assert eng.completed[r.rid] == ref, f"rid={r.rid}"


def test_paged_mode_requires_runner(tiny_model):
    cfg, model, params = tiny_model
    trace = [Request(rid=1, prompt_len=8, gen_len=4, arrival=0)]
    with pytest.raises(ValueError, match="use_runner"):
        ServeEngine(model, params, sample_trace=trace, max_len=32,
                    max_batch=2, page_tokens=8, use_runner=False,
                    attn_mode="paged")
    with pytest.raises(ValueError, match="attn_mode"):
        ServeEngine(model, params, sample_trace=trace, max_len=32,
                    max_batch=2, page_tokens=8, attn_mode="chunky")


def test_prefill_length_ladder_bounds_retraces(tiny_model):
    """8 distinct prompt lengths must collapse onto the power-of-two ladder
    (3 buckets here), not trace once per length."""
    cfg, model, params = tiny_model
    lengths = [5, 6, 7, 9, 11, 13, 17, 23]
    trace = [Request(rid=i + 1, prompt_len=n, gen_len=2, arrival=2 * i)
             for i, n in enumerate(lengths)]
    live = [GenRequest(rid=r.rid, prompt=_prompt(cfg, r.rid, r.prompt_len),
                       gen_len=r.gen_len, arrival=r.arrival) for r in trace]
    eng = ServeEngine(model, params, sample_trace=trace, max_len=32,
                      max_batch=4, page_tokens=8)
    summary = eng.run(live)
    assert summary["n_completed"] == len(lengths)
    assert eng.prefill_compiles == 3                # buckets {8, 16, 32}
    assert eng.prefill_compiles < len(set(lengths))


# ---------------------------------------------------------------------------
# sustained-load epoch closing
# ---------------------------------------------------------------------------


def _busy_engine(model, params, cfg, replan_interval):
    trace = [Request(rid=i + 1, prompt_len=8, gen_len=4, arrival=0)
             for i in range(3)]
    eng = ServeEngine(model, params, sample_trace=trace, max_len=64,
                      max_batch=3, page_tokens=8,
                      replan_interval=replan_interval)
    for r in trace:
        eng.enqueue(GenRequest(rid=r.rid,
                               prompt=_prompt(cfg, r.rid, r.prompt_len),
                               gen_len=40, arrival=0))
    while not eng.sched.idle and eng.step_count < 32:
        eng.step()
    assert not eng.sched.idle                       # still under load
    return eng


def test_replan_interval_fires_under_sustained_load(tiny_model):
    """Continuous traffic past the profile never goes idle, so the old
    idle-only epoch close starved §4.3 replans; the interval clock fires
    them mid-flight."""
    cfg, model, params = tiny_model
    eng = _busy_engine(model, params, cfg, replan_interval=8)
    assert eng.kv.stats()["n_reopt"] >= 1           # replanned while busy
    starved = _busy_engine(model, params, cfg, replan_interval=None)
    assert starved.kv.stats()["n_reopt"] == 0       # the bug being fixed
