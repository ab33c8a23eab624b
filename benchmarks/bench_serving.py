"""Beyond-paper: the DSA planner on LLM serving KV-cache traces.

Three levels:
  * planner level — per arch, the same Poisson-ish trace accounted three
    ways: paged-DSA (staircase page blocks packed by best-fit), the old
    slab-per-request accounting (one final-length rectangle per request,
    naive = no reuse), and the reactive pool replay.  The SSM row shows why
    O(1)-state archs barely need the planner at all.
  * engine level — a real (tiny) model driven through the new
    continuous-batching engine vs the old slot count: tokens/s, peak bytes,
    and max sustained concurrency.
  * measured level — the same live trace *executed* four ways: the Pallas
    paged-attention kernel (page table consumed in-kernel), its pure-jnp
    gather oracle, the runner over the contiguous cache (gather +
    contiguous flash), and the legacy full-batch ("slab") decode jit.
    Gates on measured tokens/s and decode step time, not planned bytes,
    asserts four-way token parity, and asserts the steady-state
    zero-retrace invariant (``runner_compiles_steady_delta == 0``) for the
    gather and paged paths alike.  A paged-attention microbench row times
    the kernel against the oracle outside the engine.

Emits ``BENCH_serving.json`` (machine-readable) next to the CSV lines to
seed the perf trajectory, plus ``TRACE_runner.json`` (Perfetto) for the
runner-mode run including its compile events.
"""
from __future__ import annotations

import json
import os
import random
import time

from repro.configs import get_config
from repro.runtime.serve_lib import Request
from repro.serving import plan_pool
from repro.serving.pages import choose_page_tokens

OUT_JSON = os.environ.get("BENCH_SERVING_JSON", "BENCH_serving.json")
TRACE_JSON = os.environ.get("TRACE_SERVING_JSON", "TRACE_serving.json")
TRACE_RUNNER_JSON = os.environ.get("TRACE_RUNNER_JSON", "TRACE_runner.json")


def synth_trace(n: int, seed: int = 0, prompt_hi: int = 4096,
                gen_hi: int = 768):
    """Arrivals paced so requests churn (finish while others run) — the
    regime where lifetime-aware packing beats a reactive pool."""
    rng = random.Random(seed)
    t = 0
    reqs = []
    for i in range(n):
        t += rng.randint(20, 220)
        reqs.append(Request(rid=i + 1,
                            prompt_len=rng.randint(64, prompt_hi),
                            gen_len=rng.randint(32, gen_hi),
                            arrival=t))
    return reqs


def planner_rows(quick: bool = False):
    out, records = [], []
    n = 20 if quick else 100
    for arch in ["qwen2-0.5b", "qwen3-moe-30b-a3b", "mistral-nemo-12b",
                 "mamba2-130m"]:
        cfg = get_config(arch)
        trace = synth_trace(n)
        # profile-guided page size on the dense flagship; fixed elsewhere
        if arch == "qwen2-0.5b":
            plan = choose_page_tokens(cfg, trace, candidates=(32, 64, 128))
        else:
            plan = plan_pool(cfg, trace, page_tokens=64)
        b = plan.baselines
        save_vs_slab = 1 - b["paged_dsa_peak"] / b["slab_peak"] \
            if b["slab_peak"] else 0.0
        rec = {
            "arch": arch, "n_requests": n,
            "page_tokens": plan.page_tokens,
            "n_pages": plan.n_pages,
            "paged_dsa_peak": b["paged_dsa_peak"],
            "slab_peak": b["slab_peak"],
            "pool_peak": b["pool_peak"],
            "slab_dsa_peak": b["slab_dsa_peak"],
            "lower_bound": b["lower_bound"],
            "saving_vs_slab": save_vs_slab,
        }
        records.append(rec)
        out.append((f"{arch}/n{n}", 0.0,
                    f"paged_dsa_GB={b['paged_dsa_peak'] / 1e9:.2f};"
                    f"slab_GB={b['slab_peak'] / 1e9:.2f};"
                    f"pool_GB={b['pool_peak'] / 1e9:.2f};"
                    f"slab_dsa_GB={b['slab_dsa_peak'] / 1e9:.2f};"
                    f"page_tokens={plan.page_tokens};"
                    f"saving_vs_slab={100 * save_vs_slab:.1f}%;"
                    f"lb_GB={b['lower_bound'] / 1e9:.2f}"))
    return out, records


def engine_row(quick: bool = False):
    """Drive the real tiny model through the new engine; compare sustained
    concurrency against the old engine's slot count on the same trace.

    The run is traced (``TRACE_serving.json``, Perfetto-loadable) and a
    ``DriftMonitor`` diffs the planned pool profile against what the arena
    actually observed — peak ratio, fragmentation, and per-cause replans."""
    import jax

    from repro.launch.train import reduced_config
    from repro.models import Transformer
    from repro.obs import ChromeTraceBuilder, DriftMonitor, Tracer, use_tracer
    from repro.serving import GenRequest, ServeEngine

    old_slots = 4
    n_req = 6 if quick else 12
    cfg, _, _ = reduced_config("qwen2-0.5b", "tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0))
    trace = [Request(rid=i + 1, prompt_len=8, gen_len=10, arrival=i)
             for i in range(n_req)]
    eng = ServeEngine(model, params, sample_trace=trace, max_len=64,
                      max_batch=2 * old_slots, page_tokens=8)
    # live traffic outgrows the profiled lengths (deterministic jitter), so
    # the drift section measures a real plan-vs-actual gap with replans
    rng = random.Random(1)
    live = [GenRequest(rid=r.rid,
                       prompt=jax.random.randint(jax.random.PRNGKey(r.rid),
                                                 (r.prompt_len,), 0,
                                                 cfg.vocab_size),
                       gen_len=max(2, r.gen_len + rng.randint(0, 16)),
                       arrival=r.arrival)
            for r in trace]
    tracer = Tracer()
    with use_tracer(tracer):
        s = eng.run(live)
    drift = DriftMonitor(eng.kv.plan.profile)
    drift.observe_arena(eng.kv.arena)
    tb = ChromeTraceBuilder()
    tb.add_events(tracer.events())
    tb.add_plan("kv-pool", eng.kv.plan.profile)
    tb.write(TRACE_JSON)
    rec = {
        "n_requests": n_req,
        "tokens_per_s": s["tokens_per_s"],
        "tokens": s["tokens"],
        "paged_pool_bytes": s["kv_pool_bytes"],
        "paged_planned_peak": s["kv_planned_peak"],
        "max_concurrent": s["max_concurrent"],
        "old_engine_slots": old_slots,
        "n_preemptions": s["n_preemptions"],
        "n_reopt": s["kv_n_reopt"],
        "ttft_steps_mean": s["ttft_steps_mean"],
        "drift": drift.report(),
        "replan_causes": dict(eng.kv.arena.replan_causes),
    }
    derived = (f"tok_per_s={s['tokens_per_s']:.1f};"
               f"pool_MB={s['kv_pool_bytes'] / 1e6:.3f};"
               f"max_concurrent={s['max_concurrent']};"
               f"old_slots={old_slots};"
               f"preempt={s['n_preemptions']};reopt={s['kv_n_reopt']}")
    return (f"engine/qwen2-0.5b-tiny/n{n_req}", 0.0, derived), rec


def measured_rows(quick: bool = False):
    """Execute (not just account) one live trace four ways and report what
    the clock saw:

      * ``paged_kernel`` — runner + Pallas paged-attention: the page table
        is consumed inside the decode executable, no KV gather/copy;
      * ``paged_ref``    — same paged cache, the pure-jnp gather oracle as
        the in-engine attention (differential baseline for the kernel);
      * ``paged_runner`` — runner over the contiguous cache (gather +
        contiguous flash — the execution the paged kernel replaces);
      * ``slab``         — legacy full-``max_batch`` decode jit.

    Every mode is exact (per-slot position vector / per-row page-table
    masking), so all four completed token streams must match — asserted
    here, making the speedups apples-to-apples.  The runner runs snapshot
    their compile counters after warmup, and the steady-state delta (the
    zero-retrace invariant) is part of the record for the gather AND paged
    paths.  On CPU the Pallas kernel runs in interpret mode (correctness
    and retrace accounting are the gate there; the fetch-only-owned-pages
    win is a TPU property)."""
    import jax

    from repro.launch.train import reduced_config
    from repro.models import RunOpts, Transformer
    from repro.obs import ChromeTraceBuilder, Tracer, use_tracer
    from repro.obs.metrics import MetricsRegistry, use_registry
    from repro.serving import GenRequest, ServeEngine

    n_req = 8 if quick else 16
    cfg, _, _ = reduced_config("qwen2-0.5b", "tiny")
    model = Transformer(cfg)
    model_ref = Transformer(cfg, RunOpts(paged_attn_impl="ref"))
    params = model.init(jax.random.PRNGKey(0))
    # varied prompt lengths exercise the prefill ladder; spaced arrivals hold
    # concurrency at 2-4 of the 8 slots, the regime where the slab pays for
    # every empty row each step and the bucket ladder decodes only what runs
    trace = [Request(rid=i + 1, prompt_len=5 + (3 * i) % 12,
                     gen_len=8 + i % 5, arrival=3 * i) for i in range(n_req)]

    def live():
        return [GenRequest(rid=r.rid,
                           prompt=jax.random.randint(jax.random.PRNGKey(r.rid),
                                                     (r.prompt_len,), 0,
                                                     cfg.vocab_size),
                           gen_len=r.gen_len, arrival=r.arrival)
                for r in trace]

    modes = (
        ("paged_kernel", model, True, "paged"),
        ("paged_ref", model_ref, True, "paged"),
        ("paged_runner", model, True, "gather"),
        ("slab", model, False, "gather"),
    )
    rows, completed = {}, {}
    for label, m, use_runner, attn_mode in modes:
        eng = ServeEngine(m, params, sample_trace=trace, max_len=64,
                          max_batch=8, page_tokens=8, use_runner=use_runner,
                          attn_mode=attn_mode)
        reg = MetricsRegistry()
        tracer = Tracer()
        with use_registry(reg), use_tracer(tracer):
            if use_runner:
                eng.warmup()        # AOT: buckets + the prompt ladder
                warm = eng.runner.n_compiles
            else:
                # prime the slab jit (and its eager argmax) so both timed
                # runs start compiled — warmup parity with the runner
                logits, _ = eng.decode(eng.params, eng.cache, eng.tokens)
                jax.numpy.argmax(logits, axis=-1)
            t0 = time.perf_counter()
            s = eng.run(live())
            wall = time.perf_counter() - t0
        row = {
            "n_requests": n_req,
            "tokens": s["tokens"],
            "n_completed": s["n_completed"],
            "wall_s": wall,
            "tokens_per_s_measured": s["tokens"] / wall if wall else 0.0,
            "decode_steps": eng.decode_steps,
            "decode_step_ms": 1e3 * eng.decode_time_s
            / max(1, eng.decode_steps),
            "prefill_compiles": eng.prefill_compiles,
            "n_preemptions": s["n_preemptions"],
        }
        if use_runner:
            row["runner_buckets"] = list(eng.runner.buckets)
            row["runner_compiles_warmup"] = warm
            row["runner_compiles_total"] = eng.runner.n_compiles
            row["runner_compiles_steady_delta"] = eng.runner.n_compiles - warm
            if label == "paged_runner":
                tb = ChromeTraceBuilder()
                tb.add_events(tracer.events())
                tb.add_plan("kv-pool", eng.kv.plan.profile)
                tb.write(TRACE_RUNNER_JSON)
        rows[label] = row
        completed[label] = eng.completed
    # exactness contract: execution strategy must not change the tokens
    for label in ("paged_kernel", "paged_ref", "slab"):
        assert completed[label] == completed["paged_runner"], \
            f"{label} vs paged_runner token streams diverged"

    def _speedup(a, b):             # step time of b over a
        return (rows[b]["decode_step_ms"] / rows[a]["decode_step_ms"]
                if rows[a]["decode_step_ms"] else 0.0)

    rec = {
        **rows,
        "parity_exact": True,
        "speedup_runner_vs_slab": _speedup("paged_runner", "slab"),
        "speedup_kernel_vs_gather": _speedup("paged_kernel", "paged_runner"),
        "speedup_kernel_vs_ref": _speedup("paged_kernel", "paged_ref"),
    }
    r = rows["paged_runner"]
    k = rows["paged_kernel"]
    derived = (f"tok_per_s={r['tokens_per_s_measured']:.1f};"
               f"step_ms={r['decode_step_ms']:.2f};"
               f"slab_step_ms={rows['slab']['decode_step_ms']:.2f};"
               f"kernel_step_ms={k['decode_step_ms']:.2f};"
               f"speedup={rec['speedup_runner_vs_slab']:.2f}x;"
               f"kernel_vs_gather={rec['speedup_kernel_vs_gather']:.2f}x;"
               f"compiles={r['runner_compiles_total']};"
               f"steady_delta={r['runner_compiles_steady_delta']};"
               f"paged_steady_delta={k['runner_compiles_steady_delta']}")
    return (f"measured/qwen2-0.5b-tiny/n{n_req}", 0.0, derived), rec


def kernel_row(quick: bool = False):
    """Paged-attention microbench: the kernel vs the gather oracle on one
    decode-shaped problem, outside the engine (pure attention op latency).
    On CPU the kernel runs interpreted — the row tracks correctness drift
    (max abs err vs the oracle) alongside the timings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    from repro.kernels.ref import ref_paged_attention

    b, kv, g, hd, pt, maxp = 8, 2, 2, 64, 8, 8
    rng = np.random.default_rng(0)
    n_pool = b * maxp
    q = jnp.asarray(rng.standard_normal((b, kv, g, hd)), jnp.float32)
    k_pages = jnp.asarray(rng.standard_normal((n_pool, kv, pt, hd)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((n_pool, kv, pt, hd)),
                          jnp.float32)
    tables = jnp.asarray(rng.permutation(n_pool).reshape(b, maxp), jnp.int32)
    positions = jnp.asarray(rng.integers(0, maxp * pt, size=b), jnp.int32)
    reps = 3 if quick else 10

    def bench(fn):
        out = jax.block_until_ready(fn(q, k_pages, v_pages, tables,
                                       positions))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jax.block_until_ready(fn(q, k_pages, v_pages, tables,
                                           positions))
        return out, 1e6 * (time.perf_counter() - t0) / reps

    kout, kus = bench(jax.jit(kops.paged_attention))
    rout, rus = bench(jax.jit(ref_paged_attention))
    err = float(jnp.abs(kout - rout).max())
    assert err < 2e-5, f"kernel diverged from oracle: {err}"
    rec = {"shape": {"batch": b, "kv_heads": kv, "group": g, "head_dim": hd,
                     "page_tokens": pt, "pages_per_req": maxp},
           "kernel_us": kus, "ref_us": rus, "max_abs_err": err,
           "interpret": kops.interpret_requested()}
    derived = (f"kernel_us={kus:.1f};ref_us={rus:.1f};"
               f"err={err:.2e};interpret={rec['interpret']}")
    return (f"kernel/paged_attention/b{b}", kus, derived), rec


def main(quick: bool = False):
    print("# Serving: name,us_per_call,derived")
    rows, records = planner_rows(quick)
    for name, us, derived in rows:
        print(f"serve/{name},{us:.3f},{derived}")
    erow, erec = engine_row(quick)
    print(f"serve/{erow[0]},{erow[1]:.3f},{erow[2]}")
    mrow, mrec = measured_rows(quick)
    print(f"serve/{mrow[0]},{mrow[1]:.3f},{mrow[2]}")
    krow, krec = kernel_row(quick)
    print(f"serve/{krow[0]},{krow[1]:.3f},{krow[2]}")
    with open(OUT_JSON, "w") as f:
        json.dump({"planner": records, "engine": erec,
                   "measured": mrec, "kernel": krec,
                   "drift": erec["drift"],
                   "replan_causes": erec["replan_causes"]}, f, indent=2)
    print(f"# wrote {OUT_JSON}, {TRACE_JSON} and {TRACE_RUNNER_JSON}")


if __name__ == "__main__":
    main()
