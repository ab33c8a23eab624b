"""Serving driver: the continuous-batching engine on the paged KV-cache.

Runs a real (reduced) model through ``repro.serving.ServeEngine`` over a
synthetic request trace — requests flow queue -> chunked prefill -> batched
decode -> completion with zero manual submit() calls — and reports
throughput, TTFT, page-pool telemetry, and the arena-vs-pool memory
comparison at full arch scale (``ServingArena`` is kept as the
slab-per-request baseline).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --requests 8

``--preset full`` runs the published config unchanged (its dtype, every
layer, the full vocabulary) with the weights held in that dtype; the other
presets are the reduced float32 configs of ``launch.train.reduced_config``.

``--share-hbm GB``: one budget, two workloads — a fine-tune step of the same
(reduced) model is registered as the training tenant of a ``SharedArena``,
the page pool becomes the serving tenant, and admission is gated against the
serving share of the jointly planned split.  The loop then *executes* the
joint plan: real jitted fine-tune steps run at the valley phases
``SharedPlan.schedule`` picked, interleaved with engine decode steps in one
process, and both workloads' measured step times are reported.

``--runner`` (default): decode replays the pre-compiled bucketed
``DecodeRunner`` ladder — steady state performs zero retraces
(``runner_compile_total`` stays flat after warmup).  ``--no-runner`` falls
back to the legacy full-batch decode jit for comparison.
"""
from __future__ import annotations

import argparse
import random
import time

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..core import MemoryPlanner, SharedArena, profile_fn
from ..models import Transformer
from ..obs import (ChromeTraceBuilder, DriftMonitor, SLOEngine, SLOSpec,
                   SpanTracker, Tracer, get_tracer, use_tracer)
from ..runtime.compile_cache import enable_compile_cache
from ..runtime.serve_lib import ServingArena, synth_trace
from ..serving import GenRequest, ServeEngine
from .train import PRESETS, reduced_config


def make_train_step(model, params, seq: int, batch: int, lr: float = 1e-3,
                    seed: int = 0):
    """One real jitted SGD fine-tune step on a private params replica (the
    training tenant's executable; serving keeps decoding its own weights)."""
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 7),
                                (batch, seq + 1), 0, model.cfg.vocab_size)
    tbatch = {"tokens": tokens}

    @jax.jit
    def ft(p):
        loss, grads = jax.value_and_grad(
            lambda q: model.loss_fn(q, tbatch, remat=False)[0])(p)
        return loss, jax.tree.map(lambda a, g: a - lr * g, p, grads)

    state = {"p": jax.tree.map(jnp.asarray, params)}

    def step():
        loss, state["p"] = ft(state["p"])
        return loss

    return step


def run_interleaved(eng, live, shared, train_step, max_steps: int = 100_000):
    """Execute the joint plan: engine steps with fine-tune steps fired at the
    valley phases the ``SharedArena`` scheduled, all in one process."""
    jp = shared.plan()
    window = max(1, jp.profile.meta.get("window_steps", 1))
    phases = set(jp.schedule.get("training", []))
    pending = sorted(live, key=lambda r: (r.arrival, r.rid))
    train_s, n_train, last_loss = 0.0, 0, None
    while pending or not eng.sched.idle:
        while pending and pending[0].arrival <= eng.step_count:
            eng.enqueue(pending.pop(0))
        eng.step()
        if phases and (eng.step_count - 1) % window in phases:
            t0 = time.perf_counter()
            last_loss = float(jax.block_until_ready(train_step()))
            train_s += time.perf_counter() - t0
            n_train += 1
        if eng.step_count >= max_steps:
            raise RuntimeError(f"engine did not drain in {max_steps} steps")
    return eng.metrics.summary(eng.kv.stats()), {
        "n_train_steps": n_train,
        "train_step_ms_mean": 1e3 * train_s / n_train if n_train else None,
        "train_loss": last_loss,
        "window_steps": window,
        "phases": sorted(phases),
    }


def main(argv=None) -> ServeEngine:
    """Parse ``argv``, serve the trace, print the report; returns the
    drained engine (``engine.completed`` holds every token stream)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--preset", default="tiny",
                    choices=sorted(PRESETS) + ["full"],
                    help="'full': the published config as is; otherwise a "
                         "reduced float32 config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--page-tokens", type=int, default=None,
                    help="page size in tokens (default: profile-guided)")
    ap.add_argument("--policy", choices=["fcfs", "priority"], default="fcfs")
    ap.add_argument("--prefill-chunk", type=int, default=512)
    ap.add_argument("--share-hbm", type=float, default=0.0,
                    help="GB of one HBM budget shared with a concurrent "
                         "fine-tune tenant (0 = serving owns its arena)")
    ap.add_argument("--train-steps", type=int, default=4,
                    help="--share-hbm: fine-tune steps per serving round")
    ap.add_argument("--runner", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="decode via the pre-compiled bucketed DecodeRunner "
                         "(--no-runner: legacy full-batch decode jit)")
    ap.add_argument("--attn", choices=["gather", "paged"], default="gather",
                    help="decode KV layout: 'gather' copies each slot's "
                         "contiguous cache rows through the runner; 'paged' "
                         "runs the Pallas paged-attention kernel straight "
                         "off the page pool (requires --runner; without a "
                         "TPU set REPRO_PALLAS_INTERPRET=1 for interpret "
                         "mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(runtime events + per-request span tracks + "
                         "packed-plan rectangles)")
    ap.add_argument("--metrics", action="store_true",
                    help="print the metrics registry as Prometheus text")
    ap.add_argument("--slo-ttft", type=float, default=None, metavar="STEPS",
                    help="TTFT ceiling (engine steps); enables the SLO report")
    ap.add_argument("--slo-tpot", type=float, default=None, metavar="STEPS",
                    help="per-token decode-cadence ceiling (engine steps)")
    ap.add_argument("--slo-e2e", type=float, default=None, metavar="STEPS",
                    help="enqueue->finish ceiling (engine steps)")
    args = ap.parse_args(argv)
    if args.preset == "full" and args.share_hbm > 0:
        ap.error("--share-hbm fine-tunes a reduced config; use a reduced "
                 "--preset with it")
    enable_compile_cache()

    # full-size arch for the memory accounting
    full_cfg = get_config(args.arch)
    if args.preset == "full":
        cfg, seq, batch = full_cfg, None, None
    else:
        cfg, seq, batch = reduced_config(args.arch, args.preset)
    model = Transformer(cfg)
    # serving holds its weights in the compute dtype (a no-op for the
    # float32 reduced presets); one jitted init compiles one program where
    # an eager init compiles one per parameter shape
    params = jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(cfg.dtype), model.init(key)))(
            jax.random.PRNGKey(args.seed))

    # profile run: the sample trace the planner sizes the page pool from
    trace = synth_trace(args.requests, args.prompt_len, args.gen_len,
                        seed=args.seed, jitter=False)

    acct = ServingArena(full_cfg, trace)
    cmp = acct.compare_pool()
    print(f"[{args.arch} @ full size] slab baseline for {len(trace)} requests: "
          f"dsa={cmp['dsa_peak'] / 1e9:.2f}GB pool={cmp['pool_peak'] / 1e9:.2f}GB "
          f"naive={cmp['naive_peak'] / 1e9:.2f}GB "
          f"saving_vs_pool={100 * cmp['saving_vs_pool']:.1f}%")

    shared = None
    if args.share_hbm > 0:
        # one budget, two workloads: register the fine-tune tenant first so
        # the engine's first joint plan sees both
        shared = SharedArena(int(args.share_hbm * 2 ** 30))
        planner = MemoryPlanner()
        bsds = {"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)}
        tprof = profile_fn(
            jax.grad(lambda p, b: model.loss_fn(p, b, remat=False)[0]),
            model.abstract(), bsds)
        tview = shared.register_training(
            tprof, steps_per_round=args.train_steps,
            shrink=lambda target: planner.plan_with_remat(
                tprof, target_peak=target).profile)

    eng = ServeEngine(model, params, sample_trace=trace, max_len=args.max_len,
                      max_batch=args.max_batch, page_tokens=args.page_tokens,
                      policy=args.policy, prefill_chunk=args.prefill_chunk,
                      accounting_cfg=full_cfg, shared=shared,
                      use_runner=args.runner, attn_mode=args.attn)
    if args.runner:
        eng.warmup()
        print(f"[runner] buckets={list(eng.runner.buckets)} warmed "
              f"{eng.runner.n_compiles} compiles (+{eng.prefill_compiles} "
              f"prefill) in {eng.warmup_s:.1f}s")
    kv = eng.kv.stats()
    print(f"[paged pool] page_tokens={kv['page_tokens']} "
          f"n_pages={kv['n_pages']} pool={kv['pool_bytes'] / 1e6:.2f}MB "
          f"(planned peak {kv['planned_peak'] / 1e6:.2f}MB)")
    if shared is not None:
        s = shared.stats()
        print(f"[shared arena] budget={s['hbm_budget'] / 1e9:.2f}GB "
              f"joint_peak={s['joint_peak'] / 1e6:.2f}MB "
              f"standalone_sum={s['standalone_sum'] / 1e6:.2f}MB "
              f"win={s['sharing_win'] / 1e6:.2f}MB "
              f"(joint/sum={s['joint_vs_sum']:.2f}) "
              f"train_steps@{s['schedule'].get('training', [])} "
              f"serving_cap={eng.sched.cap} "
              f"train_budget={tview.budget / 1e6:.2f}MB")

    # live traffic: same shapes with jitter, so some requests outgrow the
    # profile and exercise preemption + §4.3 replanning
    rng = random.Random(args.seed + 1)
    live = [GenRequest(rid=r.rid,
                       prompt=jax.random.randint(jax.random.PRNGKey(r.rid),
                                                 (r.prompt_len,), 0,
                                                 cfg.vocab_size),
                       gen_len=max(2, r.gen_len + rng.randint(-2, 6)),
                       arrival=r.arrival)
            for r in trace]
    want_slo = any(v is not None
                   for v in (args.slo_ttft, args.slo_tpot, args.slo_e2e))
    # a caller's active tracer keeps the events when none is asked for here
    tracer = Tracer() if (args.trace or want_slo) else get_tracer()
    colocated = None
    with use_tracer(tracer):
        if shared is not None:
            # execute the joint plan: fine-tune steps at the valley phases
            train_step = make_train_step(model, params, seq, batch,
                                         seed=args.seed)
            summary, colocated = run_interleaved(eng, live, shared, train_step)
        else:
            summary = eng.run(live)
    tracker = None
    if tracer is not None:
        # fold the event stream into per-request spans (queue/prefill/
        # decode/preempted) — the trace export and SLO report read these
        tracker = SpanTracker().feed(tracer.events())
    if args.trace:
        tb = ChromeTraceBuilder()
        tb.add_events(tracer.events())
        tb.add_events(tracker.to_events())
        tb.add_plan("kv-pool", eng.kv.plan.profile)
        if shared is not None:
            jp = shared.plan()
            tb.add_plan("joint", jp.profile, plan=jp.plan)
        tb.write(args.trace)
        print(f"[trace] {len(tracer.events())} events "
              f"(dropped {tracer.n_dropped}), "
              f"{len(tracker.finished())} request spans -> {args.trace}")
    if want_slo:
        slo = SLOEngine(SLOSpec(ttft_steps=args.slo_ttft,
                                tpot_steps=args.slo_tpot,
                                e2e_steps=args.slo_e2e))
        slo.observe_spans(tracker.finished())
        rep = slo.report(n_steps=eng.step_count, wall_s=summary["wall_s"])
        att = rep["attainment"]
        print(f"[slo] attainment={'n/a' if att is None else f'{att:.3f}'} "
              f"({rep['n_met']}/{rep['n_requests']}) "
              f"goodput={rep['goodput_tokens_per_step']:.2f} tok/step "
              f"({rep['goodput_tokens_per_s']:.1f} tok/s) "
              f"ttft_p99={rep['ttft_steps']['p99']} "
              f"e2e_p99={rep['e2e_steps']['p99']}")
    drift = DriftMonitor(eng.kv.plan.profile)
    drift.observe_arena(eng.kv.arena)
    d = drift.report()
    print(f"[drift] planned={d['planned_peak'] / 1e6:.2f}MB "
          f"observed={d['observed_peak'] / 1e6:.2f}MB "
          f"peak_ratio={d['peak_ratio']:.2f} "
          f"frag={d['fragmentation']:.2f} "
          f"replans={d['n_replans']} causes={d['replan_causes']}")
    if args.metrics:
        print(eng.metrics.registry.to_prometheus_text(), end="")
    if eng.decode_steps:
        mode = "runner" if args.runner else "legacy"
        compiles = (eng.runner.n_compiles if eng.runner is not None
                    else eng.decode_compiles)
        print(f"[decode:{mode}] steps={eng.decode_steps} "
              f"step_ms={1e3 * eng.decode_time_s / eng.decode_steps:.2f} "
              f"compiles={compiles} prefill_compiles={eng.prefill_compiles}")
    if colocated is not None:
        tms = colocated["train_step_ms_mean"]
        print(f"[colocated] train_steps={colocated['n_train_steps']} "
              f"at phases {colocated['phases']} "
              f"(window={colocated['window_steps']}) "
              f"train_step_ms={'n/a' if tms is None else f'{tms:.1f}'} "
              f"loss={colocated['train_loss']}")
    ttft = summary["ttft_steps_mean"]
    print(f"completed {summary['n_completed']}/{summary['n_requests']} "
          f"requests, {summary['tokens']} tokens in {summary['wall_s']:.1f}s "
          f"({summary['tokens_per_s']:.1f} tok/s), "
          f"ttft_mean={'n/a' if ttft is None else f'{ttft:.1f}'} steps, "
          f"max_concurrent={summary['max_concurrent']}, "
          f"preemptions={summary['n_preemptions']}, "
          f"reopts={summary['kv_n_reopt']}")
    for rid in sorted(eng.completed)[:3]:
        print(f"  req {rid}: {eng.completed[rid][:8]}...")
    if shared is not None:
        print(f"[shared arena] boundary_reopts={shared.n_reopt} "
              f"feasible={shared.plan().feasible} "
              f"reserves={{'serving': {shared.plan().reserves['serving'] / 1e6:.1f}MB, "
              f"'training': {shared.plan().reserves['training'] / 1e6:.1f}MB}}")
    return eng


if __name__ == "__main__":
    main()
