"""Serve qwen2-0.5b at its published width on one TPU chip, end to end.

    python chip_smoke.py

One process, one chip.  It drives the normal serving entry point
(``repro.launch.serve``, ``--preset full``: bf16, all 24 layers, the full
151,936-token vocabulary, random weights from ``--seed``) twice over the same
8 requests: once with the gather decode path and once with the Pallas
paged-attention kernel.  It then checks that

  * every request is served, with in-vocabulary tokens and finite logits;
  * replayed teacher forced, each decode step in the bucket it ran in and
    from the state it ran from, gather reproduces every token it served,
    paged picks gather's token at nearly every step, and each request's
    served paged stream equals its gather stream up to the first step whose
    replay put the two paths apart;
  * the compiled paged decode step holds the kernel (``tpu_custom_call``);
  * the kernel agrees with its oracle (``kernels/ref.py``) at real widths.

Each phase prints what it saw.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  Without a TPU, or with ``REPRO_PALLAS_INTERPRET`` set, it exits
non-zero before serving anything.  Compiled programs are cached in
``JAX_COMPILATION_CACHE_DIR`` if set, else in ``<checkout>/.jax_cache``; a
second run in the same checkout compiles less than the first.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro.core.peaks import peaks_for                          # noqa: E402
from repro.kernels import ops as kops                           # noqa: E402
from repro.kernels.ref import ref_paged_attention               # noqa: E402
from repro.launch import serve                                  # noqa: E402
from repro.obs.trace import Tracer, use_tracer                  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache    # noqa: E402

ARCH = "qwen2-0.5b"
N_REQUESTS = 8
PROMPT_LEN = 256
GEN_LEN = 32
MAX_LEN = 320          # >= prompt + the longest jittered generation (+6)
KERNEL_TOL_BF16 = 2e-2
# share of teacher-forced steps at which paged must pick gather's token.
# Not 1: from the same state the two paths differ only in attention
# rounding, but 24 random bf16 layers can amplify one flipped rounding past
# the gap between near-tied logits (1 step of 267 on a TPU v5e).  A paged
# layout or indexing fault disagrees at most steps (the new token's KV
# written one slot late: 183 of 267).
MIN_FORCED_AGREEMENT = 0.97


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileLog:
    """Backend compile seconds, persistent-cache hits and cache writes
    (programs compiled afresh and stored), from JAX's own monitoring
    events."""

    def __init__(self):
        self.seconds, self.hits, self.writes = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return self.seconds, self.hits, self.writes


def serve_phase(attn: str, log: CompileLog):
    """Serve the requests through ``launch/serve.py``; returns the drained
    engine and the events it traced."""
    c0, t0 = log.snapshot(), time.perf_counter()
    with use_tracer(Tracer()) as tracer:
        eng = serve.main(["--arch", ARCH, "--preset", "full",
                          "--requests", str(N_REQUESTS),
                          "--max-batch", str(N_REQUESTS),
                          "--prompt-len", str(PROMPT_LEN),
                          "--gen-len", str(GEN_LEN),
                          "--max-len", str(MAX_LEN),
                          "--attn", attn, "--seed", "0"])
    wall = time.perf_counter() - t0
    c1 = log.snapshot()
    cfg = eng.model.cfg
    leaves = jax.tree.leaves(eng.params)
    dtypes = sorted({str(x.dtype) for x in leaves})
    n_tokens = sum(len(v) for v in eng.completed.values())
    print(f"[{attn}] arch={cfg.name} layers={cfg.n_layers} d_model="
          f"{cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} vocab="
          f"{cfg.vocab_size} params={sum(x.size for x in leaves)} "
          f"dtype={','.join(dtypes)}")
    print(f"[{attn}] warmup_s={eng.warmup_s:.3f} phase_wall_s={wall:.3f} "
          f"backend_compile_s={c1[0] - c0[0]:.3f} cache_hits={c1[1] - c0[1]} "
          f"cache_writes={c1[2] - c0[2]}")
    print(f"[{attn}] decode_steps={eng.decode_steps} ms_per_step="
          f"{1e3 * eng.decode_time_s / max(1, eng.decode_steps):.3f} "
          f"requests={len(eng.completed)}/{N_REQUESTS} tokens_served={n_tokens}")
    if dtypes != [cfg.dtype]:
        fail(f"{attn}: weights are {dtypes}, config says {cfg.dtype}")
    if len(eng.completed) != N_REQUESTS:
        fail(f"{attn}: served {len(eng.completed)} of {N_REQUESTS} requests")
    toks = np.concatenate([np.asarray(v) for v in eng.completed.values()])
    if toks.min() < 0 or toks.max() >= cfg.vocab_size:
        fail(f"{attn}: token ids outside [0, {cfg.vocab_size})")
    return eng, tracer.events()


def check_logits(eng) -> None:
    """One full-width prefill through the engine's own jitted step."""
    prompt = jnp.arange(PROMPT_LEN, dtype=jnp.int32) % eng.model.cfg.vocab_size
    logits, _ = eng.prefill(eng.params, {
        "tokens": prompt[None], "true_len": jnp.asarray(PROMPT_LEN, jnp.int32)})
    finite = bool(jnp.isfinite(logits).all())
    print(f"[logits] shape={tuple(logits.shape)} dtype={logits.dtype} "
          f"finite={finite}")
    if logits.shape != (1, eng.model.cfg.padded_vocab) or not finite:
        fail("prefill logits are not finite or have the wrong shape")


def first_difference(a, b) -> int:
    """Index of the first token where two streams differ (the shorter
    length if one is a prefix of the other)."""
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


def schedule(events):
    """What the engine ran, from its trace: ``[("prefill", rid, slot)]`` and
    ``[("decode", slots)]`` in order.  Fails on a preemption: a restarted
    request would have its earlier tokens served twice."""
    out = []
    for ev in events:
        if ev.name == "prefill":
            out.append(("prefill", ev.args["rid"], ev.args["slot"]))
        elif ev.name == "decode":
            out.append(("decode", tuple(ev.args["slots"])))
        elif ev.name == "preempt":
            fail(f"request {ev.args['rid']} was preempted; the replay "
                 "assumes none was")
    return out


def check_replay(gather, gather_events, paged, paged_events) -> None:
    """Replay every decode step the gather engine ran, teacher forced, in
    the bucket it ran in, through both decode paths.

    Each step starts from the gather engine's final cache rewound to where
    that step began: each live row's position set back, its KV cleared from
    there on, its input token the one it served before.  The paged runner
    gets the same state cut into pages behind an identity page table.  The
    gates:

      * gather's replay picks every token gather served (the state is
        rebuilt bit for bit, and each bucket's program is deterministic);
      * paged picks gather's token at ``MIN_FORCED_AGREEMENT`` of the steps;
      * each request's served paged stream equals its gather stream up to
        the first step whose replay put paged and gather apart.  This one
        checks what the replay bypasses: the paged engine's own prefill
        merge and the planner-assigned page tables it served from."""
    sched = schedule(gather_events)
    if schedule(paged_events) != sched:
        fail("the gather and paged engines ran different schedules")
    gc = gather.cache
    b, ppr = paged.cache["block_tables"].shape
    pt = paged.kv.page_tokens

    def to_pages(x):                        # (G,B,L,kv,hd) -> (G,B*ppr,kv,pt,hd)
        g, _, length, kv, hd = x.shape
        x = jnp.pad(x, ((0, 0), (0, 0), (0, ppr * pt - length), (0, 0), (0, 0)))
        x = x.reshape(g, b, ppr, pt, kv, hd).transpose(0, 1, 2, 4, 3, 5)
        return x.reshape(g, b * ppr, kv, pt, hd)

    def to_paged(c):
        return {"pos": c["pos"],
                "block_tables": jnp.arange(b * ppr, dtype=jnp.int32).reshape(
                    b, ppr),
                "pattern": {i: {"k_pages": to_pages(e["k"]),
                                "v_pages": to_pages(e["v"])}
                            for i, e in c["pattern"].items()}}

    if set(gc) != {"pos", "pattern"} or jax.tree.map(
            jnp.shape, to_paged(gc)) != jax.tree.map(jnp.shape, paged.cache):
        fail("the gather cache does not map onto the paged runner's pool")
    gc_len = gc["pattern"]["0"]["k"].shape[2]
    rid_of = {e[2]: e[1] for e in sched if e[0] == "prefill"}  # slot -> rid
    if len(rid_of) != N_REQUESTS or sorted(rid_of.values()) != sorted(
            gather.completed):
        fail(f"requests did not each hold their own slot: {rid_of}")
    streams = {s: gather.completed[r] for s, r in rid_of.items()}
    # prefill picks token 0 and leaves pos at the prompt length; each decode
    # step writes at pos, picks the next token and advances pos by one
    final_pos = np.asarray(gc["pos"])
    for s, x in streams.items():
        if final_pos[s] - len(x) + 1 != PROMPT_LEN:
            fail(f"slot {s}: final position {final_pos[s]} does not match "
                 f"its {len(x)} tokens")

    v = gather.model.cfg.vocab_size
    n_tok = {s: 1 for s in streams}         # tokens served so far, per slot
    by_bucket = {}                          # bucket -> [steps, served, same]
    n_bit, dmax = 0, 0.0
    first_miss = {}                         # rid -> first j where paths part
    missed = []
    for e in sched:
        if e[0] != "decode":
            continue
        slots = list(e[1])
        j = np.array([n_tok[s] for s in slots])
        pos = final_pos.copy()
        pos[slots] = PROMPT_LEN + j - 1
        tokens = np.zeros(b, np.int32)
        tokens[slots] = [streams[s][k - 1] for s, k in zip(slots, j)]
        want = np.array([streams[s][k] for s, k in zip(slots, j)])
        keep = (np.arange(gc_len) < pos[:, None])[None, :, :, None, None]

        def rewound():
            # fresh arrays per call: each runner donates its cache
            return {"pos": jnp.asarray(pos, jnp.int32), "pattern": {
                i: {n: jnp.where(keep, x, 0) for n, x in e.items()}
                for i, e in gc["pattern"].items()}}

        toks = jnp.asarray(tokens)
        lg, _ = gather.runner.step(gather.params, rewound(), toks, slots)
        lp, _ = paged.runner.step(paged.params, to_paged(rewound()), toks,
                                  slots)
        lg = np.asarray(lg.astype(jnp.float32))[:, :v]
        lp = np.asarray(lp.astype(jnp.float32))[:, :v]
        ag, ap = lg.argmax(-1), lp.argmax(-1)
        for r in np.flatnonzero(ag != ap):
            rid = rid_of[slots[r]]
            first_miss.setdefault(rid, int(j[r]))
            # gap: how far below its own top logit each path put the
            # other's pick
            missed.append(f"rid {rid} token {j[r]} (bucket "
                          f"{gather.runner.bucket_for(len(slots))}): gather "
                          f"{ag[r]} paged {ap[r]} gaps "
                          f"{lg[r, ag[r]] - lg[r, ap[r]]:.4f}/"
                          f"{lp[r, ap[r]] - lp[r, ag[r]]:.4f}")
        cnt = by_bucket.setdefault(gather.runner.bucket_for(len(slots)),
                                   [0, 0, 0])
        cnt[0] += len(slots)
        cnt[1] += int((ag == want).sum())
        cnt[2] += int((ag == ap).sum())
        n_bit += int((lg == lp).all(-1).sum())
        dmax = max(dmax, float(np.abs(lg - lp).max()))
        for s in slots:
            n_tok[s] += 1
    if n_tok != {s: len(x) for s, x in streams.items()}:
        fail(f"the trace's decode steps do not account for every served "
             f"token: {n_tok}")

    n_cmp, n_served, n_same = (sum(c[k] for c in by_bucket.values())
                               for k in range(3))
    print(f"[replay] {n_cmp} served tokens replayed in their own bucket: "
          f"gather replay == served {n_served}/{n_cmp}, paged == gather "
          f"{n_same}/{n_cmp}, bit-identical logit rows {n_bit}/{n_cmp}, "
          f"max|dlogit|={dmax:.4e}")
    for bk, (n, ok, same) in sorted(by_bucket.items()):
        print(f"[replay] bucket {bk}: steps {n} gather == served {ok} "
              f"paged == gather {same}")
    for m in missed:
        print(f"[replay] paged != gather at {m}")
    parts = {rid: first_difference(gather.completed[rid], paged.completed[rid])
             for rid in sorted(gather.completed)}
    print(f"[streams] served gather == paged, tokens before the first "
          f"difference: {parts}; first replay miss: {first_miss}")
    if n_served != n_cmp:
        fail(f"gather's replay picked its served token at only {n_served} of "
             f"{n_cmp} steps")
    if n_same < MIN_FORCED_AGREEMENT * n_cmp:
        fail(f"paged picked gather's token at only {n_same} of {n_cmp} "
             f"teacher-forced steps")
    for rid, d in parts.items():
        n = len(gather.completed[rid])
        if len(paged.completed[rid]) != n or d < first_miss.get(rid, n):
            fail(f"rid {rid}: the paged engine's stream leaves gather's at "
                 f"token {d}, before any replayed step put them apart")


def check_kernel(eng) -> float:
    """The paged kernel against its gather-then-softmax oracle on the chip,
    at the served widths and page size, in bf16."""
    cfg = eng.model.cfg
    kv, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, \
        cfg.resolved_head_dim
    pt = eng.kv.page_tokens
    maxp = math.ceil(MAX_LEN / pt) + 1
    n_pool = N_REQUESTS * maxp
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((N_REQUESTS, kv, g, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((n_pool, kv, pt, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((n_pool, kv, pt, hd)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(n_pool).reshape(N_REQUESTS, maxp),
                         jnp.int32)
    pos = jnp.asarray(rng.integers(0, MAX_LEN, size=N_REQUESTS), jnp.int32)
    out = jax.jit(kops.paged_attention)(q, k, v, tables, pos)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_paged_attention)(q, k, v, tables, pos)
    err = float(jnp.abs(out.astype(jnp.float32) -
                        ref.astype(jnp.float32)).max())
    print(f"[kernel] paged_attention B={N_REQUESTS} KV={kv} G={g} hd={hd} "
          f"page_tokens={pt} pages_per_req={maxp} bf16 max_abs_err={err:.3e} "
          f"(tol {KERNEL_TOL_BF16})")
    if not err < KERNEL_TOL_BF16:
        fail(f"paged kernel diverged from its oracle: {err}")
    return err


def main() -> None:
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        fail("REPRO_PALLAS_INTERPRET is set: the kernels would not run "
             "compiled")
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        fail(f"no TPU found (JAX reports {dev.platform!r})")

    print(f"[cache] {enable_compile_cache()}")      # before the first compile
    log = CompileLog()
    print(f"[peaks] {peaks_for(dev.device_kind)}")

    gather, gather_events = serve_phase("gather", log)
    check_logits(gather)
    paged, paged_events = serve_phase("paged", log)

    check_replay(gather, gather_events, paged, paged_events)

    bucket = paged.runner.buckets[-1]
    hlo = paged.runner.executable(bucket).as_text()
    n_calls = hlo.count("tpu_custom_call")
    print(f"[paged step] bucket={bucket} tpu_custom_call occurrences={n_calls}")
    if n_calls == 0:
        fail("the compiled paged decode step holds no Pallas kernel")

    check_kernel(paged)

    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[memory] peak_bytes_in_use="
          f"{'not reported' if peak is None else peak}")
    secs, hits, writes = log.snapshot()
    print(f"[compile] total backend_compile_s={secs:.3f} cache_hits={hits} "
          f"cache_writes={writes}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
