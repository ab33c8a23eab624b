"""Continuous-batching decode engine on the profile-guided paged KV-cache.

Relocated and rewritten from ``repro.runtime.serve_lib.ServeEngine``: the old
engine exposed manual ``submit()`` onto fixed slots with contiguous
final-length slabs; this one owns a waiting queue and admits from it every
step (``GenRequest.arrival`` honored by ``run()``), runs chunked prefill,
batched greedy decode, preempts on page-pool exhaustion, and replans the
pool at epoch boundaries when observed generation lengths outgrow the
profile (§4.3 under serving churn).

Physical execution is exact for staggered admissions: ``cache["pos"]`` is a
per-slot position vector, so every row attends and writes at its own offset
no matter when it was admitted or how long its prompt was.  The decode hot
path replays pre-compiled bucketed steps (``DecodeRunner``) and prompts are
padded to a power-of-two ladder before the jitted prefill, so steady-state
serving performs zero retraces (watch ``runner_compile_total`` /
``prefill_compile_total``).
"""
from __future__ import annotations

import math
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..configs.base import ModelConfig
from ..core.unified import SharedArena
from ..models.transformer import Transformer
from ..obs.metrics import get_registry
from ..obs.trace import NO_SPAN, get_tracer, span
from ..runtime.serve_lib import (Request, build_decode_step,
                                 build_prefill_step)
from . import pages as pages_lib
from .metrics import ServeMetrics
from .pages import PagePoolExhausted, PagedKVCache
from .runner import DecodeRunner
from .scheduler import GenRequest, RequestState, ScheduledRequest, Scheduler

PREFILL_BUCKET_MIN = 8          # floor of the power-of-two prompt ladder


class ServeEngine:
    """Queue -> chunked prefill -> batched decode, memory-planned end to end."""

    def __init__(self, model: Transformer, params, *,
                 sample_trace: Sequence[Request], max_len: int,
                 max_batch: int = 8, page_tokens: Optional[int] = None,
                 policy: str = "fcfs", prefill_chunk: int = 512,
                 hbm_budget: Optional[int] = None, reserve_pages: int = 0,
                 accounting_cfg: Optional[ModelConfig] = None,
                 mesh: Optional[Mesh] = None,
                 shared: Optional[SharedArena] = None,
                 metrics: Optional[ServeMetrics] = None,
                 use_runner: bool = True,
                 attn_mode: str = "gather",
                 replan_interval: Optional[int] = 64):
        """``accounting_cfg`` lets the page pool account at full-size arch
        scale while a reduced model executes (the launch-driver pattern).

        ``shared`` (the ``--share-hbm`` path): the page pool becomes the
        serving tenant of a ``SharedArena`` — admission is gated against the
        tenant's share of the joint budget (register any training tenant on
        the arena *before* constructing the engine, so the first joint plan
        sees both workloads).

        ``use_runner=False`` falls back to the legacy full-max_batch decode
        jit (the "slab" execution baseline the benches compare against).

        ``attn_mode="paged"`` executes decode straight off per-layer page
        pools: the PagedKVCache's exec page tables address the pools inside
        the attention kernel, so no contiguous per-request KV copy ever
        materializes.  Requires ``use_runner=True`` (a full-batch decode
        would let stale slots scatter their next token into page 0) and a
        pure-attention model (``model.supports_paged()``).

        ``replan_interval``: close a §4.3 epoch every this many steps even
        under sustained load (None = only when fully idle, the old behavior
        that starved decode-outrun replans on busy engines)."""
        self.model = model
        self.params = params
        self.max_len = max_len
        self.max_batch = max_batch
        acct = accounting_cfg or model.cfg
        self._acct = acct
        self._sample_trace = list(sample_trace)
        self.kv = PagedKVCache(acct, sample_trace, page_tokens=page_tokens,
                               reserve_pages=reserve_pages, shared=shared)
        if hbm_budget is None and self.kv.tenant is not None:
            # unified mode: the HBM gate is this tenant's share of the split
            hbm_budget = self.kv.tenant.budget
        cap = None
        if hbm_budget is not None:
            # the scheduler clamps to max_batch anyway, so bound the feasible-
            # batch search there: each probe packs a b-request wave (~quadratic
            # in its page count) and an uncapped search under a generous budget
            # explores thousands of requests for an answer that gets clamped
            cap = pages_lib.max_concurrency(acct, sample_trace,
                                            self.kv.page_tokens, hbm_budget,
                                            hi=max_batch)
        self.sched = Scheduler(self.kv, max_batch=max_batch, policy=policy,
                               max_concurrency=cap, prefill_chunk=prefill_chunk)
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.prefill = build_prefill_step(model, mesh,
                                          trace_hook=self._on_prefill_trace)
        self.decode = build_decode_step(model, mesh, donate=False,
                                        trace_hook=self._on_decode_trace)
        self.runner = DecodeRunner(model, max_batch=max_batch,
                                   mesh=mesh) if use_runner else None
        self.replan_interval = replan_interval
        kinds = set(model.cfg.block_pattern) | set(model.cfg.tail_pattern)
        # prompt padding is exact only when every cache is positional
        # attention (recurrent/rolling state integrates pad tokens; MoE
        # capacity counts them into expert load)
        self._pad_prefill = (kinds <= {"attn"}
                             and not model.cfg.is_encoder_decoder
                             and not model.cfg.n_experts)
        self.prefill_compiles = 0
        self.decode_compiles = 0
        self.decode_steps = 0
        self.decode_time_s = 0.0
        self.warmup_s = 0.0
        if attn_mode not in ("gather", "paged"):
            raise ValueError(f"unknown attn_mode {attn_mode!r}")
        self.attn_mode = attn_mode
        if attn_mode == "paged":
            if not use_runner:
                raise ValueError(
                    "attn_mode='paged' requires use_runner=True: the legacy "
                    "full-batch decode advances every slot, so stale rows "
                    "would scatter their KV into page 0")
            if not (model.supports_paged() and self._pad_prefill):
                raise ValueError(
                    "attn_mode='paged' needs a pure-attention decoder "
                    f"(pattern {model.cfg.block_pattern}, "
                    f"tail {model.cfg.tail_pattern})")
            ept = self.kv.page_tokens
            # +1 page: the exec grant runs one token ahead of accounting
            # (decode writes position T before append_token commits T+1)
            self._pages_per_req = math.ceil(max_len / ept) + 1
            self._pool_pages = max_batch * self._pages_per_req
            self.cache = model.init_paged_cache(
                max_batch, n_pages=self._pool_pages, page_tokens=ept,
                pages_per_req=self._pages_per_req)
            self._slot_pages = [0] * max_batch  # synced table-row lengths
        else:
            self.cache = model.init_cache(max_batch, max_len)
        self.tokens = jnp.zeros((max_batch,), jnp.int32)
        self.step_count = 0
        self.completed: dict[int, list[int]] = {}

    # -- compile accounting (trace-time hooks: fire once per signature) -----------
    def _on_prefill_trace(self, batch) -> None:
        self.prefill_compiles += 1
        reg = get_registry()
        if reg is not None:
            reg.counter("prefill_compile_total",
                        "jitted prefill (re)traces").inc()
        t = get_tracer()
        if t is not None:
            t.instant("compile", "serving", track="prefill",
                      seq=int(batch["tokens"].shape[1]),
                      total=self.prefill_compiles)

    def _on_decode_trace(self, tokens) -> None:
        self.decode_compiles += 1
        t = get_tracer()
        if t is not None:
            t.instant("compile", "serving", track="decode",
                      batch=int(tokens.shape[0]), total=self.decode_compiles)

    def warmup(self) -> None:
        """Pre-compile every runner bucket *and* every prefill ladder shape
        so the serving loop never traces (the zero-retrace invariant holds
        from step 0 for decode and prefill alike).  ``warmup_s`` records the
        wall time, compilation included."""
        t0 = time.perf_counter()
        if self.runner is not None:
            self.runner.warmup(self.params, self.cache, self.tokens)
        if self._pad_prefill:
            padded = PREFILL_BUCKET_MIN
            while True:
                p = min(padded, self.max_len)
                self.prefill(self.params,
                             {"tokens": jnp.zeros((1, p), jnp.int32),
                              "true_len": jnp.asarray(p, jnp.int32)})
                if p >= self.max_len:
                    break
                padded *= 2
        jax.block_until_ready(self.cache)
        self.warmup_s = time.perf_counter() - t0

    # -- queue --------------------------------------------------------------------
    def enqueue(self, req: GenRequest) -> None:
        self.sched.enqueue(req)
        self.metrics.on_enqueue(req.rid, int(req.prompt.shape[0]),
                                self.step_count)
        t = get_tracer()
        if t is not None:
            # enqueue happens between engine steps: stamp the step the
            # request will first be visible to, so span accounting (queue =
            # admit_step - enqueue_step) matches ServeMetrics exactly
            t.set_step(self.step_count)
            t.instant("enqueue", "serving", track="queue", rid=req.rid,
                      prompt_len=int(req.prompt.shape[0]),
                      queue_depth=self.sched.queue_depth)

    @property
    def n_active(self) -> int:
        return self.sched.n_active

    # -- one engine step ------------------------------------------------------------
    def step(self) -> None:
        t = get_tracer()
        if t is not None:
            t.set_step(self.step_count)
        with (NO_SPAN if t is None else t.span(
                "step", "serving", "engine", step=self.step_count,
                n_active=self.sched.n_active,
                queue_depth=self.sched.queue_depth)):
            for sr in self.sched.admit(self.step_count):
                self.metrics.on_admit(sr.rid, self.step_count)
            for sr in self.sched.prefill_batch():
                if sr.state is RequestState.RUNNING:  # not preempted by an
                    self._model_prefill(sr)           # earlier grow this step
            self._decode_running()
            self.metrics.on_step(concurrent=self.sched.n_active,
                                 occupancy=self.kv.occupancy(),
                                 queue_depth=self.sched.queue_depth)
            self.step_count += 1
            # an epoch closes when the engine goes idle (§4.3 replan if
            # dirty) and, under sustained load that never goes idle, on a
            # clock so decode-outrun replans still fire (pool resize
            # respects live pages, so this is safe mid-flight)
            if self.sched.idle or (self.replan_interval and self.step_count
                                   % self.replan_interval == 0):
                with span("epoch", "serving", "engine"):
                    self.kv.reset_epoch()
                    self._refresh_cap()

    def _refresh_cap(self) -> None:
        """Unified mode: a boundary replan may have rebalanced the split, so
        re-gate admission against the serving tenant's current share."""
        if self.kv.tenant is None:
            return
        cap = pages_lib.max_concurrency(self._acct, self._sample_trace,
                                        self.kv.page_tokens,
                                        self.kv.tenant.budget,
                                        hi=self.max_batch)
        self.sched.cap = max(1, min(self.max_batch, cap))

    def _prefill_batch(self, prompt) -> dict:
        """Pad the prompt to a power-of-two ladder so the jitted prefill sees
        O(log max_len) shapes instead of one trace per prompt length.  The
        padded tail is exact: logits are read at ``true_len - 1`` and decode
        masks cache positions >= ``true_len`` until they are overwritten."""
        s = int(prompt.shape[0])
        if not self._pad_prefill:
            return {"tokens": prompt[None, :]}
        padded = PREFILL_BUCKET_MIN
        while padded < s:
            padded *= 2
        padded = min(padded, self.max_len) if self.max_len >= s else s
        if padded == s:
            return {"tokens": prompt[None, :],
                    "true_len": jnp.asarray(s, jnp.int32)}
        return {"tokens": jnp.pad(prompt, (0, padded - s))[None, :],
                "true_len": jnp.asarray(s, jnp.int32)}

    def _model_prefill(self, sr: ScheduledRequest) -> None:
        self.metrics.n_prefill_tokens += sr.prompt_len
        with span("prefill", "serving", "engine", rid=sr.rid,
                  prompt_len=sr.prompt_len, slot=sr.slot):
            with span("prefill.pad", "serving", "engine"):
                batch = self._prefill_batch(sr.req.prompt)
            with span("prefill.launch", "serving", "engine"):
                logits, cache1 = self.prefill(self.params, batch)
            with span("prefill.merge", "serving", "engine"):
                if self.attn_mode == "paged":
                    self.cache = self._merge_paged(self.cache, cache1, sr)
                else:
                    self.cache = _merge_slot(self.cache, cache1, sr.slot,
                                             self.max_len)
            # settle the merge here so its cost is attributed to prefill —
            # the async writes would otherwise be absorbed into the next
            # decode step's sync and pollute the measured decode step time
            with span("prefill.sync", "serving", "engine"):
                jax.block_until_ready(self.cache)
            with span("prefill.pick", "serving", "engine"):
                tok = self.model.greedy(logits[0])
                self.tokens = self.tokens.at[sr.slot].set(tok)
                tok = int(tok)
        # the span closes first: a preemption or finish below is a later
        # lifecycle event than this prefill
        if not self._grow(sr):          # prefill already yields one token
            return
        sr.out.append(tok)
        t = get_tracer()
        if (t is not None and
                self.metrics.requests[sr.rid].first_token_step is None):
            t.instant("first-token", "serving", track="engine", rid=sr.rid)
        self.metrics.on_first_token(sr.rid, self.step_count)
        self.metrics.on_token(sr.rid)
        if sr.remaining <= 0:
            self._finish(sr)

    def _decode_running(self) -> None:
        running = sorted(self.sched.running(), key=lambda s: s.slot)
        if not running:
            return
        t = get_tracer()
        with (NO_SPAN if t is None else t.span(
                "decode", "serving", "engine", rows=len(running),
                bucket=(self.runner.bucket_for(len(running))
                        if self.runner is not None else self.max_batch),
                slots=[sr.slot for sr in running])):
            t0 = time.perf_counter()
            if self.runner is not None:
                slots = [sr.slot for sr in running]
                # greedy pick + token-buffer update happen inside the
                # compiled step, so this branch is pure executable replay;
                # nxt arrives as host ints (step_greedy blocks on the
                # transfer)
                nxt, self.tokens, self.cache = self.runner.step_greedy(
                    self.params, self.cache, self.tokens, slots)
                by_slot = {slot: i for i, slot in enumerate(slots)}
            else:
                logits, self.cache = self.decode(self.params, self.cache,
                                                 self.tokens)
                nxt = self.model.greedy(jax.block_until_ready(logits))
                self.tokens = nxt
                by_slot = None
            self.decode_time_s += time.perf_counter() - t0
            self.decode_steps += 1
            for sr in running:
                if sr.state is not RequestState.RUNNING:
                    continue        # preempted by an earlier grow this step
                if not self._grow(sr):
                    continue            # sr itself was the preemption victim
                tok = (nxt[by_slot[sr.slot]] if by_slot is not None
                       else nxt[sr.slot])
                sr.out.append(int(tok))
                self.metrics.on_token(sr.rid)
                if sr.remaining <= 0:
                    self._finish(sr)

    def _merge_paged(self, cache, cache1, sr: ScheduledRequest):
        """Install one request into the paged cache: position clock, exec
        page-table row, and the prefill KV cut into page_tokens chunks and
        scattered to the granted pool rows.  The padded prompt tail (ladder
        padding past ``true_len``) lands in granted pages where the per-row
        position mask hides it until decode overwrites it in place."""
        ept = self.kv.page_tokens
        row = self.kv.exec_table(sr.rid)
        n_rowp = len(row)
        ids = jnp.asarray(row, jnp.int32)
        table_row = jnp.zeros((self._pages_per_req,),
                              jnp.int32).at[:n_rowp].set(ids)
        new = dict(cache)
        new["pos"] = cache["pos"].at[sr.slot].set(cache1["pos"][0])
        new["block_tables"] = cache["block_tables"].at[sr.slot].set(table_row)
        want = n_rowp * ept

        def cut(x):                 # (G,1,S,kv,hd) -> (G,n_rowp,kv,ept,hd)
            x = x[:, 0]
            s = x.shape[1]
            if s < want:
                x = jnp.pad(x, ((0, 0), (0, want - s)) + ((0, 0),) *
                            (x.ndim - 2))
            elif s > want:          # ladder padding past the granted pages
                x = x[:, :want]
            x = x.reshape(x.shape[0], n_rowp, ept, *x.shape[2:])
            return x.transpose(0, 1, 3, 2, 4)

        pat = {}
        for i, entry in cache["pattern"].items():
            c1 = cache1["pattern"][i]
            pat[i] = {"k_pages": entry["k_pages"].at[:, ids].set(cut(c1["k"])),
                      "v_pages": entry["v_pages"].at[:, ids].set(cut(c1["v"]))}
        new["pattern"] = pat
        self._slot_pages[sr.slot] = n_rowp
        return new

    def _sync_table_row(self, sr: ScheduledRequest) -> None:
        """Mirror an exec-table growth into the device block-table row (a
        no-op in steady state: rows only change when a page is granted)."""
        row = self.kv.exec_table(sr.rid)
        if len(row) == self._slot_pages[sr.slot]:
            return
        assert len(row) <= self._pages_per_req and \
            max(row) < self._pool_pages, (row, self._pool_pages)
        arr = jnp.zeros((self._pages_per_req,),
                        jnp.int32).at[:len(row)].set(jnp.asarray(row, jnp.int32))
        self.cache["block_tables"] = \
            self.cache["block_tables"].at[sr.slot].set(arr)
        self._slot_pages[sr.slot] = len(row)

    def _grow(self, sr: ScheduledRequest) -> bool:
        """Account one generated token; preempt the youngest request until the
        growth page fits.  Returns False if ``sr`` itself was evicted."""
        while True:
            try:
                self.kv.append_token(sr.rid)
                if self.attn_mode == "paged":
                    self._sync_table_row(sr)
                return True
            except PagePoolExhausted:
                self.kv.request_replan()    # observed lengths outgrew the plan
                if self.sched.n_active <= 1:
                    # no other victim: grow the pool rather than thrash
                    self.kv.ensure_free(1)
                    continue
                victim = self.sched.preempt_victim()
                self.metrics.on_preempt(victim.rid,
                                        discarded_tokens=len(victim.out))
                t = get_tracer()
                if t is not None:
                    t.instant("preempt", "serving", track="scheduler",
                              rid=victim.rid, grower=sr.rid,
                              discarded=len(victim.out))
                if victim.rid == sr.rid:
                    return False

    def _finish(self, sr: ScheduledRequest) -> None:
        self.completed[sr.rid] = sr.out
        self.sched.finish(sr)
        self.metrics.on_finish(sr.rid, self.step_count)
        t = get_tracer()
        if t is not None:
            t.instant("finish", "serving", track="engine", rid=sr.rid,
                      n_tokens=len(sr.out), n_preempt=sr.n_preempt)

    # -- drive a whole trace ----------------------------------------------------------
    def run(self, requests: Sequence[GenRequest],
            max_steps: int = 100_000) -> dict:
        """Feed requests by ``arrival`` step and run until everything drains.
        Zero manual submit() calls: queue -> prefill -> decode -> completion."""
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        while pending or not self.sched.idle:
            while pending and pending[0].arrival <= self.step_count:
                self.enqueue(pending.pop(0))
            self.step()
            if self.step_count >= max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")
        return self.metrics.summary(self.kv.stats())


def _merge_slot(batched_cache, single_cache, slot: int, max_len: int):
    """Copy one request's prefill cache into slot ``slot`` of the batch cache.

    Pattern-group leaves are (G, B, ...) — batch axis 1; tail leaves are
    (B, ...) — batch axis 0; "pos" is the (B,) per-slot position vector, so
    only the admitted row's clock moves (the old scalar-clock ``jnp.maximum``
    merge skewed every other in-flight request's attention offsets)."""
    b_paths = jax.tree_util.tree_flatten_with_path(batched_cache)
    s_leaves = jax.tree_util.tree_flatten(single_cache)[0]
    treedef = jax.tree_util.tree_structure(batched_cache)
    out = []
    for (kp, b), s in zip(b_paths[0], s_leaves):
        path = tuple(str(getattr(k, "key", "")) for k in kp)
        if path[-1] == "pos":               # (B,) <- (1,): one row's clock
            out.append(b.at[slot].set(s[0]))
            continue
        axis = 1 if "pattern" in path else 0
        pads = [(0, 0)] * b.ndim
        for d in range(b.ndim):
            if d != axis and s.shape[d] < b.shape[d]:
                pads[d] = (0, b.shape[d] - s.shape[d])
        sp = jnp.pad(s, pads)
        idx = [slice(None)] * b.ndim
        idx[axis] = slice(slot, slot + 1)
        out.append(b.at[tuple(idx)].set(sp))
    return jax.tree_util.tree_unflatten(treedef, out)
