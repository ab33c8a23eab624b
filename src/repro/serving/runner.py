"""Bucketed pre-compiled decode steps over the planner-addressed cache.

The engine's steady-state decode cost must not depend on Python retracing:
following the CUDA-graph capture idiom (one captured graph per batch-size
bucket, replayed into fixed per-B input buffers), :class:`DecodeRunner`
AOT-compiles one decode step per bucket B in {1, 2, 4, ..., max_batch} with
``jax.jit(...).lower(...).compile()``.  Calls to a compiled executable can
never retrace, which turns the steady-state zero-retrace expectation into a
*structural* invariant — surfaced through the ``runner_compile_total``
metrics counter (incremented by a trace-time hook, so it moves only when a
bucket is actually (re)compiled) and tracer ``compile`` events.

Each step gathers the running slots' rows out of the full planner-addressed
batch cache, runs the bucket's compiled step, and scatters the updated rows
back — the gather/scatter is the flashinfer-style paged indirection, executed
inside the compiled step so the cache stays donated end to end.  A partial
batch is padded to its bucket by repeating the last running slot: duplicated
rows compute identical updates from identical inputs, so the duplicate
scatter writes are value-identical and harmless.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..models.transformer import Transformer
from ..obs.metrics import MetricsRegistry, get_registry
from ..obs.trace import get_tracer, span
from ..runtime import mesh_ctx


def bucket_ladder(max_batch: int) -> tuple[int, ...]:
    """Powers of two up to ``max_batch``, plus ``max_batch`` itself."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


def _batch_axis(path: tuple):
    """Pattern-group cache leaves are (G, B, ...); everything else (B, ...).
    Paged pool leaves (``*_pages``) carry no batch axis at all — they are
    shared by every slot and pass through the gather/scatter wholesale, which
    is precisely how the paged path drops the in-executable KV copy: only the
    (B,)-small pos/block-table/token rows are ever gathered."""
    if path and path[-1].endswith("_pages"):
        return None
    return 1 if "pattern" in path else 0


def _gather_rows(cache, slots):
    """Sub-cache of the rows named by ``slots`` (bucket-sized batch)."""
    def take(kp, leaf):
        path = tuple(str(getattr(k, "key", "")) for k in kp)
        axis = _batch_axis(path)
        return leaf if axis is None else jnp.take(leaf, slots, axis=axis)
    return jax.tree_util.tree_map_with_path(take, cache)


def _scatter_rows(cache, sub, slots):
    """Write the updated sub-cache rows back into the full batch cache."""
    flat_sub = jax.tree_util.tree_leaves(sub)
    out = []
    for ((kp, full), s) in zip(
            jax.tree_util.tree_flatten_with_path(cache)[0], flat_sub):
        path = tuple(str(getattr(k, "key", "")) for k in kp)
        axis = _batch_axis(path)
        if axis is None:                # shared pool: sub IS the full leaf
            out.append(s)
        elif axis == 1:
            out.append(full.at[:, slots].set(s))
        else:
            out.append(full.at[slots].set(s))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(cache), out)


class DecodeRunner:
    """Ladder of pre-compiled decode steps over batch-size buckets.

    ``step(params, cache, tokens, slots)`` selects the smallest bucket that
    fits ``len(slots)``, pads by repeating the last slot, and replays the
    bucket's compiled executable against the full donated cache.  With
    ``warmup()`` called once, the hot loop is pure executable dispatch:
    ``n_compiles`` (and the ``runner_compile_total`` registry counter) stay
    flat no matter how admissions, finishes and preemptions churn the batch.
    """

    def __init__(self, model: Transformer, *, max_batch: int,
                 mesh: Optional[Mesh] = None,
                 buckets: Optional[Sequence[int]] = None,
                 donate: Optional[bool] = None,
                 registry: Optional[MetricsRegistry] = None):
        """``donate`` defaults to True off-CPU (the CPU backend cannot alias
        donated buffers and warns); ``registry`` defaults to the active
        observability registry at count time."""
        self.model = model
        self.mesh = mesh
        self.max_batch = max_batch
        self.buckets = tuple(sorted(set(buckets))) if buckets else \
            bucket_ladder(max_batch)
        if self.buckets[-1] < max_batch:
            raise ValueError(f"largest bucket {self.buckets[-1]} < "
                             f"max_batch {max_batch}")
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self.donate = donate
        self._registry = registry
        self.n_compiles = 0
        self._compiled: dict[int, jax.stages.Compiled] = {}
        self._jit = jax.jit(self._step_fn,
                            donate_argnums=(1,) if donate else ())

    # -- the traced step ----------------------------------------------------------
    def _step_fn(self, params, cache, tokens, slots):
        self._note_compile(int(slots.shape[0]))      # trace-time only
        ctx = (mesh_ctx.use_mesh(self.mesh, rules=self.model.opts.mesh_rules())
               if self.mesh is not None else None)
        with jax.named_scope("gather"):
            sub = _gather_rows(cache, slots)
            sub_tokens = jnp.take(tokens, slots)
        if ctx is not None:
            with ctx:
                logits, new_sub = self.model.decode_step(params, sub, sub_tokens)
        else:
            logits, new_sub = self.model.decode_step(params, sub, sub_tokens)
        # greedy selection and the token-buffer update live inside the
        # executable: the engine's hot loop then never runs eager per-shape
        # ops (an eager argmax/scatter would quietly compile once per batch
        # size, off the runner's compile counter)
        nxt = self.model.greedy(logits)
        with jax.named_scope("scatter"):
            new_tokens = tokens.at[slots].set(nxt)
            new_cache = _scatter_rows(cache, new_sub, slots)
        return logits, nxt, new_tokens, new_cache

    def _note_compile(self, bucket: int) -> None:
        """Runs while tracing (never on executable replay): count a compile."""
        self.n_compiles += 1
        reg = self._registry if self._registry is not None else get_registry()
        if reg is not None:
            reg.counter("runner_compile_total",
                        "decode-runner bucket (re)compilations").inc()
        t = get_tracer()
        if t is not None:
            t.instant("compile", "serving", track="runner", bucket=bucket,
                      total=self.n_compiles)

    # -- bucket management --------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket that fits ``n`` running requests."""
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"{n} running requests exceed every bucket "
                         f"{self.buckets}")

    def _ensure_compiled(self, bucket: int, params, cache, tokens):
        c = self._compiled.get(bucket)
        if c is None:
            sds = lambda t: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
            lowered = self._jit.lower(
                sds(params), sds(cache), sds(tokens),
                jax.ShapeDtypeStruct((bucket,), jnp.int32))
            c = self._compiled[bucket] = lowered.compile()
        return c

    def executable(self, bucket: int) -> jax.stages.Compiled:
        """The compiled step of an already compiled ``bucket``."""
        return self._compiled[bucket]

    def warmup(self, params, cache, tokens) -> int:
        """Compile every bucket up front *and* replay each one end to end
        through the real hot path against a throwaway zeroed cache
        (donation-safe: the dummy is what gets donated).  Routing through
        ``step_greedy`` matters: first-call costs per bucket (executable
        load, the slot-vector device put, the host readback) are paid here,
        so the serving loop is steady-state from step 0.  Returns the
        compile count, after which decode performs zero retraces by
        construction."""
        for b in self.buckets:
            self._ensure_compiled(b, params, cache, tokens)
            dummy = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), cache)
            self.step_greedy(params, dummy,
                             jnp.zeros(tokens.shape, tokens.dtype), [0] * b)
        return self.n_compiles

    # -- the hot path -------------------------------------------------------------
    def step(self, params, cache, tokens, slots: Sequence[int]):
        """One decode step for the rows in ``slots`` (any count <= max_batch).

        Returns ``(logits, new_cache)`` with ``logits[i]`` the next-token
        logits for ``slots[i]``; rows outside ``slots`` are untouched (the
        pad rows' duplicate writes replay the last slot's own update).
        """
        n = len(slots)
        if n == 0:
            return jnp.zeros((0, self.model.cfg.padded_vocab)), cache
        logits, _, _, new_cache = self._replay(params, cache, tokens, slots)
        return logits[:n], new_cache

    def step_greedy(self, params, cache, tokens, slots: Sequence[int]):
        """Engine hot path: one decode step plus in-executable greedy pick.

        Returns ``(next_tokens, new_tokens, new_cache)`` where
        ``next_tokens[i]`` is the argmax token for ``slots[i]`` (a host
        numpy array — one blocking (bucket,)-int transfer instead of an
        eager device slice that would quietly compile per (bucket, n) shape
        pair, plus per-row ``int()`` syncs downstream) and ``new_tokens``
        is the full (max_batch,) token buffer with those rows updated.
        """
        n = len(slots)
        if n == 0:
            return np.zeros(0, np.int32), tokens, cache
        _, nxt, new_tokens, new_cache = self._replay(params, cache, tokens,
                                                     slots)
        with span("runner.readback", "serving", "runner"):
            nxt = np.asarray(nxt)
        return nxt[:n], new_tokens, new_cache

    def _replay(self, params, cache, tokens, slots):
        bucket = self.bucket_for(len(slots))
        compiled = self._ensure_compiled(bucket, params, cache, tokens)
        padded = list(slots) + [slots[-1]] * (bucket - len(slots))
        with span("runner.put", "serving", "runner"):
            padded = jnp.asarray(padded, jnp.int32)
        with span("runner.launch", "serving", "runner"):
            return compiled(params, cache, tokens, padded)

    def stats(self) -> dict:
        return {"buckets": list(self.buckets),
                "n_compiled": len(self._compiled),
                "n_compiles": self.n_compiles,
                "donate": self.donate}
