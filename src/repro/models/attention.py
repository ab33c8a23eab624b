"""Attention blocks: global/local (windowed) GQA with RoPE, three impls.

Implementations (selected via ``impl``):
  * "full"    — materialized scores einsum; fine to ~8k tokens under remat.
  * "chunked" — lax.scan over KV chunks with an online softmax (the XLA
                flash-equivalent used for 32k prefill; maps 1:1 onto the
                Pallas kernel in repro.kernels.flash_attention).
  * "pallas"  — TPU Pallas kernel (repro.kernels.ops.flash_attention).

Decode-time attention has two cache layouts: ``attend_decode`` over the
contiguous per-slot batch cache, and ``attend_paged_decode`` straight off the
paged pool (per-request page tables consumed inside the Pallas kernel).

GQA is computed with separate (kv_heads, group) axes — no materialized
repeat_kv — so the kv_heads axis can be model-sharded.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..runtime import mesh_ctx
from .layers import apply_rope, cdt, rope_angles

NEG_INF = -1e30


def _split_heads(x, n_kv: int, group: int, head_dim: int):
    b, s, _ = x.shape
    return x.reshape(b, s, n_kv, group, head_dim)


def qkv_project(x, p, cfg, compute_dtype):
    """x: (B,S,D) -> q (B,S,kv,g,hd), k/v (B,S,kv,hd)."""
    hd = cfg.resolved_head_dim
    n_kv = cfg.n_kv_heads
    g = cfg.n_heads // n_kv
    xc = cdt(x, compute_dtype)
    q = jnp.einsum("bsd,dnh->bsnh", xc, cdt(p["wq"], compute_dtype))
    k = jnp.einsum("bsd,dnh->bsnh", xc, cdt(p["wk"], compute_dtype))
    v = jnp.einsum("bsd,dnh->bsnh", xc, cdt(p["wv"], compute_dtype))
    if cfg.qkv_bias:
        q = q + cdt(p["bq"], compute_dtype)
        k = k + cdt(p["bk"], compute_dtype)
        v = v + cdt(p["bv"], compute_dtype)
    q = q.reshape(*q.shape[:2], n_kv, g, hd)
    q = mesh_ctx.shard(q, "batch", "seq", "kv_heads", None, "head_dim")
    k = mesh_ctx.shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = mesh_ctx.shard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def out_project(ctx, p, cfg, compute_dtype):
    b, s = ctx.shape[:2]
    ctx = ctx.reshape(b, s, cfg.n_heads, cfg.resolved_head_dim)
    return jnp.einsum("bsnh,nhd->bsd", ctx, cdt(p["wo"], compute_dtype))


# ---------------------------------------------------------------------------
# score-level masking
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, causal: bool, window: int, dtype):
    """(len_q, len_k) additive bias from positions."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(dtype)


# ---------------------------------------------------------------------------
# impls
# ---------------------------------------------------------------------------


def attend_full(q, k, v, *, causal=True, window=0, q_offset=0,
                softmax_dtype=jnp.float32):
    """q: (B,Sq,kv,g,hd); k/v: (B,Sk,kv,hd).

    ``softmax_dtype=bfloat16`` keeps the S^2 score tensor in bf16 end-to-end
    (row stats still accumulate in f32) — the storage policy the Pallas flash
    kernel uses in VMEM, applied at the XLA level: halves attention HBM
    traffic at the cost of ~1e-2 logit error (validated in tests).
    """
    hd = q.shape[-1]
    scale = hd ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k) * scale
    q_pos = q_offset + jnp.arange(q.shape[1])
    k_pos = jnp.arange(k.shape[1])
    if softmax_dtype == jnp.float32:
        bias = _mask_bias(q_pos, k_pos, causal, window, jnp.float32)
        probs = jax.nn.softmax(scores.astype(jnp.float32) + bias,
                               axis=-1).astype(q.dtype)
    else:
        bias = _mask_bias(q_pos, k_pos, causal, window, scores.dtype)
        s = scores + bias
        m = jax.lax.stop_gradient(s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m)                                   # bf16 storage
        l = jnp.sum(p, axis=-1, keepdims=True, dtype=jnp.float32)
        probs = (p / l.astype(p.dtype)).astype(q.dtype)
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return ctx


def attend_chunked(q, k, v, *, causal=True, window=0, q_offset=0, chunk=1024):
    """Online-softmax scan over KV chunks — O(Sq*chunk) live memory."""
    b, sq, n_kv, g, hd = q.shape
    sk = k.shape[1]
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    kc = k.reshape(b, n_chunks, chunk, n_kv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, n_kv, hd).transpose(1, 0, 2, 3, 4)
    scale = hd ** -0.5
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        m, l, acc = carry
        idx, kb, vb = xs
        k_pos = idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgh,bskh->bkgqs", q, kb).astype(jnp.float32) * scale
        ok = k_pos[None, :] < sk
        if causal:
            ok &= k_pos[None, :] <= q_pos[:, None]
        if window:
            ok &= k_pos[None, :] > (q_pos[:, None] - window)
        s = s + jnp.where(ok, 0.0, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p.astype(q.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, n_kv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, n_kv, g, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc))
    ctx = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return ctx.transpose(0, 3, 1, 2, 4)           # (B,Sq,kv,g,hd)


def attend(q, k, v, *, impl="full", causal=True, window=0, q_offset=0,
           chunk=1024, softmax_dtype=jnp.float32):
    if impl == "chunked":
        return attend_chunked(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, chunk=chunk)
    if impl == "pallas":
        from ..kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset)
    return attend_full(q, k, v, causal=causal, window=window,
                       q_offset=q_offset, softmax_dtype=softmax_dtype)


# ---------------------------------------------------------------------------
# decode-time attention against a cache
# ---------------------------------------------------------------------------


def attend_decode(q, k_cache, v_cache, cache_pos, *, window=0, rolling=False):
    """q: (B,1,kv,g,hd); caches: (B,C,kv,hd); positions < cache_pos are valid.

    ``cache_pos`` is a scalar (one shared clock) or a (B,) vector of per-slot
    positions — staggered admissions give every batch row its own clock, so
    the validity mask is computed per row.

    ``rolling=True`` means the cache is a circular window buffer (local
    attention at long context); validity is then positional-age based and
    already guaranteed by construction, so only the fill mask applies.

    Scores, probabilities and the context stay in float32 until the context
    is cast back to ``q.dtype`` — the numerics of the paged decode kernel, so
    the gather and paged paths differ only in f32 rounding, in bf16 too.
    """
    hd = q.shape[-1]
    scale = hd ** -0.5
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k_cache,
                   preferred_element_type=jnp.float32) * scale
    c = k_cache.shape[1]
    idx = jnp.arange(c)
    pos = jnp.asarray(cache_pos).reshape(-1, 1)         # (B,1) or (1,1)
    if rolling:
        valid = idx[None, :] < jnp.minimum(pos + 1, c)
    else:
        valid = idx[None, :] <= pos
        if window:
            valid &= idx[None, :] > (pos - window)
    s = s + jnp.where(valid[:, None, None, None, :], 0.0, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgqs,bskh->bqkgh", p, v_cache.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return ctx.astype(q.dtype)


def attend_paged_decode(q, k_pages, v_pages, tables, cache_pos, *,
                        impl="pallas"):
    """Decode attention straight off the paged pool — no gather, no copy.

    q: (B,1,kv,g,hd); k/v pools: (P,kv,pt,hd) shared by the whole batch;
    tables: (B,maxp) int32 page-index rows (token t of row b, head h lives
    at (tables[b, t//pt], h, t%pt)); cache_pos: (B,) per-slot positions — row b
    attends to token indices <= cache_pos[b].

    ``impl="pallas"`` runs the Pallas kernel (the page table drives the
    BlockSpec index_maps via scalar prefetch); ``impl="ref"`` runs the
    pure-jnp gather oracle — the differential baseline the kernel is gated
    against."""
    qh = q[:, 0]                                        # (B,kv,g,hd)
    pos = jnp.asarray(cache_pos, jnp.int32).reshape(-1)
    if impl == "ref":
        from ..kernels.ref import ref_paged_attention
        ctx = ref_paged_attention(qh, k_pages, v_pages, tables, pos)
    else:
        from ..kernels import ops as kops
        ctx = kops.paged_attention(qh, k_pages, v_pages, tables, pos)
    return ctx[:, None]                                 # (B,1,kv,g,hd)
