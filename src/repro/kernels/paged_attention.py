"""Paged decode attention — Pallas TPU kernel over the page-table indirection.

The serving pool stores KV as fixed-size pages (``serving.pages.PagedKVCache``
block tables); the contiguous flash kernel therefore implies a gather before
attention.  This kernel consumes the page table *directly*: the per-request
page-index row is a scalar-prefetch operand, so the k/v BlockSpec index_maps
read ``tables[b, i]`` and the pipeline fetches exactly the pages each request
owns — no gather, no contiguous copy (the flashinfer
``BatchDecodeWithPagedKVCacheWrapper`` idiom, in Pallas).

Grid (B, KV, n_pages_per_req): the page axis is innermost, so TPU sequential
grid execution carries the online-softmax (m, l, acc) VMEM scratch across a
request's pages.  Masking is per row: the runner's per-slot position vector
bounds validity (``k_pos <= pos[b]``), which also makes partial last pages
and the zero-padded tail of short page-table rows exact — padded entries
point at page 0, whose keys fall outside every row's valid range.

Layout: q (B, KV, G, hd); k/v pools (P, KV, page_tokens, hd);
tables (B, n_pages_per_req) int32; positions (B,) int32 -> out (B, KV, G, hd).
The pool keeps page_tokens and hd as its two minor axes so that one (head,
page) block is a (page_tokens, hd) tile: the TPU lowering requires a block's
last two dims to be multiples of (8, 128) or the full array dims, which a
KV axis cut to 1 in the second-minor position breaks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# f32 contractions at full precision: Mosaic's default may round f32 operands
# to bf16, which would put the probabilities through a bf16 rounding that
# attention.attend_decode (the gather path) does not make
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, page_tokens, n_pages):
    b = pl.program_id(0)
    i = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pages wholly past this row's position contribute nothing (their keys
    # are all masked) — skip the math, not just the result
    @pl.when(i * page_tokens <= pos)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (pt, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32)  # (G, pt)
        g = s.shape[0]
        k_pos = i * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (g, page_tokens), 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        v = v_ref[0, 0].astype(jnp.float32)                # (pt, hd)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)

    @pl.when(i == n_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def paged_attention_decode(q, k_pages, v_pages, tables, positions, *,
                           interpret=False):
    """q: (B, KV, G, hd); k/v pools: (P, KV, pt, hd);
    tables: (B, maxp) int32 page ids (pad unused entries with any in-bounds
    id — masking keeps them inert); positions: (B,) int32, row b attends to
    token indices <= positions[b].  Returns (B, KV, G, hd)."""
    b, kv, g, hd = q.shape
    p, kv_k, pt, hd_k = k_pages.shape
    assert (kv_k, hd_k) == (kv, hd), (k_pages.shape, q.shape)
    assert v_pages.shape == k_pages.shape
    maxp = tables.shape[1]
    assert tables.shape == (b, maxp) and positions.shape == (b,)
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_kernel, scale=scale, page_tokens=pt,
                               n_pages=maxp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bi, hi, i, tbl, pos: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, pt, hd),
                         lambda bi, hi, i, tbl, pos: (tbl[bi, i], hi, 0, 0)),
            pl.BlockSpec((1, 1, pt, hd),
                         lambda bi, hi, i, tbl, pos: (tbl[bi, i], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, hi, i, tbl, pos: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), positions.astype(jnp.int32), q, k_pages,
      v_pages)


def vmem_blocks(group: int, page_tokens: int, hd: int, dtype=jnp.bfloat16):
    """Working-set descriptors for MemoryPlanner.check_vmem (paper planner)."""
    return [((group, hd), dtype),                         # q tile
            ((page_tokens, hd), dtype),                   # k page
            ((page_tokens, hd), dtype),                   # v page
            ((group, hd), jnp.dtype("float32")),          # acc scratch
            ((group,), jnp.dtype("float32")),
            ((group,), jnp.dtype("float32")),
            ((group, hd), dtype)]                         # out tile
