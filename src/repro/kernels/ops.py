"""Jit'd wrappers exposing the Pallas kernels in model-native layouts.

The kernels compile for the TPU.  There is no automatic fallback: on a host
without a chip a kernel call fails unless interpret mode is asked for, either
with ``interpret=True`` or with ``REPRO_PALLAS_INTERPRET=1`` in the
environment.  ``tests/conftest.py`` sets that variable (with
``JAX_PLATFORMS=cpu``) so the differential suites run on the CPU;
``chip_smoke.py`` refuses to run while it is set.  Block shapes are validated
against the VMEM budget with the paper's planner before launch.
"""
from __future__ import annotations

import os
from functools import partial

import jax.numpy as jnp

from ..core.planner import MemoryPlanner
from . import flash_attention as _fa
from . import paged_attention as _pa
from . import rglru_scan as _rg
from . import ssd_scan as _ssd


def interpret_requested() -> bool:
    """True iff ``REPRO_PALLAS_INTERPRET`` asks for interpret mode."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    return env.lower() not in ("", "0", "false", "no")


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_k=128, interpret=None):
    """Model layout q: (B,S,KV,G,hd); k/v: (B,S,KV,hd) -> ctx (B,S,KV,G,hd)."""
    interpret = interpret_requested() if interpret is None else interpret
    b, s, kv, g, hd = q.shape
    check = MemoryPlanner.check_vmem(_fa.vmem_blocks(block_q, block_k, hd,
                                                     q.dtype))
    assert check["fits"], f"flash blocks exceed VMEM: {check}"
    qh = q.reshape(b, s, kv * g, hd).transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention_bhsd(qh, kh, vh, causal=causal, window=window,
                                   q_offset=q_offset, block_q=block_q,
                                   block_k=block_k, interpret=interpret)
    return out.transpose(0, 2, 1, 3).reshape(b, s, kv, g, hd)


def paged_attention(q, k_pages, v_pages, tables, positions, *, interpret=None):
    """Decode layout q: (B,KV,G,hd); pools (P,KV,pt,hd); tables (B,maxp);
    positions (B,) -> ctx (B,KV,G,hd).  The page table is consumed inside the
    kernel (scalar-prefetch index_maps) — no gather, no contiguous copy."""
    interpret = interpret_requested() if interpret is None else interpret
    _, kv, g, hd = q.shape
    pt = k_pages.shape[2]
    check = MemoryPlanner.check_vmem(_pa.vmem_blocks(g, pt, hd, q.dtype))
    assert check["fits"], f"paged blocks exceed VMEM: {check}"
    return _pa.paged_attention_decode(q, k_pages, v_pages, tables, positions,
                                      interpret=interpret)


def ssd_scan(x, dt, a_log, b_mat, c_mat, d_skip, *, chunk=128,
             interpret=None):
    """Mirror of models.ssm.ssd_chunked: x (B,S,H,P), dt (B,S,H) softplus'd,
    a_log (H,), b/c (B,S,G,N), d_skip (H,).  Returns (y f32, h_fin f32)."""
    interpret = interpret_requested() if interpret is None else interpret
    a = -jnp.exp(a_log.astype(jnp.float32))
    dta = dt.astype(jnp.float32) * a
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    y, h_fin = _ssd.ssd_scan_kernel(xdt, dta, b_mat, c_mat, chunk=chunk,
                                    interpret=interpret)
    y = y + x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, None, :, None]
    return y, h_fin


def rglru_scan(a, b, h0=None, *, block=256, interpret=None):
    """Linear recurrence y_t = a_t y_{t-1} + b_t over axis 1.  (B,S,L) f32."""
    interpret = interpret_requested() if interpret is None else interpret
    return _rg.rglru_scan_kernel(a, b, h0, block=block, interpret=interpret)
