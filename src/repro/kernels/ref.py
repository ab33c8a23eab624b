"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ref_attention_bhsd(q, k, v, *, causal=True, window=0, q_offset=0):
    """q: (B,H,Sq,D); k/v: (B,KV,Sk,D).  Materialized-softmax reference."""
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    rep = h // kv
    kf = jnp.repeat(k, rep, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, rep, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) / jnp.sqrt(d)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(sk)
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(ok[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)


def ref_paged_attention(q, k_pages, v_pages, tables, positions):
    """Gather-then-softmax oracle for the paged decode kernel.

    q: (B,KV,G,hd); k/v pools: (P,KV,pt,hd); tables: (B,maxp) int32;
    positions: (B,) — row b attends to token indices <= positions[b].
    Token t of row b, head h lives at (tables[b, t // pt], h, t % pt)."""
    b, kv, g, hd = q.shape
    pt = k_pages.shape[2]
    maxp = tables.shape[1]

    def gather(pages):              # (B,maxp,KV,pt,hd) -> (B,KV,maxp*pt,hd)
        return pages[tables].transpose(0, 2, 1, 3, 4).reshape(
            b, kv, maxp * pt, hd).astype(jnp.float32)

    k, v = gather(k_pages), gather(v_pages)
    s = jnp.einsum("bkgh,bksh->bkgs", q.astype(jnp.float32), k) / jnp.sqrt(hd)
    idx = jnp.arange(maxp * pt)
    valid = idx[None, :] <= positions[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgs,bksh->bkgh", p, v).astype(q.dtype)


def ref_ssd(x, dta, b_mat, c_mat, h0=None):
    """Sequential SSD recurrence.  x: (B,S,H,P) dt-scaled; dta: (B,S,H)
    log-decays; b/c: (B,S,G,N).  Returns (y (B,S,H,P) f32, h (B,H,P,N) f32)."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    bh = jnp.repeat(b_mat.astype(jnp.float32), rep, axis=2)
    ch = jnp.repeat(c_mat.astype(jnp.float32), rep, axis=2)

    def step(hst, t):
        xt, dtat, bt, ct = t
        a = jnp.exp(dtat)[:, :, None, None]                  # (B,H,1,1)
        hst = a * hst + jnp.einsum("bhn,bhp->bhpn", bt, xt)
        y = jnp.einsum("bhn,bhpn->bhp", ct, hst)
        return hst, y

    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    xs = (x.astype(jnp.float32).transpose(1, 0, 2, 3),
          dta.astype(jnp.float32).transpose(1, 0, 2),
          bh.transpose(1, 0, 2, 3), ch.transpose(1, 0, 2, 3))
    h_fin, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), h_fin


def ref_rglru(a, b, h0=None):
    """Sequential linear recurrence h_t = a_t h_{t-1} + b_t.  (B,S,L) f32."""
    bsz, s, l = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, l), jnp.float32)

    def step(h, t):
        at, bt = t
        h = at * h + bt
        return h, h

    _, ys = jax.lax.scan(step, h0.astype(jnp.float32),
                         (a.astype(jnp.float32).transpose(1, 0, 2),
                          b.astype(jnp.float32).transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2)
