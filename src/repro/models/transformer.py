"""Composable decoder-only / encoder-decoder transformer over block patterns.

One model class covers all 10 assigned architectures: the config's
``block_pattern`` (e.g. ``("attn",)``, ``("rec","rec","local")``,
``("mamba2",)``, ``("xattn",)``) selects per-layer kinds; layers are stacked
per pattern position and executed with ``lax.scan`` over groups so the HLO
stays compact for the 512-device dry-run.

Three entry points per model:
  * ``loss_fn(params, batch)``        — training forward (+ CE loss)
  * ``prefill(params, batch)``        — inference forward, builds the cache
  * ``decode_step(params, cache, t)`` — one-token serve step
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..runtime import mesh_ctx
from . import attention as attn
from . import moe as moe_lib
from . import rglru as rglru_lib
from . import ssm as ssm_lib
from .layers import apply_norm, cdt, embed_lookup, rope_angles
from .schema import P, Schema, stack


@dataclass(frozen=True)
class RunOpts:
    """Runtime knobs independent of the architecture spec."""
    attention_impl: str = "auto"      # auto | full | chunked | pallas
    attn_chunk: int = 1024
    loss_impl: str = "full"           # full | chunked
    loss_chunk: int = 512
    use_kernels: bool = False         # Pallas paths for ssd / rglru
    ssd_chunk: int = 256
    paged_attn_impl: str = "pallas"   # pallas | ref (paged decode cache)
    # ---- §Perf hillclimb knobs (beyond-paper optimizations) ---------------
    softmax_dtype: str = "float32"    # float32 | bfloat16 (score storage)
    cp_attention: bool = False        # context-parallel attention over model
    moe_grouped: bool = False         # hierarchical MoE dispatch per data shard
    sp_residual: bool = False         # Megatron-SP: residual stream seq->model
    ssd_shard_p: bool = False         # shard SSD head_dim P over model (H may not divide)

    def mesh_rules(self) -> Optional[dict]:
        rules = {}
        if self.sp_residual:
            rules["seq"] = ("model",)
        if self.ssd_shard_p:
            rules["ssm_p"] = ("model",)
        return rules or None


# ===========================================================================
# schema
# ===========================================================================


def _norm_schema(cfg) -> Schema:
    s: Schema = {"scale": P((cfg.d_model,), (None,),
                            init="zeros" if cfg.norm == "rmsnorm" else "ones")}
    if cfg.norm == "layernorm":
        s["bias"] = P((cfg.d_model,), (None,), init="zeros")
    return s


def _attn_schema(cfg) -> Schema:
    hd = cfg.resolved_head_dim
    s: Schema = {
        "norm": _norm_schema(cfg),
        "wq": P((cfg.d_model, cfg.n_heads, hd), ("embed", "heads", "head_dim")),
        "wk": P((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wv": P((cfg.d_model, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wo": P((cfg.n_heads, hd, cfg.d_model), ("heads", "head_dim", "embed"),
                scale=1.0 / math.sqrt(cfg.n_heads * hd)),
    }
    if cfg.qkv_bias:
        s["bq"] = P((cfg.n_heads, hd), ("heads", "head_dim"), init="zeros")
        s["bk"] = P((cfg.n_kv_heads, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bv"] = P((cfg.n_kv_heads, hd), ("kv_heads", "head_dim"), init="zeros")
    return s


def _mlp_schema(cfg) -> Schema:
    if cfg.n_experts:
        return {
            "w_router": P((cfg.d_model, cfg.n_experts), ("embed", "experts")),
            "w_gate": P((cfg.n_experts, cfg.d_model, cfg.d_ff),
                        ("experts", "embed", "expert_mlp")),
            "w_up": P((cfg.n_experts, cfg.d_model, cfg.d_ff),
                      ("experts", "embed", "expert_mlp")),
            "w_down": P((cfg.n_experts, cfg.d_ff, cfg.d_model),
                        ("experts", "expert_mlp", "embed")),
        }
    s: Schema = {"w_up": P((cfg.d_model, cfg.d_ff), ("embed", "mlp")),
                 "w_down": P((cfg.d_ff, cfg.d_model), ("mlp", "embed"))}
    if cfg.act in ("swiglu", "geglu"):
        s["w_gate"] = P((cfg.d_model, cfg.d_ff), ("embed", "mlp"))
    elif cfg.qkv_bias:  # starcoder2/whisper-style biases on the plain MLP
        s["b_up"] = P((cfg.d_ff,), ("mlp",), init="zeros")
        s["b_down"] = P((cfg.d_model,), (None,), init="zeros")
    return s


def _rec_schema(cfg) -> Schema:
    """Griffin recurrent residual block: RG-LRU mixer + its own MLP."""
    L = cfg.lru_width
    nb = cfg.n_heads                     # block-diagonal gates, one per head
    bs = L // nb
    return {
        "mlp_norm": _norm_schema(cfg),
        "mlp": _mlp_schema(cfg),
        "norm": _norm_schema(cfg),
        "w_branch": P((cfg.d_model, L), ("embed", "lru")),
        "w_gate": P((cfg.d_model, L), ("embed", "lru")),
        "w_conv": P((cfg.conv_width, L), (None, "lru"), scale=0.1),
        "b_conv": P((L,), ("lru",), init="zeros"),
        "w_out": P((L, cfg.d_model), ("lru", "embed")),
        "lru": {
            "w_a": P((nb, bs, bs), ("heads", None, None)),
            "b_a": P((nb, bs), ("heads", None), init="zeros"),
            "w_x": P((nb, bs, bs), ("heads", None, None)),
            "b_x": P((nb, bs), ("heads", None), init="zeros"),
            "lam": P((L,), ("lru",), init="ones", scale=1.0),
        },
    }


def _mamba2_schema(cfg) -> Schema:
    d_in = cfg.d_inner
    g, n, h = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    conv_dim = d_in + 2 * g * n
    proj = 2 * d_in + 2 * g * n + h
    return {
        "norm": _norm_schema(cfg),
        "w_in": P((cfg.d_model, proj), ("embed", None)),
        "w_conv": P((cfg.conv_width, conv_dim), (None, None), scale=0.1),
        "b_conv": P((conv_dim,), (None,), init="zeros"),
        "dt_bias": P((h,), (None,), init="zeros"),
        "a_log": P((h,), (None,), init="ones", scale=1.0),
        "d_skip": P((h,), (None,), init="ones"),
        "norm_scale": P((d_in,), (None,), init="zeros"),
        "w_out": P((d_in, cfg.d_model), (None, "embed")),
    }


def _block_schema(kind: str, cfg) -> Schema:
    if kind in ("attn", "local"):
        return {"attn": _attn_schema(cfg), "mlp_norm": _norm_schema(cfg),
                "mlp": _mlp_schema(cfg)}
    if kind == "xattn":
        return {"attn": _attn_schema(cfg), "xnorm": _norm_schema(cfg),
                "xattn": _attn_schema(cfg), "mlp_norm": _norm_schema(cfg),
                "mlp": _mlp_schema(cfg)}
    if kind == "rec":
        return _rec_schema(cfg)
    if kind == "mamba2":
        return _mamba2_schema(cfg)
    raise ValueError(f"unknown block kind {kind!r}")


# ===========================================================================
# model
# ===========================================================================


class Transformer:
    def __init__(self, cfg: ModelConfig, opts: RunOpts = RunOpts()):
        self.cfg = cfg
        self.opts = opts
        self.compute_dtype = jnp.dtype(cfg.dtype)

    # ---- schema / params ------------------------------------------------------
    def schema(self) -> Schema:
        cfg = self.cfg
        s: Schema = {
            "embed": P((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
            "final_norm": _norm_schema(cfg),
        }
        if not cfg.tie_embeddings:
            s["lm_head"] = P((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                             scale=0.02)
        if cfg.block_pattern:
            s["pattern"] = {
                str(i): stack(cfg.n_pattern_groups, _block_schema(kind, cfg), "layers")
                for i, kind in enumerate(cfg.block_pattern)}
        if cfg.tail_pattern:
            s["tail"] = {str(i): _block_schema(kind, cfg)
                         for i, kind in enumerate(cfg.tail_pattern)}
        if cfg.is_encoder_decoder:
            s["encoder"] = {
                "blocks": stack(cfg.encoder_layers, _block_schema("attn", cfg),
                                "layers"),
                "final_norm": _norm_schema(cfg),
            }
        return s

    def init(self, key) -> Any:
        from .schema import init_params
        return init_params(self.schema(), key, dtype="float32")

    def abstract(self) -> Any:
        from .schema import abstract_params
        return abstract_params(self.schema(), dtype="float32")

    # ---- shared pieces -----------------------------------------------------------
    @jax.named_scope("embed")
    def _embed_in(self, params, tokens):
        cfg = self.cfg
        x = embed_lookup(params["embed"], tokens, self.compute_dtype)
        if cfg.family == "hybrid":                  # gemma-style embed scaling
            x = x * jnp.asarray(math.sqrt(cfg.d_model), self.compute_dtype)
        return mesh_ctx.shard(x, "batch", "seq", "embed")

    def _rope(self, positions):
        cfg = self.cfg
        if not cfg.rope:
            return None
        return rope_angles(positions, cfg.resolved_head_dim, cfg.rope_theta)

    def _sinusoid(self, positions):
        d = self.cfg.d_model
        half = d // 2
        freqs = np.exp(-math.log(10_000.0) * np.arange(half) / half)
        ang = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(self.compute_dtype)

    def _attn_impl(self, seq_len: int, training: bool) -> str:
        o = self.opts.attention_impl
        if o != "auto":
            return o
        return "full" if seq_len <= 8192 else "chunked"

    # ---- full-sequence block application (train / prefill) ------------------------
    def _apply_block(self, kind, x, p, rope_cs, *, training, enc_out=None,
                     want_cache=False):
        cfg, opts, dt = self.cfg, self.opts, self.compute_dtype
        cache_out = {}
        if kind in ("attn", "local", "xattn"):
            with jax.named_scope("attn"):
                h = apply_norm(x, p["attn"]["norm"], cfg.norm)
                q, k, v = attn.qkv_project(h, p["attn"], cfg, dt)
                if rope_cs is not None:
                    q = attn.apply_rope(q, *rope_cs)
                    k = attn.apply_rope(k, *rope_cs)
                impl = self._attn_impl(x.shape[1], training)
                window = cfg.local_window if kind == "local" else 0
                if opts.cp_attention:
                    # context parallelism: q's sequence over the model
                    # axis; k/v stay replicated there (gathered once — they
                    # are kv-headed and small), so the S^2 work shards even
                    # when head counts don't divide the model axis.
                    q = mesh_ctx.shard(q, "batch", "seq_cp", "kv_heads", None,
                                       "head_dim")
                ctx = attn.attend(q, k, v, impl=impl, causal=cfg.causal,
                                  window=window, chunk=opts.attn_chunk,
                                  softmax_dtype=jnp.dtype(opts.softmax_dtype))
                if opts.cp_attention:
                    ctx = mesh_ctx.shard(ctx, "batch", None, "kv_heads", None,
                                         "head_dim")
                x = x + attn.out_project(ctx, p["attn"], cfg, dt)
            if want_cache:
                cache_out["self"] = {"k": k, "v": v}
            if kind == "xattn":
                h = apply_norm(x, p["xnorm"], cfg.norm)
                qx, _, _ = attn.qkv_project(h, p["xattn"], cfg, dt)
                he = enc_out
                _, kx, vx = attn.qkv_project(he, p["xattn"], cfg, dt)
                ctx = attn.attend(qx, kx, vx, impl="full", causal=False)
                x = x + attn.out_project(ctx, p["xattn"], cfg, dt)
                if want_cache:
                    cache_out["cross"] = {"k": kx, "v": vx}
            with jax.named_scope("mlp"):
                h = apply_norm(x, p["mlp_norm"], cfg.norm)
                if cfg.n_experts:
                    y, aux = moe_lib.moe_mlp(h, p["mlp"], cfg, dt,
                                             grouped=opts.moe_grouped)
                    x = x + y
                    cache_out["aux"] = aux
                else:
                    from .layers import mlp as dense_mlp
                    x = x + dense_mlp(h, p["mlp"], cfg.act, dt)
        elif kind == "rec":
            h = apply_norm(x, p["norm"], cfg.norm)
            x = x + rglru_lib.recurrent_block(h, p, cfg, dt,
                                              use_kernel=opts.use_kernels)
            from .layers import mlp as dense_mlp
            h = apply_norm(x, p["mlp_norm"], cfg.norm)
            x = x + dense_mlp(h, p["mlp"], cfg.act, dt)
        elif kind == "mamba2":
            h = apply_norm(x, p["norm"], cfg.norm)
            x = x + ssm_lib.mamba2_block(h, p, cfg, dt, chunk=opts.ssd_chunk,
                                         use_kernel=opts.use_kernels)
        else:
            raise ValueError(kind)
        x = mesh_ctx.shard(x, "batch", "seq", "embed")
        return x, cache_out

    def _run_stack(self, params, x, rope_cs, *, training, enc_out=None,
                   remat=False):
        """Scan over pattern groups; returns (x, aux_loss_sum).

        ``remat`` accepts the legacy bool or a ``repro.remat.RematPolicy``:
        True/``full`` checkpoints every group output, a planned policy
        recomputes only the primitives the eviction search selected.
        """
        cfg = self.cfg
        pattern = cfg.block_pattern

        def group_body(carry, group_params):
            x, aux = carry
            for i, kind in enumerate(pattern):
                x, co = self._apply_block(kind, x, group_params[str(i)], rope_cs,
                                          training=training, enc_out=enc_out)
                aux = aux + co.get("aux", 0.0)
            return (x, aux), None

        from ..remat.policy import RematPolicy
        body = RematPolicy.coerce(remat).wrap(group_body)
        aux0 = jnp.zeros((), jnp.float32)
        if cfg.block_pattern:
            (x, aux), _ = jax.lax.scan(body, (x, aux0), params["pattern"])
        else:
            aux = aux0
        for i, kind in enumerate(cfg.tail_pattern):
            x, co = self._apply_block(kind, x, params["tail"][str(i)], rope_cs,
                                      training=training, enc_out=enc_out)
            aux = aux + co.get("aux", 0.0)
        return x, aux

    def _encode(self, params, frames, *, training):
        """Whisper encoder over precomputed frame embeddings (stub frontend)."""
        cfg = self.cfg
        pos = jnp.arange(frames.shape[1])
        x = cdt(frames, self.compute_dtype) + self._sinusoid(pos)[None]
        x = mesh_ctx.shard(x, "batch", "seq", "embed")
        enc_cfg = cfg.with_overrides(causal=False)
        saved, self.cfg = self.cfg, enc_cfg
        try:
            def body(carry, layer_params):
                y, _ = self._apply_block("attn", carry, layer_params, None,
                                         training=training)
                return y, None
            x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
        finally:
            self.cfg = saved
        return apply_norm(x, params["encoder"]["final_norm"], cfg.norm)

    # ---- logits / loss --------------------------------------------------------------
    def _lm_table(self, params):
        return params.get("lm_head", params["embed"])

    @jax.named_scope("head")
    def logits(self, params, x):
        cfg = self.cfg
        table = self._lm_table(params)
        out = jnp.einsum("bsd,vd->bsv", cdt(x, self.compute_dtype),
                         cdt(table, self.compute_dtype))
        return mesh_ctx.shard(out, "batch", "seq", "vocab")

    def greedy(self, logits):
        """Greedy next-token ids from (..., padded_vocab) logits.  The
        padding rows of the vocabulary are never picked: they are weights
        like any other, and their logits can win."""
        return jnp.argmax(logits[..., :self.cfg.vocab_size],
                          axis=-1).astype(jnp.int32)

    def _ce(self, logits, targets, mask):
        cfg = self.cfg
        lf = logits.astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            pad_bias = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab_size,
                                 0.0, -1e30)
            lf = lf + pad_bias
        lse = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)

    def _loss_from_h(self, params, x, targets, mask):
        opts = self.opts
        if opts.loss_impl == "full":
            return self._ce(self.logits(params, x), targets, mask)
        # chunked-vocab-free CE: scan over sequence chunks, remat each chunk
        c = opts.loss_chunk
        b, s, d = x.shape
        pad = (-s) % c
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nchunks = x.shape[1] // c
        xs = (x.reshape(b, nchunks, c, d).transpose(1, 0, 2, 3),
              targets.reshape(b, nchunks, c).transpose(1, 0, 2),
              mask.reshape(b, nchunks, c).transpose(1, 0, 2))

        @jax.checkpoint
        def chunk_nll(xc, tc, mc):
            lg = self.logits(params, xc)
            lf = lg.astype(jnp.float32)
            if self.cfg.padded_vocab != self.cfg.vocab_size:
                lf = lf + jnp.where(
                    jnp.arange(self.cfg.padded_vocab) < self.cfg.vocab_size, 0.0, -1e30)
            lse = jax.nn.logsumexp(lf, axis=-1)
            gold = jnp.take_along_axis(lf, tc[..., None], axis=-1)[..., 0]
            return ((lse - gold) * mc).sum()

        def body(acc, chunk):
            return acc + chunk_nll(*chunk), None
        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
        return tot / jnp.maximum(mask.sum(), 1.0)

    # ---- public: training ------------------------------------------------------------
    def loss_fn(self, params, batch, *, remat=True):
        """batch: {"tokens": (B, S+1) int32[, "frames": (B, F, D)]}."""
        cfg = self.cfg
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(targets, jnp.float32)
        else:
            mask = mask[:, 1:].astype(jnp.float32)
        x = self._embed_in(params, inputs)
        s = inputs.shape[1]
        rope_cs = self._rope(jnp.arange(s)[None, :])
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["frames"], training=True)
        x, aux = self._run_stack(params, x, rope_cs, training=True,
                                 enc_out=enc_out, remat=remat)
        x = apply_norm(x, params["final_norm"], cfg.norm)
        ce = self._loss_from_h(params, x, targets, mask)
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}

    # ======================================================================
    # serving: cache init / prefill / decode
    # ======================================================================

    def _cache_len(self, kind: str, max_len: int) -> int:
        if kind == "local":
            return min(self.cfg.local_window, max_len)
        return max_len

    def _block_cache_schema(self, kind: str, batch: int, max_len: int):
        """ShapeDtypeStructs for one block's decode cache (unstacked)."""
        cfg, dt = self.cfg, self.compute_dtype
        hd, kv = cfg.resolved_head_dim, cfg.n_kv_heads
        if kind in ("attn", "local", "xattn"):
            c = self._cache_len(kind, max_len)
            e = {"k": jax.ShapeDtypeStruct((batch, c, kv, hd), dt),
                 "v": jax.ShapeDtypeStruct((batch, c, kv, hd), dt)}
            if kind == "xattn":
                f = cfg.encoder_seq
                e["xk"] = jax.ShapeDtypeStruct((batch, f, kv, hd), dt)
                e["xv"] = jax.ShapeDtypeStruct((batch, f, kv, hd), dt)
            return e
        if kind == "rec":
            return {"conv": jax.ShapeDtypeStruct(
                        (batch, cfg.conv_width - 1, cfg.lru_width), dt),
                    "h": jax.ShapeDtypeStruct((batch, cfg.lru_width), jnp.float32)}
        if kind == "mamba2":
            conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
            return {"conv": jax.ShapeDtypeStruct(
                        (batch, cfg.conv_width - 1, conv_dim), dt),
                    "ssm": jax.ShapeDtypeStruct(
                        (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                        jnp.float32)}
        raise ValueError(kind)

    def cache_spec(self, batch: int, max_len: int):
        """Abstract cache pytree (dry-run input spec for serve_step)."""
        cfg = self.cfg
        g = cfg.n_pattern_groups

        def stack_sds(tree, n):
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)

        cache = {"pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}
        if cfg.block_pattern:
            cache["pattern"] = {
                str(i): stack_sds(self._block_cache_schema(kind, batch, max_len), g)
                for i, kind in enumerate(cfg.block_pattern)}
        if cfg.tail_pattern:
            cache["tail"] = {str(i): self._block_cache_schema(kind, batch, max_len)
                             for i, kind in enumerate(cfg.tail_pattern)}
        return cache

    def init_cache(self, batch: int, max_len: int):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_spec(batch, max_len))

    # ---- paged decode cache ----------------------------------------------------
    def supports_paged(self) -> bool:
        """Paged decode stores KV only — every block must be plain global
        attention (no rolling windows, recurrent state, or cross-attention)."""
        cfg = self.cfg
        return (set(cfg.block_pattern) <= {"attn"} and not cfg.tail_pattern
                and not cfg.is_encoder_decoder)

    def paged_cache_spec(self, batch: int, *, n_pages: int, page_tokens: int,
                         pages_per_req: int):
        """Abstract paged cache: per-layer k/v *pools* shared by the whole
        batch plus one page-table row and position per slot.  Pool leaves
        carry no batch axis — the DecodeRunner passes them through its
        gather/scatter wholesale, which is exactly how the in-executable KV
        copy is dropped."""
        cfg, dt = self.cfg, self.compute_dtype
        assert self.supports_paged(), \
            f"paged cache unsupported for pattern {cfg.block_pattern}"
        g = cfg.n_pattern_groups
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        pool = jax.ShapeDtypeStruct((g, n_pages, kv, page_tokens, hd), dt)
        return {
            "pos": jax.ShapeDtypeStruct((batch,), jnp.int32),
            "block_tables": jax.ShapeDtypeStruct((batch, pages_per_req),
                                                 jnp.int32),
            "pattern": {str(i): {"k_pages": pool, "v_pages": pool}
                        for i in range(len(cfg.block_pattern))},
        }

    def init_paged_cache(self, batch: int, *, n_pages: int, page_tokens: int,
                         pages_per_req: int):
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.paged_cache_spec(batch, n_pages=n_pages,
                                  page_tokens=page_tokens,
                                  pages_per_req=pages_per_req))

    # ---- per-block decode ------------------------------------------------------
    def _apply_block_decode(self, kind, x, p, cache, pos, rope_cs):
        """x: (B,1,D); cache: this block's entries; pos: (B,) int32 — every
        batch row advances on its own position clock, so staggered admissions
        with unequal prompt lengths attend (and write) at their own offsets."""
        cfg, dt = self.cfg, self.compute_dtype
        new_cache = dict(cache)
        if kind in ("attn", "local", "xattn"):
            with jax.named_scope("attn"):
                h = apply_norm(x, p["attn"]["norm"], cfg.norm)
                q, k, v = attn.qkv_project(h, p["attn"], cfg, dt)
                if rope_cs is not None:
                    q = attn.apply_rope(q, *rope_cs)
                    k = attn.apply_rope(k, *rope_cs)
                c = cache["k"].shape[1]
                slot = (jnp.mod(pos, c) if kind == "local"
                        else jnp.minimum(pos, c - 1))
                rows = jnp.arange(k.shape[0])
                k_cache = cache["k"].at[rows, slot].set(k[:, 0])
                v_cache = cache["v"].at[rows, slot].set(v[:, 0])
                new_cache["k"], new_cache["v"] = k_cache, v_cache
                window = cfg.local_window if kind == "local" else 0
                ctx = attn.attend_decode(q, k_cache, v_cache, pos,
                                         window=window,
                                         rolling=(kind == "local"))
                x = x + attn.out_project(ctx, p["attn"], cfg, dt)
            if kind == "xattn":
                h = apply_norm(x, p["xnorm"], cfg.norm)
                qx, _, _ = attn.qkv_project(h, p["xattn"], cfg, dt)
                enc_len = cache["xk"].shape[1]
                ctx = attn.attend_decode(qx, cache["xk"], cache["xv"],
                                         jnp.asarray(enc_len - 1, jnp.int32))
                x = x + attn.out_project(ctx, p["xattn"], cfg, dt)
            with jax.named_scope("mlp"):
                h = apply_norm(x, p["mlp_norm"], cfg.norm)
                if cfg.n_experts:
                    y, _ = moe_lib.moe_mlp(h, p["mlp"], cfg, dt,
                                           grouped=self.opts.moe_grouped)
                    x = x + y
                else:
                    from .layers import mlp as dense_mlp
                    x = x + dense_mlp(h, p["mlp"], cfg.act, dt)
            return x, new_cache
        if kind == "rec":
            h = apply_norm(x, p["norm"], cfg.norm)
            y, st = rglru_lib.recurrent_block_decode(h[:, 0], cache, p, cfg, dt)
            x = x + y[:, None, :]
            from .layers import mlp as dense_mlp
            h = apply_norm(x, p["mlp_norm"], cfg.norm)
            return x + dense_mlp(h, p["mlp"], cfg.act, dt), st
        if kind == "mamba2":
            h = apply_norm(x, p["norm"], cfg.norm)
            y, st = ssm_lib.mamba2_block_decode(h[:, 0], cache, p, cfg, dt)
            return x + y[:, None, :], st
        raise ValueError(kind)

    def _apply_block_decode_paged(self, x, p, cache, pos, tables, rope_cs):
        """One attn block against the paged pool.  cache: {"k_pages",
        "v_pages"} (P,kv,pt,hd); tables: (B,maxp) page-index rows; pos: (B,).
        The new token's KV is scattered to (tables[b, pos//pt], :, pos%pt) and
        attention reads the pool through the table — no gathered copy of the
        request's KV ever materializes."""
        cfg, dt = self.cfg, self.compute_dtype
        with jax.named_scope("attn"):
            h = apply_norm(x, p["attn"]["norm"], cfg.norm)
            q, k, v = attn.qkv_project(h, p["attn"], cfg, dt)
            if rope_cs is not None:
                q = attn.apply_rope(q, *rope_cs)
                k = attn.apply_rope(k, *rope_cs)
            k_pages, v_pages = cache["k_pages"], cache["v_pages"]
            pt = k_pages.shape[2]
            page = jnp.take_along_axis(tables, (pos // pt)[:, None],
                                       axis=1)[:, 0]
            off = pos % pt
            # duplicate (page, off) pairs from runner slot-padding write
            # identical values, so the scatter is order-independent
            k_pages = k_pages.at[page, :, off].set(k[:, 0])  # (B,kv,hd) rows
            v_pages = v_pages.at[page, :, off].set(v[:, 0])
            ctx = attn.attend_paged_decode(q, k_pages, v_pages, tables, pos,
                                           impl=self.opts.paged_attn_impl)
            x = x + attn.out_project(ctx, p["attn"], cfg, dt)
        with jax.named_scope("mlp"):
            h = apply_norm(x, p["mlp_norm"], cfg.norm)
            if cfg.n_experts:
                y, _ = moe_lib.moe_mlp(h, p["mlp"], cfg, dt,
                                       grouped=self.opts.moe_grouped)
                x = x + y
            else:
                from .layers import mlp as dense_mlp
                x = x + dense_mlp(h, p["mlp"], cfg.act, dt)
        return x, {"k_pages": k_pages, "v_pages": v_pages}

    # ---- public: decode (one token for every sequence in the batch) --------------
    def decode_step(self, params, cache, tokens):
        """tokens: (B,) int32 -> (logits (B, V), new cache).

        ``cache["pos"]`` is a (B,) per-slot position vector: each row attends
        at its own offset, so a batch mixing requests admitted at different
        times (unequal prompt lengths) decodes exactly.  A cache carrying
        ``block_tables`` selects the paged path: KV lives in per-layer page
        pools and attention consumes the page table in-kernel."""
        if "block_tables" in cache:
            return self._decode_step_paged(params, cache, tokens)
        cfg = self.cfg
        pos = cache["pos"]
        x = self._embed_in(params, tokens[:, None])
        rope_cs = self._rope(pos[:, None])

        pattern = cfg.block_pattern
        new_cache = {"pos": pos + 1}
        if pattern:
            def body(x, xs):
                gp, gc = xs
                outs = {}
                for i, kind in enumerate(pattern):
                    x, nc = self._apply_block_decode(kind, x, gp[str(i)],
                                                     gc[str(i)], pos, rope_cs)
                    outs[str(i)] = nc
                return x, outs
            x, pat_cache = jax.lax.scan(
                body, x, (params["pattern"], cache["pattern"]))
            new_cache["pattern"] = pat_cache
        if cfg.tail_pattern:
            tail = {}
            for i, kind in enumerate(cfg.tail_pattern):
                x, nc = self._apply_block_decode(kind, x, params["tail"][str(i)],
                                                 cache["tail"][str(i)], pos, rope_cs)
                tail[str(i)] = nc
            new_cache["tail"] = tail
        x = apply_norm(x, params["final_norm"], cfg.norm)
        logits = self.logits(params, x)[:, 0, :]
        return logits, new_cache

    def _decode_step_paged(self, params, cache, tokens):
        """Paged decode step: same contract as ``decode_step`` over the
        ``paged_cache_spec`` layout.  ``block_tables`` rides along unchanged
        (the engine maintains it host-side as pages are granted)."""
        cfg = self.cfg
        pos = cache["pos"]
        tables = cache["block_tables"]
        x = self._embed_in(params, tokens[:, None])
        rope_cs = self._rope(pos[:, None])

        def body(x, xs):
            gp, gc = xs
            outs = {}
            for i in range(len(cfg.block_pattern)):
                x, nc = self._apply_block_decode_paged(
                    x, gp[str(i)], gc[str(i)], pos, tables, rope_cs)
                outs[str(i)] = nc
            return x, outs

        x, pat_cache = jax.lax.scan(body, x,
                                    (params["pattern"], cache["pattern"]))
        new_cache = {"pos": pos + 1, "block_tables": tables,
                     "pattern": pat_cache}
        x = apply_norm(x, params["final_norm"], cfg.norm)
        logits = self.logits(params, x)[:, 0, :]
        return logits, new_cache

    # ---- public: prefill -----------------------------------------------------------
    def prefill(self, params, batch, max_len: Optional[int] = None):
        """batch: {"tokens": (B,S)[, "frames": ..., "true_len": scalar]}
        -> (last-pos logits, cache).

        ``true_len`` (traced scalar) supports length-bucketed prompts: tokens
        beyond it are padding — the returned logits are read at position
        ``true_len - 1`` and the cache position starts there, so the padded
        tail is masked out of every subsequent decode step until it is
        overwritten.  Only attention caches are pad-safe (recurrent state
        integrates every input token); callers gate on the architecture."""
        cfg = self.cfg
        tokens = batch["tokens"]
        true_len = batch.get("true_len")
        b, s = tokens.shape
        max_len = max_len or s
        x = self._embed_in(params, tokens)
        rope_cs = self._rope(jnp.arange(s)[None, :])
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, batch["frames"], training=False)

        def fill_kv(kind, k, v):
            """(B,S,KV,hd) -> cache buffer of length _cache_len(kind)."""
            c = self._cache_len(kind, max_len)
            if kind == "local":
                # keep the last `c` positions, stored in rolling order
                start = max(0, s - c)
                kw, vw = k[:, start:], v[:, start:]
                if kw.shape[1] < c:
                    kw = jnp.pad(kw, ((0, 0), (0, c - kw.shape[1]), (0, 0), (0, 0)))
                    vw = jnp.pad(vw, ((0, 0), (0, c - vw.shape[1]), (0, 0), (0, 0)))
                idx = jnp.mod(start + jnp.arange(c), c)
                kr = jnp.zeros_like(kw).at[:, idx].set(kw)
                vr = jnp.zeros_like(vw).at[:, idx].set(vw)
                return kr, vr
            if s < c:
                k = jnp.pad(k, ((0, 0), (0, c - s), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, c - s), (0, 0), (0, 0)))
            return k[:, :c], v[:, :c]

        def apply_prefill(kind, x, p):
            dt = self.compute_dtype
            if kind in ("attn", "local", "xattn"):
                with jax.named_scope("attn"):
                    h = apply_norm(x, p["attn"]["norm"], cfg.norm)
                    q, k, v = attn.qkv_project(h, p["attn"], cfg, dt)
                    if rope_cs is not None:
                        q = attn.apply_rope(q, *rope_cs)
                        k = attn.apply_rope(k, *rope_cs)
                    impl = self._attn_impl(s, training=False)
                    window = cfg.local_window if kind == "local" else 0
                    ctx = attn.attend(q, k, v, impl=impl, causal=True,
                                      window=window,
                                      chunk=self.opts.attn_chunk)
                    x = x + attn.out_project(ctx, p["attn"], cfg, dt)
                kc, vc = fill_kv(kind, k, v)
                entry = {"k": kc, "v": vc}
                if kind == "xattn":
                    h = apply_norm(x, p["xnorm"], cfg.norm)
                    qx, _, _ = attn.qkv_project(h, p["xattn"], cfg, dt)
                    _, kx, vx = attn.qkv_project(enc_out, p["xattn"], cfg, dt)
                    ctx = attn.attend(qx, kx, vx, impl="full", causal=False)
                    x = x + attn.out_project(ctx, p["xattn"], cfg, dt)
                    entry["xk"], entry["xv"] = kx, vx
                with jax.named_scope("mlp"):
                    h = apply_norm(x, p["mlp_norm"], cfg.norm)
                    if cfg.n_experts:
                        y, _ = moe_lib.moe_mlp(h, p["mlp"], cfg, dt,
                                               grouped=self.opts.moe_grouped)
                        x = x + y
                    else:
                        from .layers import mlp as dense_mlp
                        x = x + dense_mlp(h, p["mlp"], cfg.act, dt)
                return x, entry
            if kind == "rec":
                h = apply_norm(x, p["norm"], cfg.norm)
                y, st = rglru_lib.recurrent_block_prefill(h, p, cfg, dt)
                x = x + y
                from .layers import mlp as dense_mlp
                h = apply_norm(x, p["mlp_norm"], cfg.norm)
                return x + dense_mlp(h, p["mlp"], cfg.act, dt), st
            if kind == "mamba2":
                h = apply_norm(x, p["norm"], cfg.norm)
                y, st = ssm_lib.mamba2_block_prefill(h, p, cfg, dt,
                                                     chunk=self.opts.ssd_chunk)
                return x + y, st
            raise ValueError(kind)

        pos0 = jnp.asarray(s if true_len is None else true_len, jnp.int32)
        cache = {"pos": jnp.broadcast_to(pos0, (b,))}
        pattern = cfg.block_pattern
        if pattern:
            def body(x, gp):
                outs = {}
                for i, kind in enumerate(pattern):
                    x, entry = apply_prefill(kind, x, gp[str(i)])
                    outs[str(i)] = entry
                return x, outs
            x, pat_cache = jax.lax.scan(body, x, params["pattern"])
            cache["pattern"] = pat_cache
        if cfg.tail_pattern:
            tail = {}
            for i, kind in enumerate(cfg.tail_pattern):
                x, entry = apply_prefill(kind, x, params["tail"][str(i)])
                tail[str(i)] = entry
            cache["tail"] = tail
        x = apply_norm(x, params["final_norm"], cfg.norm)
        if true_len is None:
            last = x[:, -1:, :]
        else:
            last = jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(true_len, jnp.int32) - 1, 1, axis=1)
        logits = self.logits(params, last)[:, 0, :]
        return logits, cache

    # ---- public: inference forward (no cache) — smoke tests -----------------------------
    def forward(self, params, tokens, frames=None):
        cfg = self.cfg
        x = self._embed_in(params, tokens)
        rope_cs = self._rope(jnp.arange(tokens.shape[1])[None, :])
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = self._encode(params, frames, training=False)
        x, _ = self._run_stack(params, x, rope_cs, training=False, enc_out=enc_out)
        x = apply_norm(x, params["final_norm"], cfg.norm)
        return self.logits(params, x)
