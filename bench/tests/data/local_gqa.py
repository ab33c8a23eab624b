"""A dense GQA decoder whose layers alternate a sliding-window attention
layer and a global one, as the program's ``("local", "attn")`` pattern runs
them.  The harness's tests plant it as a new file of ``families/`` to show
that a family is found by name and needs no edit of a file that is there.

It is ``dense_gqa`` with three changes: the program's pattern and window,
the layers split over the pattern's two positions, and a reference whose
local layers let a position see itself and the ``sliding_window`` - 1
before it.  Its yardstick counts the KV a local layer keeps as at most the
window; its FLOPs are dense_gqa's.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import weights as wlib
from families import dense_gqa as dense
from reference import ein, ident, rms_norm, rope

PATTERN = ("local", "attn")


@dataclass(frozen=True)
class Dims(dense.Dims):
    window: int = 0

    def kv_bytes(self, contexts) -> int:
        per_layer = self.kv_bytes_per_token() // self.layers
        n_local = self.layers // len(PATTERN)
        return per_layer * sum(
            n_local * min(int(c), self.window) +
            (self.layers - n_local) * int(c) for c in contexts)


def yardstick(cfg_file: dict) -> Dims:
    return Dims(**vars(dense.Dims.from_config(cfg_file)),
                window=cfg_file["sliding_window"])


def check_program(cfg, cfg_file: dict) -> None:
    dense.check_against(cfg, dict(dense.program_want(cfg_file),
                                  block_pattern=PATTERN,
                                  local_window=cfg_file["sliding_window"]))


def program_params(seed: int, cfg_file: dict, model):
    dims = yardstick(cfg_file)
    dtype = jnp.dtype(cfg_file["torch_dtype"])

    def make(k):
        p = dense.to_program(dense.canonical(k, dims, dtype))
        block = p["pattern"]["0"]           # layers stacked in order
        p["pattern"] = {str(i): jax.tree.map(
            lambda a, i=i: a[i::len(PATTERN)], block)
            for i in range(len(PATTERN))}
        return p
    return wlib.on_device(make, seed,
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def local_layer(x, w, dims: Dims, theta: float, eps: float, q, window: int):
    """``dense_gqa.layer``, where a layer whose weights carry ``local``
    lets a position attend to itself and the ``window`` - 1 before it
    only."""
    pos = jnp.arange(x.shape[1])
    h = rms_norm(x, w["attn_norm"], eps)
    qh = ein("bsd,dnh->bsnh", h, w["wq"], q)
    kh = ein("bsd,dnh->bsnh", h, w["wk"], q)
    vh = ein("bsd,dnh->bsnh", h, w["wv"], q)
    if dims.qkv_bias:
        qh, kh, vh = qh + w["bq"], kh + w["bk"], vh + w["bv"]
    qh, kh = rope(qh, pos, theta), rope(kh, pos, theta)
    g = dims.n_heads // dims.n_kv_heads
    kh = jnp.repeat(kh, g, axis=2)
    vh = jnp.repeat(vh, g, axis=2)
    scores = ein("bqnh,bknh->bnqk", qh, kh, q) / np.sqrt(dims.head_dim)
    seen = (pos[None, :] <= pos[:, None]) & (
        ~w["local"] | (pos[None, :] > pos[:, None] - window))
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    x = x + ein("bsnh,nhd->bsd", ein("bnqk,bknh->bqnh", p, vh, q),
                w["wo"], q)
    h = rms_norm(x, w["mlp_norm"], eps)
    a = jax.nn.silu(ein("bsd,df->bsf", h, w["w_gate"], q)) * \
        ein("bsd,df->bsf", h, w["w_up"], q)
    return x + ein("bsf,fd->bsd", a, w["w_down"], q)


@dataclass
class Reference(dense.Reference):
    """``dense_gqa.Reference`` whose layer weights say whether the layer
    is local, and whose layer masks by that."""
    local_window: int = 0

    def __post_init__(self):
        super().__post_init__()
        dims, theta, eps, q = self.dims, self.theta, self.eps, self.q
        win, layer_w = self.local_window, self._layer_w
        self._layer_w = lambda k, li: dict(
            layer_w(k, li),
            local=jnp.asarray(PATTERN[li % len(PATTERN)] == "local"))
        self._layer = jax.jit(
            lambda x, w: local_layer(x, w, dims, theta, eps, q, win))


def reference(cfg_file: dict, seed: int, quant: str = "none") -> Reference:
    return Reference(dims=yardstick(cfg_file),
                     theta=float(cfg_file["rope_theta"]),
                     eps=float(cfg_file["rms_norm_eps"]), seed=seed,
                     seq_len=cfg_file["engine"]["max_len"],
                     rows=cfg_file["check"]["rows"], quant=quant,
                     local_window=cfg_file["sliding_window"])
