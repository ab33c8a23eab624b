"""Random weights of a dense GQA decoder, made from the seed.

The benchmark owns the weights: it draws them in its own layout, leaf by
leaf and layer by layer from keys folded out of ``--seed``, and hands the
program a copy rearranged into the program's parameter tree.  The float32
reference draws the same leaves again from the same seed, one layer at a
time, so it never reads anything the program made.

Scales: every matrix is normal with standard deviation 1/sqrt(its
contraction width), the tables ``TABLE_STD``, the QKV biases ``BIAS_STD``,
the norm weights 1.  At these scales a random 24-layer stack is not chaotic, so
rounding in the program moves a logit by about its own size and no more,
and a logit comparison with a tight limit is possible.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from yardstick import Dims

# leaf -> stable id (folded into the key: never renumber, only append)
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
                "mlp_norm", "w_gate", "w_up", "w_down")
TABLES = {"embed": 100, "lm_head": 101}
TABLE_STD = 0.02
BIAS_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed up to 64 bits (the benchmark's seeds
    pass 32 signed bits)."""
    seed = int(seed)
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def layer_leaf_shapes(dims: Dims) -> dict:
    """name -> (shape of one layer's leaf, std, or None for a ones leaf)."""
    d, nh, kv, hd, ff = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                         dims.head_dim, dims.d_ff)
    out = {
        "attn_norm": ((d,), None),
        "wq": ((d, nh, hd), 1 / math.sqrt(d)),
        "wk": ((d, kv, hd), 1 / math.sqrt(d)),
        "wv": ((d, kv, hd), 1 / math.sqrt(d)),
        "wo": ((nh, hd, d), 1 / math.sqrt(nh * hd)),
        "mlp_norm": ((d,), None),
        "w_gate": ((d, ff), 1 / math.sqrt(d)),
        "w_up": ((d, ff), 1 / math.sqrt(d)),
        "w_down": ((ff, d), 1 / math.sqrt(ff)),
    }
    if dims.qkv_bias:
        out["bq"] = ((nh, hd), "bias")
        out["bk"] = ((kv, hd), "bias")
        out["bv"] = ((kv, hd), "bias")
    return {k: out[k] for k in LAYER_LEAVES if k in out}


def _draw(key, shape, std, dtype):
    if std is None:
        return jnp.ones(shape, dtype)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_leaf(seed_k, name: str, layer, dims: Dims, dtype=jnp.bfloat16):
    """One layer's leaf ``name`` (``layer`` may be traced)."""
    shape, std = layer_leaf_shapes(dims)[name]
    if std == "bias":
        std = BIAS_STD
    key = jax.random.fold_in(
        jax.random.fold_in(seed_k, LAYER_LEAVES.index(name)), layer)
    return _draw(key, shape, std, dtype)


def layer_weights(k, layer, dims: Dims, dtype=jnp.bfloat16) -> dict:
    """Every leaf of one layer, as the served dtype gives them."""
    return {n: layer_leaf(k, n, layer, dims, dtype)
            for n in layer_leaf_shapes(dims)}


def table(k, name: str, dims: Dims, dtype=jnp.bfloat16):
    """The embedding (or the untied head) over the padded vocabulary."""
    key = jax.random.fold_in(k, TABLES[name])
    return _draw(key, (dims.padded_vocab, dims.d_model), TABLE_STD,
                 dtype)


def canonical(k, dims: Dims, dtype=jnp.bfloat16) -> dict:
    """The whole model in the benchmark's layout, layers stacked on axis 0,
    from the key ``k`` of ``seed_key``.  Jit it with ``k`` as an argument:
    each leaf is then drawn, scaled and cast in one fused program, and one
    compiled program serves every seed."""
    layers = {}
    for name in layer_leaf_shapes(dims):
        layers[name] = jax.vmap(
            lambda l, n=name: layer_leaf(k, n, l, dims, dtype))(
                jnp.arange(dims.layers))
    out = {"embed": table(k, "embed", dims, dtype),
           "final_norm": jnp.ones((dims.d_model,), dtype),
           "layers": layers}
    if not dims.tied:
        out["lm_head"] = table(k, "lm_head", dims, dtype)
    return out


def to_program(c: dict) -> dict:
    """The benchmark's layout -> the tree ``repro.models.Transformer`` reads
    for a one-kind ``("attn",)`` pattern.  Its RMS norms scale by
    ``1 + scale``, so a norm weight w is handed over as w - 1."""
    L = c["layers"]
    attn = {"norm": {"scale": L["attn_norm"] - 1},
            "wq": L["wq"], "wk": L["wk"], "wv": L["wv"], "wo": L["wo"]}
    for b in ("bq", "bk", "bv"):
        if b in L:
            attn[b] = L[b]
    out = {"embed": c["embed"],
           "final_norm": {"scale": c["final_norm"] - 1},
           "pattern": {"0": {
               "attn": attn,
               "mlp_norm": {"scale": L["mlp_norm"] - 1},
               "mlp": {"w_gate": L["w_gate"], "w_up": L["w_up"],
                       "w_down": L["w_down"]}}}}
    if "lm_head" in c:
        out["lm_head"] = c["lm_head"]
    return out


def program_params(seed: int, dims: Dims, abstract, dtype=jnp.bfloat16):
    """Make the program's parameters on the device in one jitted call, and
    check them leaf by leaf against ``abstract`` (the program's own
    ``jax.eval_shape`` of its init): same tree, same shapes."""
    fn = jax.jit(lambda k: to_program(canonical(k, dims, dtype)))
    key = seed_key(seed)
    want = jax.tree_util.tree_structure(abstract)
    got_abs = jax.eval_shape(fn, key)
    if jax.tree_util.tree_structure(got_abs) != want:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{jax.tree_util.tree_structure(got_abs)}\n{want}")
    for a, b in zip(jax.tree_util.tree_leaves(got_abs),
                    jax.tree_util.tree_leaves(abstract)):
        if a.shape != b.shape:
            raise ValueError(f"leaf shape {a.shape} != program's {b.shape}")
    return fn(key)
