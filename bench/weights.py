"""What every family's random weights share: the key made from ``--seed``,
the normal draw, and one jitted call that makes the program's parameters
on the device and checks them against the program's own tree.

The benchmark owns the weights.  A family (``families/<name>.py``) draws
them in its own layout from keys folded out of the seed, hands the program
a copy rearranged into the program's parameter tree, and draws the same
leaves again for its float32 reference, so that the reference never reads
anything the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed up to 64 bits (the benchmark's seeds
    pass 32 signed bits)."""
    seed = int(seed)
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def draw(key, shape, std, dtype):
    """Normal with standard deviation ``std``, drawn in float32 and cast;
    ``std`` None is a leaf of ones (a norm weight)."""
    if std is None:
        return jnp.ones(shape, dtype)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def on_device(make, seed: int, abstract):
    """``make(key)``, the program's parameters from ``seed_key(seed)``, run
    as one jitted call, after checking leaf by leaf against ``abstract``
    (the program's own ``jax.eval_shape`` of its init): same tree, same
    shapes.  One compiled program serves every seed."""
    fn = jax.jit(make)
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
