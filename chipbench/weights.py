"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the plain reference can start
from the same numbers without taking anything the program made.  Each leaf of
the program's parameter tree is drawn by the rule the program's own
initialiser uses for a leaf of that name: normal with std 1/sqrt(fan_in) for a
matrix, std 0.02 for the embedding, zeros for norm scales and biases, ones for
the SSM skip.  Every leaf has its own key, folded from the seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ZEROS = {"ln", "ln1", "ln2", "final_ln", "gate_ln", "cross_ln", "conv_b",
         "bq", "bk", "bv", "a_log", "dt_bias"}
ONES = {"d_skip"}


def seed_key(seed: int):
    """A PRNG key for any whole number the command line takes."""
    return jax.random.key(seed % (1 << 63))


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _draw(key, name: str, shape, dtype):
    if name in ZEROS:
        return jnp.zeros(shape, dtype)
    if name in ONES:
        return jnp.ones(shape, dtype)
    std = 0.02 if name == "embed" else 1.0 / math.sqrt(max(shape[-2], 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_init(shapes, shardings=None):
    """``init(key) -> tree`` shaped like ``shapes`` (a tree of
    ShapeDtypeStruct), jitted once; ``shardings`` places the leaves."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key):
        leaves = [_draw(jax.random.fold_in(key, i), leaf_name(path),
                        s.shape, s.dtype)
                  for i, (path, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(init, out_shardings=shardings)
