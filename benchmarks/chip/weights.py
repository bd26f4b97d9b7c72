"""Seeded random weights, made on the device in one jitted call.

The harness makes the weights itself, in the served dtype and in the
layout the program's parameter tree has, and hands the same arrays to
the program and (upcast) to the reference.  Initialisation is by each
leaf's role, with fan-in scaling, so logits stay of order one and a
comparison of logits means something.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ONES = ("scale", "q_norm", "k_norm")
ZEROS = ("bias",)


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including seeds wider than
    32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(
        jax.random.PRNGKey(seed % 2**32), seed // 2**32 % 2**32
    )


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _fan_in(name: str, shape, stacked: bool) -> int:
    dims = shape[1:] if stacked else shape
    if name == "w_o":            # (heads, head_dim, d_model)
        return dims[0] * dims[1]
    return dims[0]


def _init(path, s, key):
    name = _leaf_name(path)
    if name in ONES:
        return jnp.ones(s.shape, s.dtype)
    if name in ZEROS:
        return jnp.zeros(s.shape, s.dtype)
    if name == "embedding":
        std = 0.02
    else:
        stacked = any(getattr(p, "key", None) == "stages" for p in path)
        std = 1.0 / math.sqrt(_fan_in(name, s.shape, stacked))
    return (jax.random.normal(key, s.shape, s.dtype) * std).astype(s.dtype)


def make_params(shapes, seed: int):
    """``shapes``: the program's parameter tree as ShapeDtypeStructs."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [_init(p, s, k) for (p, s), k in zip(flat, keys)]
        )

    return jax.jit(build)(key_for(seed))
