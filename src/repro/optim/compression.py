"""Int8 gradient compression with error feedback for the DCN (pod) axis.

The paper's internode measurements (Figs. 14, 19) show the NIC is the
weakest datapath — two orders of magnitude under HBM.  The TPU analogue is
the inter-pod DCN link, which carries exactly one traffic class in training:
the cross-pod gradient all-reduce.  This module quantizes that traffic to
int8 (4x fewer wire bytes) with error feedback so the quantization error is
re-injected next step (1-bit-Adam-style convergence behavior).

Mechanics: inside a ``shard_map`` over the ``pod`` axis, the all-reduce is
decomposed into all-to-all(int8 segments) -> local f32 sum -> requantize ->
all-gather(int8): every wire crossing is int8, every accumulation is f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def quantized_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean over ``axis_name`` with int8 wire traffic (call inside shard_map).

    x is this shard's f32 gradient (replicated-layout w.r.t. the axis).
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    orig_shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    flat = jnp.pad(flat, (0, pad))
    segs = flat.reshape(n, -1)                       # segment s for rank s

    q, scale = quantize(segs)
    # everyone sends segment s to rank s: all_to_all over leading dim
    q_recv = jax.lax.all_to_all(
        q, axis_name, split_axis=0, concat_axis=0, tiled=False
    )                                                # (n, seg) int8 on wire
    scales = jax.lax.all_gather(scale, axis_name)    # (n,) f32 (tiny)
    local_sum = jnp.sum(
        q_recv.astype(jnp.float32) * scales[:, None], axis=0
    ) / n                                            # mean, f32 accumulate

    q2, scale2 = quantize(local_sum)
    q_all = jax.lax.all_gather(q2, axis_name)        # (n, seg) int8 on wire
    scale_all = jax.lax.all_gather(scale2, axis_name)
    out = (q_all.astype(jnp.float32) * scale_all[:, None]).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape)


def init_error_feedback(grads):
    return jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads
    )


def compressed_grad_sync(
    grads,
    ef,
    mesh: Mesh,
    axis: str = "pod",
):
    """Cross-pod gradient mean with int8 wire + error feedback.

    ``grads`` are the per-pod means (already synced over in-pod axes by
    pjit); ``ef`` is the persistent error-feedback pytree.  Returns
    (synced_grads, new_ef).  No-op (exact mean preserved) if the mesh has
    no ``axis``.
    """
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return grads, ef

    other_axes = tuple(a for a in mesh.axis_names if a != axis)

    def leaf_sync(g, e):
        gf = g.astype(jnp.float32) + e
        synced = quantized_all_reduce(gf, axis)
        new_e = gf - synced                      # residual re-injected later
        return synced.astype(g.dtype), new_e

    def tree_sync(gs, es):
        return jax.tree.map(leaf_sync, gs, es, is_leaf=None), None

    # shard_map: everything replicated over `axis` (grads are identical
    # within a pod after pjit's automatic in-pod reduction).  Two maps, not
    # one returning tuples — tree.map would recurse INTO the tuples; XLA
    # CSEs the duplicated sync.
    def fn(gs, es):
        new_g = jax.tree.map(lambda g, e: leaf_sync(g, e)[0], gs, es)
        new_e = jax.tree.map(lambda g, e: leaf_sync(g, e)[1], gs, es)
        return new_g, new_e

    spec = P()  # replicated over every axis; collectives only over `axis`
    specs_g = jax.tree.map(lambda _: spec, grads)
    specs_e = jax.tree.map(lambda _: spec, ef)
    # manual over `axis` only; the other mesh axes stay automatic
    fn_mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(specs_g, specs_e),
        out_specs=(specs_g, specs_e),
        axis_names={axis},
        check_vma=False,
    )
    return fn_mapped(grads, ef)
