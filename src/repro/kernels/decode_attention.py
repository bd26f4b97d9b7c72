"""Flash-decode (split-K) attention kernel for single-token decode.

The decode shapes (``decode_32k``, ``long_500k``) are the paper's Fig. 17
regime: one token's worth of compute against a huge read-mostly buffer —
pure data movement.  Arithmetic intensity is ~1 FLOP/byte, so the *only*
lever is keeping the KV read stream at full HBM bandwidth; this kernel
streams the cache through VMEM in ``block_k`` tiles, carrying the online
softmax statistics in scratch, with all ``G = Hq/Hkv`` query heads of a KV
head processed per tile (the KV tile is read ONCE for all of them — the
kernel-level expression of the paper's "reads dominate" GEMM finding).

The cache is the decode step's whole stacked buffer ``(layers, B, Hkv,
Smax, D)``; the layer to read is scalar-prefetched into the K/V index
maps, so no layer slice of the cache is ever materialised.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF

DEFAULT_BLOCK_K = 512


def _decode_kernel(
    len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bk, scale, ragged,
):
    del layer_ref  # used by the index maps only
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]   # whole (B,) vector lives in SMEM
    k_lo = kv_idx * bk

    @pl.when(k_lo < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (G, D)
        k = k_ref[0, 0, 0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, 0, 0].astype(jnp.float32)           # (bk, D)
        if ragged:
            # the last tile overhangs the cache: its rows past the end
            # hold unspecified values, and 0 * NaN would poison the sum
            row = k_lo + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(row < length, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                # (G, bk)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,        # (B, Hq, D) — one new token per row
    k_cache: jax.Array,  # (L, B, Hkv, Smax, D) — the stacked cache
    v_cache: jax.Array,  # (L, B, Hkv, Smax, D)
    lengths: jax.Array,  # (B,) int32 valid lengths
    layer: jax.Array,    # () int32 layer of the stack to read
    *,
    scale: float | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, D = q.shape
    _, _, Hkv, Smax, _ = k_cache.shape
    G = Hq // Hkv
    bk = min(block_k, Smax)
    scale = (D ** -0.5) if scale is None else scale

    qg = q.reshape(B, Hkv, G, D)
    # any cache length: a last tile that overhangs the cache is masked by
    # position, so the cache is never padded (a copy per step)
    grid = (B, Hkv, pl.cdiv(Smax, bk))

    def kv_map(b, h, j, len_ref, layer_ref):
        return (layer_ref[0], b, h, j, 0)

    def q_map(b, h, j, len_ref, layer_ref):
        return (b, h, 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, bk=bk, scale=scale, ragged=Smax % bk != 0
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                       # lengths, layer
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), q_map),
                pl.BlockSpec((1, 1, 1, bk, D), kv_map),
                pl.BlockSpec((1, 1, 1, bk, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D), q_map),
            scratch_shapes=[
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G,), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        name="flash_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        qg, k_cache, v_cache,
    )
    return out.reshape(B, Hq, D)
