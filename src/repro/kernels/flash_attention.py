"""Blocked flash attention (Pallas, TPU target).

TPU adaptation of the FlashAttention idea through the paper's lens: the
datapath that matters on-chip is HBM→VMEM.  A naive attention materializes
the (Sq, Sk) score matrix in HBM — `2·Sq·Sk·2B` of traffic per head; the
blocked kernel keeps a (bq, bk) tile plus the running (m, l, acc) statistics
in VMEM, so HBM traffic drops to the Q/K/V/O tensors themselves.  BlockSpec
shapes are the on-chip placement policy: bq/bk are chosen so
``(bq + 2·bk)·D·2B + bq·bk·4B`` fits VMEM with MXU-aligned dims
(multiples of 128).

Supports the mask kinds of the assigned architectures (causal, sliding
window, chunked, bidirectional) and GQA via q-head grouping; fully-masked
KV blocks are *compute-skipped* with ``pl.when`` (the TPU analogue of not
launching the CUDA block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _block_reachable(kind: str, window: int, chunk: int,
                     q_lo, q_hi, k_lo, k_hi):
    """Static/traced predicate: can *any* (q, k) pair in this tile attend?

    q in [q_lo, q_hi), k in [k_lo, k_hi).  Used for compute-skipping.
    """
    if kind == "bidirectional":
        return True
    causal_ok = q_hi - 1 >= k_lo
    if kind == "causal":
        return causal_ok
    if kind == "sliding":
        # need q - k < window for some pair: min over tile of (q-k) is
        # q_lo - (k_hi-1); also q >= k possible.
        return jnp.logical_and(causal_ok, (q_hi - 1) - k_lo >= 0) & (
            (k_hi - 1) >= q_lo - window + 1
        )
    if kind == "chunked":
        return jnp.logical_and(causal_ok, q_lo // chunk <= (k_hi - 1) // chunk) & (
            (q_hi - 1) // chunk >= k_lo // chunk
        )
    raise ValueError(kind)


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bq, bk, scale, kind, window, chunk, q_offset, k_len,
):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = q_offset + q_idx * bq
    k_lo = kv_idx * bk

    @pl.when(
        _block_reachable(kind, window, chunk, q_lo, q_lo + bq, k_lo, k_lo + bk)
    )
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < k_len                 # keys past Sk are padding
        if kind != "bidirectional":
            mask &= q_pos >= k_pos
            if kind == "sliding":
                mask &= (q_pos - k_pos) < window
            elif kind == "chunked":
                mask &= (q_pos // chunk) == (k_pos // chunk)
        s = jnp.where(mask, s, NEG_INF)

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
        l = jnp.maximum(l_scr[...], 1e-30)   # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,      # (B, Hq, Sq, D)
    k: jax.Array,      # (B, Hkv, Sk, D)
    v: jax.Array,      # (B, Hkv, Sk, D)
    *,
    kind: str = "causal",
    window: int = 0,
    chunk: int = 0,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Pallas flash attention. GQA handled by repeating KV heads blockwise."""
    B, Hq, Sq, D = q.shape
    _, Hkv, k_len, _ = k.shape
    G = Hq // Hkv
    # lengths the blocks do not divide are padded up to a block multiple:
    # padded queries are cut from the output, padded keys masked out
    bq = min(block_q, Sq)
    bk = min(block_k, k_len)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, (-Sq) % bq), (0, 0)))
    k, v = (
        jnp.pad(t, ((0, 0), (0, 0), (0, (-k_len) % bk), (0, 0)))
        for t in (k, v)
    )
    Sq_out, Sq, Sk = Sq, q.shape[2], k.shape[2]
    scale = (D ** -0.5) if scale is None else scale

    # collapse (B, Hq) into one parallel grid axis; map each q-head block
    # to its kv head: h_kv = h_q // G.
    qf = q.reshape(B * Hq, Sq, D)
    grid = (B * Hq, Sq // bq, Sk // bk)

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        b = bh // Hq
        hkv = (bh % Hq) // G
        return (b * Hkv + hkv, j, 0)

    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel,
            bq=bq, bk=bk, scale=scale, kind=kind,
            window=window, chunk=chunk, q_offset=q_offset, k_len=k_len,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        name="flash_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, D)[:, :, :Sq_out]


def _prefill_kernel(
    q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bq, bk, scale, kind, window, chunk,
):
    """Chunked-prefill attention: causal within chunk, full vs prior cache.

    Same online-softmax loop as ``_fa_kernel``, but positions come from the
    prefetched ``qpos``/``kpos`` tensors instead of iota — the KV axis is
    the concatenation [prior cache slots ++ chunk keys], where cache slots
    carry a recovered absolute position (ring caches wrap, every batch row
    sits at its own offset) and ``kpos < 0`` marks holes (unwritten tail,
    padding past this row's ``new_lens``).
    """
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qp = qpos_ref[0, 0]                              # (bq,) int32
    kp = kpos_ref[0, 0]                              # (bk,) int32
    mask = (qp[:, None] >= kp[None, :]) & (kp[None, :] >= 0)
    if kind == "sliding":
        mask &= (qp[:, None] - kp[None, :]) < window
    elif kind == "chunked":
        mask &= (qp[:, None] // chunk) == (kp[None, :] // chunk)

    @pl.when(jnp.any(mask))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = jnp.where(mask, s, NEG_INF)
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
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_prefill(
    q: jax.Array,       # (B, Hq, Sq, D) chunk queries
    k: jax.Array,       # (B, Hkv, Sk, D) prior cache ++ chunk keys
    v: jax.Array,       # (B, Hkv, Sk, D)
    q_pos: jax.Array,   # (B, Sq) int32 absolute query positions
    k_pos: jax.Array,   # (B, Sk) int32 absolute key positions; < 0 = hole
    *,
    kind: str = "causal",
    window: int = 0,
    chunk: int = 0,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Pallas chunked-prefill attention (ref: ``ref.prefill_attention``).

    One HBM pass over the prior cache per chunk instead of one per token —
    the kernel-level half of the serve engine's batched prefill.  The KV
    axis is padded up to a block multiple with ``k_pos = -1`` holes, which
    the mask (and the fully-masked-block compute skip) eliminates.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    bq = min(block_q, Sq)
    if Sq % bq:
        bq = Sq
    bk = min(block_k, Sk)
    pad = (-Sk) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        Sk += pad

    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)
    grid = (B * Hq, Sq // bq, Sk // bk)

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        return ((bh // Hq) * Hkv + (bh % Hq) // G, j, 0)

    # positions ride as (B, 1, S): a (1, 1, block) tile's last two dims
    # are (whole axis, lane multiple), which the TPU tiling rule admits
    # for any batch — a (1, block) tile of (B, S) is refused for B > 1
    def qpos_map(bh, i, j):
        return (bh // Hq, 0, i)

    def kpos_map(bh, i, j):
        return (bh // Hq, 0, j)

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel,
            bq=bq, bk=bk, scale=scale, kind=kind, window=window, chunk=chunk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bq), qpos_map),
            pl.BlockSpec((1, 1, bk), kpos_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        name="flash_prefill",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        qf, kf, vf,
        q_pos.astype(jnp.int32)[:, None, :],
        k_pos.astype(jnp.int32)[:, None, :],
    )
    return out.reshape(B, Hq, Sq, D)


def vmem_footprint_bytes(bq: int, bk: int, d: int, itemsize: int = 2) -> int:
    """Predicted VMEM working set of one grid step (for tiling choices)."""
    tiles = (bq * d + 2 * bk * d) * itemsize      # q, k, v tiles
    scores = bq * bk * 4                          # f32 scores
    stats = (2 * bq + bq * d) * 4                 # m, l, acc
    out = bq * d * itemsize
    return tiles + scores + stats + out
