"""Mamba-2 SSD (state-space duality) chunked-scan kernel (Pallas, TPU).

The SSD algorithm (arXiv:2405.21060) is itself a data-movement argument of
the kind the paper makes: the same recurrence can be evaluated as a
sequential scan (latency-bound, no MXU) or as chunked quadratic blocks
(MXU-friendly, VMEM-resident tiles) plus a tiny inter-chunk state
recurrence.  This kernel implements the chunked form with the chunk loop as
the *sequential* grid axis, carrying the (P, N) state in VMEM scratch —
HBM traffic is exactly one read of x/dt/B/C and one write of y.

Grid: (batch, heads, chunks); chunks is ``arbitrary`` (sequential).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 64


def _ssd_kernel(
    x_ref,    # (1, 1, c, P)
    dt_ref,   # (1, 1, c, 1)
    a_ref,    # (H,) in SMEM
    b_ref,    # (1, c, N)
    c_ref,    # (1, c, N)
    y_ref,    # (1, 1, c, P)
    h_scr,    # (P, N) f32 state
    *, chunk,
):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)              # (c, P)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (c, 1)
    A = a_ref[pl.program_id(1)]                      # scalar
    Bm = b_ref[0].astype(jnp.float32)                # (c, N)
    Cm = c_ref[0].astype(jnp.float32)                # (c, N)

    a = A * dt                                       # (c, 1) log-decays
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = row >= col
    tri = mask.astype(jnp.float32)
    # inclusive cumsum as a column and as a row, both by the MXU, at
    # float32 precision: the sums feed exp(), and a bf16-rounded input
    # would put its rounding into every decay
    cum = jax.lax.dot_general(                       # (c, 1)
        tri, a, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    cum_row = jax.lax.dot_general(                   # (1, c)
        a, tri, (((0,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    L = jnp.where(mask, jnp.exp(cum - cum_row), 0.0)  # (c, c)

    G = jax.lax.dot_general(                         # C_i . B_j
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    M = G * L                                        # (c, c)
    xdt = x * dt                                     # (c, P)
    y_intra = jax.lax.dot_general(
        M, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (c, P)

    # inter-chunk: y_inter[i] = exp(cum_i) * C_i . h_prev
    h_prev = h_scr[...]                              # (P, N)
    ch = jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                # (c, P)
    y = y_intra + jnp.exp(cum) * ch
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h = exp(cum_end) * h_prev + sum_j decay_to_end_j dt_j x_j B_j
    cum_end = jnp.sum(a, axis=0, keepdims=True)      # (1, 1)
    decay_to_end = jnp.exp(cum_end - cum)            # (c, 1)
    sx = xdt * decay_to_end                          # (c, P)
    add = jax.lax.dot_general(                       # (P, N)
        sx, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h_scr[...] = h_prev * jnp.exp(cum_end) + add


def ssd_scan(
    x: jax.Array,     # (B, T, H, P)
    dt: jax.Array,    # (B, T, H)
    A: jax.Array,     # (H,)
    Bmat: jax.Array,  # (B, T, N)
    Cmat: jax.Array,  # (B, T, N)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    Bsz, T, H, P = x.shape
    N = Bmat.shape[-1]
    c = min(chunk, T)
    assert T % c == 0, (T, c)
    nchunks = T // c
    grid = (Bsz, H, nchunks)

    # heads-major: every tile's last two dims are (chunk, whole axis),
    # which the TPU tiling rule admits; a (1, P) head slice of a
    # (T, H, P) array is refused
    xh = x.transpose(0, 2, 1, 3)                     # (B, H, T, P)
    dth = dt.transpose(0, 2, 1)[..., None]           # (B, H, T, 1)
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, c, P), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, c, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, c, N), lambda b, h, i: (b, i, 0)),
            pl.BlockSpec((1, c, N), lambda b, h, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, c, P), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        name="ssd_scan",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xh, dth, A.astype(jnp.float32), Bmat, Cmat)
    return y.transpose(0, 2, 1, 3)
