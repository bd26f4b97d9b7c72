"""Tiled GEMM (Pallas, TPU): the paper's GEMM study at the VMEM tier.

Paper Figs. 15-16 show one GEMM flipping between compute- and memory-bound
purely as a function of where its operands live.  On a TPU chip the same
experiment exists one tier down: the BlockSpec *is* the placement decision.
With (bm, bn, bk) tiles, HBM traffic per output tile is
``bm·bk + bk·bn`` reads amortized over ``2·bm·bn·bk`` FLOPs — arithmetic
intensity grows with tile size until the working set
``(bm·bk + bk·bn + bm·bn·2)`` no longer fits VMEM.  ``traffic_model``
exposes this analytically; bench_gemm sweeps it.

Grid: (M/bm, N/bn, K/bk), K sequential with an f32 VMEM accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _mm_kernel(a_ref, b_ref, o_ref, acc_scr, *, out_dtype):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k_idx == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_scr[...].astype(out_dtype)


def blocked_matmul(
    a: jax.Array,   # (M, K)
    b: jax.Array,   # (K, N)
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    out_dtype = out_dtype or a.dtype

    return pl.pallas_call(
        functools.partial(_mm_kernel, out_dtype=out_dtype),
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="blocked_matmul",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)


def traffic_model(
    M: int, N: int, K: int, bm: int, bn: int, bk: int, itemsize: int = 2
) -> dict[str, float]:
    """Analytic HBM traffic + VMEM footprint of the tiling.

    Every A tile is read N/bn times, every B tile M/bm times — the
    'how many times does each byte cross the bus' question is the paper's
    central one, answered for the on-chip datapath.
    """
    a_reads = M * K * (N // bn)
    b_reads = K * N * (M // bm)
    c_writes = M * N
    vmem = (bm * bk + bk * bn) * itemsize + bm * bn * 4 + bm * bn * itemsize
    flops = 2.0 * M * N * K
    traffic = (a_reads + b_reads + c_writes) * itemsize
    return {
        "hbm_bytes": float(traffic),
        "vmem_bytes": float(vmem),
        "flops": flops,
        "arithmetic_intensity": flops / traffic,
    }


def best_tiling(
    M: int, N: int, K: int,
    vmem_budget: int = 96 * 2**20,
    itemsize: int = 2,
    candidates=(128, 256, 512, 1024),
) -> tuple[int, int, int]:
    """Pick the tiling with max arithmetic intensity that fits VMEM."""
    best = None
    for bm in candidates:
        for bn in candidates:
            for bk in candidates:
                if M % bm or N % bn or K % bk:
                    continue
                t = traffic_model(M, N, K, bm, bn, bk, itemsize)
                if t["vmem_bytes"] > vmem_budget:
                    continue
                key = (t["arithmetic_intensity"], -t["vmem_bytes"])
                if best is None or key > best[0]:
                    best = (key, (bm, bn, bk))
    return best[1] if best else (min(128, M), min(128, N), min(128, K))
