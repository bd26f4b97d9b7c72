"""Paper Figs. 15/16 + Table III: GEMM throughput vs dtype and operand
placement.

Measured: jnp.dot and the Pallas blocked matmul (interpret) on CPU-sized
matrices — validates the harness and the tiling sweep.  Analytic: the TPU
datapath verdict for the paper's experiment — per dtype (Table III) and
per operand placement (A/B resident in HBM vs streamed from host/peer),
reporting compute-vs-movement bound exactly like Fig. 15's colour map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core import MemoryTier, get_active_system, read_bound
from repro.core.membench import measure
from repro.kernels.blocked_matmul import best_tiling, blocked_matmul, traffic_model


def measured() -> None:
    N = 512
    for dtype in (jnp.float32, jnp.bfloat16):
        a = jax.random.normal(jax.random.PRNGKey(0), (N, N), dtype)
        b = jax.random.normal(jax.random.PRNGKey(1), (N, N), dtype)
        f = jax.jit(lambda a, b: jnp.dot(a, b))
        m = measure(
            lambda: f(a, b), name=f"xla_gemm[{N},{dtype.__name__}]",
            flops=2 * N**3, repeats=5,
        )
        emit(m.name, m.us_per_call, f"{m.tflops:.3f}TF/s")

    # Pallas tiling sweep: compiled on TPU, interpret mode elsewhere
    # (there it checks correctness + the traffic model, not speed)
    interpret = jax.default_backend() != "tpu"
    for bm, bn, bk in ((128, 128, 128), (256, 256, 256)):
        a = jax.random.normal(jax.random.PRNGKey(0), (512, 512), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(1), (512, 512), jnp.float32)
        m = measure(
            lambda: blocked_matmul(
                a, b, bm=bm, bn=bn, bk=bk, interpret=interpret
            ),
            name=f"pallas_gemm[512,bm{bm}]", flops=2 * 512**3, repeats=2,
        )
        t = traffic_model(512, 512, 512, bm, bn, bk, 4)
        emit(m.name, m.us_per_call,
             f"AI={t['arithmetic_intensity']:.1f}flops/B")


def analytic() -> None:
    c = get_active_system().chip
    N = 16384  # paper uses 4 GB square matrices; bf16 16k^2 = 512 MB each
    flops = 2.0 * N**3

    # Table III analogue: dtype sweep, HBM-resident
    for dtype, peak in c.peak_flops_by_dtype.items():
        itemsize = {"bfloat16": 2, "float32": 4, "int8": 1}[dtype]
        t = traffic_model(N, N, N, *best_tiling(N, N, N), itemsize=itemsize)
        t_mem = t["hbm_bytes"] / c.hbm_bandwidth
        t_cmp = flops / peak
        bound = "compute" if t_cmp > t_mem else "memory"
        emit(
            f"analytic_gemm[hbm,{dtype}]",
            max(t_cmp, t_mem) * 1e6,
            f"{flops/max(t_cmp,t_mem)/1e12:.1f}TF/s {bound}-bound",
        )

    # Fig. 15 analogue: operand placement sweep at bf16.  Reads dominate
    # (the paper's key asymmetry): destination placement never appears in
    # the bound because C is written once but A/B stream repeatedly.
    bm, bn, bk = best_tiling(N, N, N)
    reuse_a = N // bn   # times each A byte is re-read
    reuse_b = N // bm
    for pa in (MemoryTier.HBM, MemoryTier.HOST, MemoryTier.PEER_HBM):
        for pb in (MemoryTier.HBM, MemoryTier.HOST, MemoryTier.PEER_HBM):
            nbytes = N * N * 2
            t_a = nbytes * reuse_a / read_bound(pa).bandwidth
            t_b = nbytes * reuse_b / read_bound(pb).bandwidth
            t_cmp = flops / c.peak_bf16_flops
            t_total = max(t_cmp, t_a + t_b)
            bound = "compute" if t_cmp >= t_a + t_b else "memory"
            emit(
                f"analytic_gemm[A={pa},B={pb}]",
                t_total * 1e6,
                f"{flops/t_total/1e12:.1f}TF/s {bound}-bound",
            )


def main() -> None:
    measured()
    analytic()


if __name__ == "__main__":
    main()
