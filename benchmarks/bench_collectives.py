"""Paper Figs. 18/19: collective scaling (all-reduce / all-gather) by
buffer size and by axis locality.

The paper's conclusion — Superchip locality matters more than memory type —
maps to axis choice: the same collective over the 'model' (ICI) vs 'pod'
(DCN) axis.  Measured: psum/all_gather over the devices present, in
this process (>= 2 needed).  Analytic: algorithmic-bandwidth scaling per axis."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, multi_device_count
from repro.core import collective_bound
from repro.core.hardware import Link
from repro.launch.mesh import make_mesh_for


def measure_collectives() -> None:
    """psum / all_gather over a (2, n/2) ('pod', 'model') mesh of the
    devices present."""
    n_dev = multi_device_count()
    mesh = make_mesh_for((2, n_dev // 2), ("pod", "model"))
    for op in ("psum", "all_gather"):
        for axis in ("model", "pod"):
            for log2 in (16, 22):
                n = 2 ** log2 // 4
                x = jnp.ones((n,), jnp.float32)
                if op == "psum":
                    body = lambda v: jax.lax.psum(v, axis)  # noqa: E731
                else:
                    body = lambda v: jax.lax.all_gather(v, axis)  # noqa: E731
                f = jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=P(None), out_specs=P(None),
                    check_vma=False,
                ))
                out = f(x)
                jax.block_until_ready(out)
                reps = 10
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = f(x)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / reps
                emit(f"measured_{op}[{axis},{n*4}B]", dt * 1e6,
                     f"{n*4/dt/1e9:.2f}GB/s")


def main() -> None:
    measure_collectives()
    # analytic: per-chip algorithmic bandwidth, ICI vs DCN axes
    for kind in ("all_reduce", "all_gather"):
        for axis, link, size in (
            ("model", Link.ICI, 16),
            ("data", Link.ICI, 16),
            ("pod", Link.DCN, 2),
        ):
            bw = collective_bound(size, link, kind)
            for nbytes in (2**20, 2**26, 2**32):
                t = nbytes / bw
                emit(
                    f"analytic_{kind}[{axis},{nbytes}B]",
                    t * 1e6,
                    f"{nbytes/t/1e9:.1f}GB/s algo-bw",
                )


if __name__ == "__main__":
    main()
