"""Paper Fig. 14: internode bandwidth scaling with message size and the
number of injection streams.

Alps: one NIC per GH200, 4 per node — full node bandwidth needs 4 MPI
processes.  TPU analogue: per-chip DCN injection; a pod's inter-pod
bandwidth scales with how many chips participate in the cross-pod
collective.  Measured: psum over the 'pod' axis of a (2, n/2) mesh of the
devices present, in this process (>= 2 needed).  Analytic: alpha-beta model over message size for
1/2/4 streams."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.common import emit, multi_device_count
from repro.core import Link, get_active_system
from repro.launch.mesh import make_mesh_for


def measure_pod_reduce() -> None:
    """Cross-'pod' psum over a (2, n/2) mesh of the devices present."""
    n_dev = multi_device_count()
    mesh = make_mesh_for((2, n_dev // 2), ("pod", "data"))
    g = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, "pod"), mesh=mesh,
        in_specs=P(None), out_specs=P(None), check_vma=False,
    ))
    for log2 in (16, 20, 24):
        n = 2 ** log2 // 4
        x = jax.device_put(
            jnp.ones((n,), jnp.float32), NamedSharding(mesh, P())
        )
        out = g(x)
        jax.block_until_ready(out)
        reps = 10
        t0 = time.perf_counter()
        for _ in range(reps):
            out = g(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps
        emit(f"measured_podreduce[{n*4}B]", dt * 1e6,
             f"{(n * 4) / dt / 1e9:.2f}GB/s")


def main() -> None:
    measure_pod_reduce()
    sys = get_active_system()
    beta = sys.link_bandwidth(Link.DCN)
    alpha = sys.link_latency(Link.DCN)
    for streams in (1, 2, 4):
        for size in (2**12, 2**16, 2**20, 2**24, 2**28):
            t = alpha + size / (beta * streams)
            emit(
                f"analytic_internode[{streams}streams,{size}B]",
                t * 1e6,
                f"{size / t / 1e9:.2f}GB/s",
            )


if __name__ == "__main__":
    main()
