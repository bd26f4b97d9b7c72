"""Paper Fig. 13: ping-pong latency between processing units.

TPU adaptation (DESIGN.md §2.1): the CAS ping-pong becomes a
``collective_permute`` round trip between mesh neighbors at increasing
topological distance — the quantity preserved is which hop dominates
small-message latency.  Measured on the devices present, in this process
(>= 2 needed); analytic rows give the ICI-hop/DCN ladder of the hardware model."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, multi_device_count
from repro.core import Link, get_active_system
from repro.launch.mesh import make_mesh_for


def measure_pingpong() -> None:
    """ppermute round trips on a ring of the devices present."""
    n = multi_device_count()
    mesh = make_mesh_for((n,), ("x",))
    x = jnp.arange(float(n)).reshape(n, 1)
    # single permute per dispatch (the two-permute program deadlocks the
    # CPU backend's transfer manager); round trip = 2x one-way.
    for dist in (d for d in (1, 2, 4) if d < n):
        fwd = [(i, (i + dist) % n) for i in range(n)]
        f = jax.jit(jax.shard_map(
            lambda v, fwd=fwd: jax.lax.ppermute(v, "x", fwd),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
        ))
        out = f(x)
        jax.block_until_ready(out)
        reps = 30
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(out)
        jax.block_until_ready(out)
        dt = 2 * (time.perf_counter() - t0) / reps
        emit(f"pingpong[dist={dist}]", dt * 1e6, "round-trip(2x one-way)")


def main() -> None:
    measure_pingpong()
    # analytic ladder: 1 ICI hop, multi-hop, cross-pod (paper's G0/H0..H3)
    c = get_active_system()
    for hops in (1, 2, 4, 8):
        lat = 2 * hops * c.link_latency(Link.ICI)
        emit(f"analytic_pingpong[ici,{hops}hops]", lat * 1e6, "round-trip")
    lat = 2 * c.link_latency(Link.DCN)
    emit("analytic_pingpong[dcn]", lat * 1e6, "round-trip")
    lat = 2 * c.link_latency(Link.PCIE)
    emit("analytic_pingpong[host]", lat * 1e6, "round-trip")


if __name__ == "__main__":
    main()
