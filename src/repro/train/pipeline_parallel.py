"""GPipe pipeline parallelism over the ``pod`` axis (shard_map + ppermute).

For multi-pod meshes the ``pod`` axis crosses DCN — the weakest link in the
datapath model.  Pure DP on that axis all-reduces *every gradient byte*
across it each step; pipelining instead sends only **microbatch activations**
across the cut, shrinking DCN traffic by params/activations ratio (the
planner quantifies this; §Perf uses it as a lever).

Implementation: parameters are stacked over a leading ``stage`` dimension
sharded onto the pipeline axis; microbatches advance through stages with
``jax.lax.ppermute`` handoffs in a (n_micro + n_stages - 1)-tick schedule.
Differentiable (ppermute transposes to the reverse permute), validated
against the sequential model in tests.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,          # (stage_params, x) -> x
    axis_name: str,
    n_stages: int,
    n_micro: int,
):
    """Build the per-shard pipelined apply: (stacked_params, x_micro) -> y.

    Call inside ``shard_map`` with the stage dim of params sharded over
    ``axis_name`` and microbatches stacked on the leading dim of x.
    """

    def apply(params_local, x_micro):
        # params_local: (1, ...) this stage's slice; x_micro: (n_micro, B, ...)
        params_local = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis_name)
        n_ticks = n_micro + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        buf = jnp.zeros_like(x_micro[0])
        outs = jnp.zeros_like(x_micro)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(
                jnp.logical_and(stage == 0, t < n_micro),
                x_micro[mb_idx],
                buf,
            )
            y = stage_fn(params_local, inject)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = jnp.clip(t - n_stages + 1, 0, n_micro - 1)
            emit = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            outs = jax.lax.cond(
                emit,
                lambda o: o.at[out_idx].set(y),
                lambda o: o,
                outs,
            )
            buf = jax.lax.ppermute(y, axis_name, fwd_perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(n_ticks)
        )
        # only the last stage holds real outputs (others kept zeros):
        # one psum broadcasts them to every stage.
        return jax.lax.psum(outs, axis_name)

    return apply


def pipelined_forward(
    mesh: Mesh,
    stage_fn: Callable,
    stacked_params,              # leading dim = n_stages
    x_micro,                     # (n_micro, B_local, ...)
    axis_name: str = "pod",
):
    """shard_map wrapper: returns outputs gathered from the last stage.

    Non-pipeline mesh axes stay automatic (the body still runs TP/DP via
    pjit-style constraint propagation within each stage).
    """
    n_stages = mesh.shape[axis_name]
    n_micro = x_micro.shape[0]
    apply = pipeline_apply(stage_fn, axis_name, n_stages, n_micro)

    pspec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    # axis_names={pipe axis} keeps the other mesh axes automatic, so
    # stage bodies still run TP/DP via constraint propagation
    fn = jax.shard_map(
        apply,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        axis_names={axis_name},
        check_vma=False,
    )
    outs = fn(stacked_params, x_micro)
    return outs
