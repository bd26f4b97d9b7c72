"""Logical-axis sharding: one rule table instead of per-site PartitionSpecs.

Tensors are annotated with *logical* axis names ("batch", "heads", "d_ff",
"experts", ...) and a swappable rule table maps those to mesh axes.  This is
what makes sharding a hillclimbable config knob (§Perf): changing
``data→("pod","data")`` vs sequence-parallel vs FSDP is a rules swap, not a
model edit.

Divisibility-safety: a rule is silently dropped for a tensor dimension it
does not divide (e.g. kv_heads=2 over a 16-way model axis — Megatron-style
KV replication emerges naturally), and for axes absent from the active mesh
(e.g. "pod" on the single-pod mesh).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_log = logging.getLogger("repro.models.sharding")


#: default rules — the paper-faithful baseline: TP over the fast 'model'
#: axis, DP over 'data'+'pod', no FSDP, no sequence parallelism.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),
    "kv_seq": (),
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "d_ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_cap": (),
    "lora": (),
    "ssm_heads": ("model",),
    "d_inner": ("model",),
    "state": (),
    "conv": (),
    "layers": (),
    "fsdp": (),       # extra param-dim sharding axis; () = ZeRO off
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Mesh | None = None
        self.rules: dict[str, tuple[str, ...]] = dict(DEFAULT_RULES)
        self.kv_donor_axes: tuple[str, ...] = ()


_CTX = _Ctx()


@contextlib.contextmanager
def use_sharding(
    mesh: Mesh | None,
    rules: Mapping[str, Sequence[str]] | None = None,
    kv_donor_axes: Sequence[str] = (),
):
    """Install mesh + rules for trace-time constraint resolution.

    ``kv_donor_axes`` names the donor axes a peer/remote-tier KV cache is
    sharded over (``donor_axes_for`` of its tier), so the per-device
    attention kernels split the cache where it lives.
    """
    old = _CTX.mesh, _CTX.rules, _CTX.kv_donor_axes
    _CTX.mesh = mesh
    _CTX.kv_donor_axes = tuple(kv_donor_axes)
    if rules is not None:
        merged = dict(DEFAULT_RULES)
        merged.update({
            k: tuple(v) if isinstance(v, (list, tuple)) else v
            for k, v in rules.items()
        })
        _CTX.rules = merged
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules, _CTX.kv_donor_axes = old


def current_mesh() -> Mesh | None:
    return _CTX.mesh


def current_rules() -> dict[str, tuple[str, ...]]:
    return _CTX.rules


def current_kv_donor_axes() -> tuple[str, ...]:
    return _CTX.kv_donor_axes


def spec_for(
    shape: Sequence[int],
    axes: Sequence[str | None],
    mesh: Mesh | None = None,
    rules: Mapping[str, Sequence[str]] | None = None,
) -> P:
    """PartitionSpec for ``shape`` under the rules, divisibility-checked.

    ``rules`` is treated as an OVERLAY on DEFAULT_RULES — callers pass only
    the overrides (e.g. {"seq": ("model",)}) without losing the TP rules.
    """
    mesh = mesh or _CTX.mesh
    if rules is None:
        rules = _CTX.rules
    else:
        rules = {**DEFAULT_RULES, **{
            k: tuple(v) if isinstance(v, (list, tuple)) else v
            for k, v in rules.items()
        }}
    if mesh is None:
        return P()
    mesh_axes = dict(mesh.shape)
    used: set[str] = set()
    out = []
    for dim, name in zip(shape, axes):
        assigned: list[str] = []
        if name:
            size = 1
            for m in rules.get(name, ()):
                if m not in mesh_axes or m in used:
                    continue
                if dim % (size * mesh_axes[m]) != 0:
                    continue
                assigned.append(m)
                size *= mesh_axes[m]
        for m in assigned:
            used.add(m)
        out.append(tuple(assigned) if len(assigned) > 1 else (assigned[0] if assigned else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def grad_cast(x, dtype):
    """Identity whose COTANGENT is cast to ``dtype``.

    Placed at layer boundaries it clamps the backward chain to bf16, so
    the SPMD-inserted gradient all-reduces move half the bytes (bf16 grad
    sync — the industry default; baseline keeps f32 for paper-faithful
    apples-to-apples, §Perf measures the delta)."""
    return x


def _grad_cast_fwd(x, dtype):
    return x, None


def _grad_cast_bwd(dtype, _, g):
    return (g.astype(dtype),)


grad_cast.defvjp(_grad_cast_fwd, _grad_cast_bwd)


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """with_sharding_constraint under the active mesh (no-op without one)."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = spec_for(x.shape, axes, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Param:
    """Declarative parameter: shape + logical axes + init scale.

    Also used as the shaped placeholder for non-parameter state (caches,
    token inputs); ``dtype=None`` means "the model dtype".
    """

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"          # normal | zeros | ones
    scale: float | None = None    # None -> 1/sqrt(fan_in)
    dtype: str | None = None      # None -> model default

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_param(x) -> bool:
    return isinstance(x, Param)


def _init_one(p: Param, key, dtype):
    import jax.numpy as jnp

    dt = p.dtype or dtype
    if jnp.issubdtype(jnp.dtype(dt), jnp.integer):
        return jnp.zeros(p.shape, dt)
    if p.init == "zeros":
        return jnp.zeros(p.shape, dt)
    if p.init == "ones":
        return jnp.ones(p.shape, dt)
    scale = p.scale if p.scale is not None else (max(p.shape[0], 1)) ** -0.5
    return (jax.random.normal(key, p.shape, jnp.float32) * scale).astype(dt)


def materialize(defs, key, dtype) -> dict:
    """Param-def pytree -> initialized array pytree."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_param)
    keys = jax.random.split(key, len(leaves))
    arrs = [_init_one(p, k, dtype) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, arrs)


def defs_to_shapes(defs, dtype):
    """Param-def pytree -> ShapeDtypeStruct pytree (dry-run inputs)."""
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype or dtype),
        defs,
        is_leaf=is_param,
    )


def fsdp_extend(
    spec: P,
    shape: Sequence[int],
    mesh: Mesh,
    fsdp_axes: Sequence[str],
    logical_axes: Sequence[str | None] | None = None,
    prefer_stack: bool = False,
) -> P:
    """ZeRO-style extra sharding: place ``fsdp_axes`` on the first dim the
    base spec leaves unsharded and that they divide.  Used for parameters
    and optimizer state so per-chip residency scales with the data axis,
    not just TP (how 236B/400B archs fit 16 GiB HBM).

    The stacked ``layers`` dim is skipped when any other dim qualifies:
    sharding the scan dim makes every layer-slice a cross-data reshard and
    the AD transpose then emits full replicated f32 grad stacks (observed
    5.4 GiB/device); sharding a within-layer dim keeps slices sharded.
    ``prefer_stack=True`` flips that preference — donor-axis *streaming*
    placements want whole layers resident on the donor slices so each
    fetched window is one contiguous layer.
    """
    mesh_axes = dict(mesh.shape)
    fsdp_axes = [a for a in fsdp_axes if a in mesh_axes]
    if not fsdp_axes:
        return spec
    size = 1
    for a in fsdp_axes:
        size *= mesh_axes[a]
    used = set()
    for e in spec:
        if isinstance(e, tuple):
            used.update(e)
        elif e is not None:
            used.add(e)
    if any(a in used for a in fsdp_axes):
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))

    def assign(i: int) -> P:
        entries[i] = (
            tuple(fsdp_axes) if len(fsdp_axes) > 1 else fsdp_axes[0]
        )
        out = list(entries)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    candidates = [
        i for i, dim in enumerate(shape)
        if entries[i] is None and dim % size == 0 and dim >= size
    ]
    layer = [
        i for i in candidates
        if logical_axes and i < len(logical_axes)
        and logical_axes[i] == "layers"
    ]
    non_layer = [i for i in candidates if i not in layer]
    ordered = layer + non_layer if prefer_stack else non_layer + layer
    if ordered:
        return assign(ordered[0])
    return spec


def shard_defs(tree, defs, fsdp_axes: Sequence[str] = ()):
    """with_sharding_constraint each leaf to its def's logical spec (+FSDP).

    Used inside scan bodies on the per-layer param slice: the transpose of
    the constraint pins the *gradient* slice to the same sharding, which is
    what keeps ZeRO-3 grads sharded inside the backward loop.
    """
    mesh = _CTX.mesh
    if mesh is None:
        return tree

    def one(x, p: Param):
        spec = spec_for(p.shape, p.axes, mesh)
        if fsdp_axes:
            spec = fsdp_extend(spec, p.shape, mesh, fsdp_axes, p.axes)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec)
        )

    return jax.tree.map(one, tree, defs, is_leaf=lambda t: isinstance(t, Param))


def defs_to_specs(
    defs,
    mesh: Mesh,
    rules=None,
    memory_kind: str | None = None,
    fsdp_axes: Sequence[str] = (),
    donor_axes: Sequence[str] = (),
    donor_prefer_stack: bool = False,
):
    """Param-def pytree -> NamedSharding pytree.

    ``donor_axes`` extends every spec over a donor mesh axis (peer/remote
    tier realization — see :mod:`repro.core.placement`); it is applied
    after ``fsdp_axes`` so the two compose onto different dims.
    """
    def one(p: Param):
        spec = spec_for(p.shape, p.axes, mesh, rules)
        if fsdp_axes:
            spec = fsdp_extend(spec, p.shape, mesh, fsdp_axes, p.axes)
        if donor_axes:
            spec = donor_extend(
                spec, p.shape, mesh, donor_axes, p.axes,
                prefer_stack=donor_prefer_stack,
            )
        return NamedSharding(mesh, spec, memory_kind=memory_kind)

    return jax.tree.map(one, defs, is_leaf=is_param)


def spec_axes(spec: P) -> set[str]:
    """Every mesh-axis name a PartitionSpec references (tuples flattened)."""
    out: set[str] = set()
    for e in spec:
        out.update(e if isinstance(e, tuple) else [e])
    out.discard(None)
    return out


def donor_extend(
    spec: P,
    shape: Sequence[int],
    mesh: Mesh,
    donor_axes: Sequence[str],
    logical_axes: Sequence[str | None] | None = None,
    prefer_stack: bool = False,
) -> P:
    """Extend ``spec`` over the donor axes (peer/remote realization).

    Same mechanics as :func:`fsdp_extend`; ``prefer_stack=True`` targets
    the stacked ``layers`` dim first, so a ``Strategy.STREAM`` placement
    keeps whole layers on the donor slices and each streamed window is one
    contiguous layer (the planner's per-chunk ``copy_bound`` granularity).
    """
    return fsdp_extend(
        spec, shape, mesh, donor_axes, logical_axes, prefer_stack
    )


def _policy_specs(
    defs,
    mesh: Mesh,
    rules,
    role,
    policy,
    fsdp_axes: Sequence[str] = (),
):
    """NamedShardings realizing ``policy``'s placement of ``role``.

    The one entry point every realizer uses — via the
    :class:`repro.api.Runtime` facade (``Runtime.specs`` /
    ``Runtime.realize``); importing it directly as ``policy_specs`` is
    deprecated.  Resolves the role's memory kind on this backend and,
    for peer/remote tiers, the donor mesh axes that physically hold the
    bytes.  Raises :class:`repro.core.placement.DonorAxisError` if the
    mesh cannot realize the tier — the placement never silently degrades
    to local memory.
    """
    from repro.core.placement import Strategy, donor_axes_for

    pl = policy.placement(role)
    donor = donor_axes_for(mesh, pl.tier)
    specs = defs_to_specs(
        defs, mesh, rules,
        memory_kind=policy.memory_kind(role),
        fsdp_axes=fsdp_axes,
        donor_axes=donor,
        donor_prefer_stack=pl.strategy is Strategy.STREAM,
    )
    if donor:
        # Per-leaf divisibility can defeat the donor extension (no free
        # dim divisible by the axis size) — those leaves stay in LOCAL
        # memory while the planner charged them to the donor pool, so
        # make the degradation loud.
        local = sum(
            1 for s in jax.tree.leaves(specs)
            if not (spec_axes(s.spec) & set(donor))
        )
        if local:
            _log.warning(
                "policy %s/%s: %d of %d tensors could not be donor-"
                "sharded over %s (no divisible free dim) and stay in "
                "local memory — donor-pool capacity accounting is "
                "optimistic for them",
                policy.name, role.value, local,
                len(jax.tree.leaves(specs)), donor,
            )
    return specs


def __getattr__(name: str):
    # PEP 562 shim: `policy_specs` keeps resolving for external callers,
    # with a one-shot DeprecationWarning pointing at the facade.
    if name == "policy_specs":
        from repro.analysis.warnings_registry import warn_once

        warn_once(
            f"deprecated:{name}",
            "repro.models.sharding.policy_specs is deprecated; use "
            "repro.api.Runtime.specs / Runtime.realize instead",
            DeprecationWarning,
        )
        return _policy_specs
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def donation_compatible(policy, role) -> bool:
    """May a jitted step donate ``role``'s buffers under ``policy``?

    Donation is the zero-copy half of the decode hot path: XLA aliases the
    output cache onto the input cache's buffer, so the per-token update is
    in place instead of allocate+copy.  It is safe exactly for RESIDENT
    placements (local HBM, host-pinned, or donor-slice resident — the
    pinned ``out_shardings`` keep the aliased buffer in its tier).  A
    ``Strategy.STREAM`` placement must NOT donate: the jitted step computes
    on a staged copy while the far-tier resident buffer remains the source
    of truth for the next touch's migration, and donating it hands XLA the
    resident bytes as scratch mid-stream.
    """
    from repro.core.placement import Strategy

    return policy.placement(role).strategy is not Strategy.STREAM


def assert_donation_compatible(policy, role) -> None:
    """Raise if a realizer is about to donate a STREAM-placed role."""
    if not donation_compatible(policy, role):
        pl = policy.placement(role)
        raise ValueError(
            f"policy {policy.name!r} places {role.value} as "
            f"{pl.strategy.value} in {pl.tier}: streamed placements must "
            "keep their resident buffer undonated (the staging window is "
            "re-fetched from it every touch)"
        )


def stack_defs(defs, count: int, axis_name: str | None = "layers"):
    """Stack a layer's param defs ``count`` times (scan-over-layers).

    Preserves every per-def field — notably an explicit ``dtype`` (e.g.
    the SSM recurrent state pinned to float32): losing it here would
    materialize the stacked cache in the model dtype while the step
    function still emits the pinned one, a silent mismatch that breaks
    the decode step's donation alias.
    """
    return jax.tree.map(
        lambda p: Param(
            (count, *p.shape), (axis_name, *p.axes), p.init,
            # the fan-in is the layer's own, not the stack depth
            p.scale if p.scale is not None else max(p.shape[0], 1) ** -0.5,
            p.dtype,
        ),
        defs,
        is_leaf=is_param,
    )
