"""Production meshes.

Axis semantics (see DESIGN.md §4): ``model`` = tensor/expert parallelism
(highest collective volume — lives on the fastest ICI axis), ``data`` =
data/FSDP parallelism, ``pod`` = the DCN axis (gradient all-reduce once per
step, or pipeline handoffs).  Functions, not module constants — importing
this module never touches jax device state.

**Donor axes** (the paper's peer-memory experiments, Figs. 15-17): an axis
named :data:`DONOR_AXIS` (``"donor"``, laid on ICI) or
:data:`REMOTE_DONOR_AXIS` (``"donor_pod"``, laid on DCN) marks a group of
chips whose memory is donated to the computation.  No sharding rule maps a
logical tensor axis onto a donor axis, so ordinary tensors are replicated
over it; only :mod:`repro.core.placement`'s peer/remote tiers shard across
it, putting their bytes a link-hop away in the donor slices' pools —
which is what makes ``kv_peer_hbm``/``weights_peer_hbm``/``opt_peer_host``
/``kv_remote_hbm`` executable instead of analysis-only.  Build one with
:func:`make_donor_mesh`, or pass any shape containing the axis name to
:func:`make_mesh_for`.

All mesh construction goes through :func:`make_mesh_for`, which builds
``Auto`` axes: the sharding rules place tensors by constraint propagation,
not by explicit-axis typing (``jax.make_mesh`` defaults to ``Explicit``).
"""

from __future__ import annotations

import jax

from repro.core.placement import DONOR_AXIS, REMOTE_DONOR_AXIS  # noqa: F401


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_for(shape, axes)


def make_mesh_for(devices_shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh with ``Auto`` axes (tests, benchmarks, elastic
    rescale)."""
    return jax.make_mesh(
        devices_shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_donor_mesh(
    compute_shape: tuple[int, ...] = (1,),
    compute_axes: tuple[str, ...] = ("data",),
    donor_size: int = 2,
    *,
    remote: bool = False,
):
    """Compute mesh with a leading donor axis of ``donor_size`` slices.

    The donor axis is the ICI :data:`DONOR_AXIS` by default or the DCN
    :data:`REMOTE_DONOR_AXIS` with ``remote=True``; total devices used =
    ``donor_size * prod(compute_shape)``.  Slice 0 is 'the' local slice
    only by convention — peer-tier tensors are sharded across all slices,
    so every slice is simultaneously accessor and donor (the symmetric
    form of the paper's accessor/donor pairing).
    """
    axis = REMOTE_DONOR_AXIS if remote else DONOR_AXIS
    if donor_size < 2:
        raise ValueError(f"donor axis needs >= 2 slices, got {donor_size}")
    return make_mesh_for(
        (donor_size, *compute_shape), (axis, *compute_axes)
    )


def mesh_axes_dict(mesh) -> dict[str, int]:
    return dict(mesh.shape)
