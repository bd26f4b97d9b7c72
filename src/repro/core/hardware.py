"""TPU hardware model: the single source of truth for roofline constants.

The paper characterizes the Quad GH200 node of Alps by enumerating its
processing units, physical memories, and interconnects (paper Fig. 1) and
deriving a theoretical bound for every datapath (paper Fig. 3).  This module
is the TPU v5e analogue: a declarative description of the chip, the host
link, the ICI torus, and the inter-pod DCN, consumed by
:mod:`repro.core.datapath` and :mod:`repro.core.roofline`.

All bandwidth numbers are bytes/second, latencies in seconds, capacities in
bytes.  Values marked ``# task-spec`` are the constants prescribed for the
roofline analysis; the others are public v5e-class figures used only for
secondary analyses (latency plots, VMEM tiling checks) and clearly separable.

Provenance
----------

The paper's whole method is *measuring* each datapath and reporting the
achieved fraction of the bound — a planner priced off spec-sheet numbers
alone is exactly the "assumed placement" trap §IV warns against.  Every
calibratable term therefore carries a provenance tag:

* ``spec``     — the declarative constant below (the default);
* ``measured`` — rewritten from a microbenchmark via
  :meth:`SystemSpec.with_measurements` (see
  :mod:`repro.core.calibration`);
* ``override`` — pinned by hand via :meth:`SystemSpec.with_overrides`.

Consumers resolve their system through :func:`get_active_system` (or an
explicitly passed ``system=``); the spec-sheet baseline stays available
as the module's default system and is what every process starts with.
"""

from __future__ import annotations

import dataclasses
import enum
import math
from typing import Mapping


class MemoryTier(str, enum.Enum):
    """Physical memory pools a tensor can live in, from the chip's view.

    Mirrors the paper's {HBM, DDR, HBM-p, DDR-p} axis (Figs. 5, 7, 9),
    adapted to the TPU memory system plus the on-chip VMEM tier.
    """

    VMEM = "vmem"            # on-chip scratch (Pallas BlockSpec target)
    HBM = "hbm"              # local device HBM
    HOST = "host"            # this chip's host DRAM (pinned_host)
    PEER_HBM = "hbm_p"       # another chip's HBM, same pod (via ICI)
    PEER_HOST = "host_p"     # another host's DRAM, same pod (PCIe+ICI+PCIe)
    REMOTE_HBM = "hbm_r"     # a chip's HBM in another pod (via DCN)

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return self.value


class Link(str, enum.Enum):
    """Interconnects, the paper's 'datapath segments'."""

    HBM_BUS = "hbm_bus"      # HBM <-> chip
    VMEM_BUS = "vmem_bus"    # VMEM <-> compute units
    PCIE = "pcie"            # host DRAM <-> chip
    ICI = "ici"              # chip <-> neighbor chip, per link
    DCN = "dcn"              # pod <-> pod, per chip

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return self.value


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """One TPU chip."""

    name: str = "tpu-v5e"
    peak_bf16_flops: float = 197e12          # task-spec: 197 TFLOP/s bf16
    hbm_bandwidth: float = 819e9             # task-spec: 819 GB/s
    hbm_capacity: float = 16 * 2**30         # 16 GiB (v5e-class)
    # Per-chip share of the host's DRAM (v5e-class hosts pair ~512 GiB of
    # DDR with 8 chips) — the planner's second capacity pool, mirroring the
    # paper's 480 GiB LPDDR per Grace (vs 96 GiB HBM per Hopper).
    host_dram_capacity: float = 64 * 2**30
    vmem_capacity: float = 128 * 2**20       # ~128 MiB VMEM (v5e-class)
    vmem_bandwidth: float = 11.4e12          # derived: keeps 8x8x128 MXU fed
    ici_link_bandwidth: float = 50e9         # task-spec: ~50 GB/s/link ICI
    ici_links_per_axis: int = 1              # links used per hop of a collective
    pcie_bandwidth: float = 32e9             # PCIe Gen4 x16-class host link
    dcn_bandwidth: float = 25e9              # per-chip inter-pod bandwidth
    # Latency terms (seconds) for the latency benchmarks (paper Figs. 11-13).
    hbm_latency: float = 700e-9
    vmem_latency: float = 30e-9
    pcie_latency: float = 2.0e-6
    ici_hop_latency: float = 1.0e-6
    dcn_latency: float = 10.0e-6
    # MXU tile: matmul dims should be multiples of this for full utilization.
    mxu_dim: int = 128
    # Peak flops by dtype (GEMM study, paper Table III analogue).
    peak_flops_by_dtype: Mapping[str, float] = dataclasses.field(
        default_factory=lambda: {
            "bfloat16": 197e12,
            "float32": 98.5e12,   # fp32 runs at half MXU rate on v5e-class
            "int8": 394e12,
        }
    )


@dataclasses.dataclass(frozen=True)
class PodSpec:
    """A pod slice: chips arranged in a 2D ICI torus (v5e-style 16x16)."""

    chip: ChipSpec = dataclasses.field(default_factory=ChipSpec)
    mesh_shape: tuple[int, ...] = (16, 16)
    torus_wraparound: bool = True

    @property
    def num_chips(self) -> int:
        return math.prod(self.mesh_shape)

    def ici_hops(self, a: tuple[int, ...], b: tuple[int, ...]) -> int:
        """Manhattan hop distance between two chips on the (wrapped) torus."""
        hops = 0
        for ax, (i, j) in enumerate(zip(a, b)):
            d = abs(i - j)
            if self.torus_wraparound:
                d = min(d, self.mesh_shape[ax] - d)
            hops += d
        return hops

    def bisection_bandwidth(self) -> float:
        """All-links bisection bandwidth of the pod (for sanity checks)."""
        # Cut the torus along its largest axis: 2 * (other-axes product)
        # links cross the cut (x2 for wraparound).
        longest = max(self.mesh_shape)
        cross = self.num_chips // longest
        wrap = 2 if self.torus_wraparound else 1
        return cross * wrap * self.chip.ici_link_bandwidth


#: provenance values a calibratable term may carry
PROVENANCES = ("spec", "measured", "override")

#: Calibratable terms: name -> the :class:`ChipSpec` field it rewrites.
#: These are exactly the bandwidth/latency/peak constants the datapath
#: bounds are built from — the terms :mod:`repro.core.calibration`
#: measures and :mod:`repro.core.replay` validates.
CALIBRATED_TERMS: dict[str, str] = {
    "peak_bf16_flops": "peak_bf16_flops",
    "hbm_bandwidth": "hbm_bandwidth",
    "vmem_bandwidth": "vmem_bandwidth",
    "pcie_bandwidth": "pcie_bandwidth",
    "ici_link_bandwidth": "ici_link_bandwidth",
    "dcn_bandwidth": "dcn_bandwidth",
    "hbm_latency": "hbm_latency",
    "vmem_latency": "vmem_latency",
    "pcie_latency": "pcie_latency",
    "ici_hop_latency": "ici_hop_latency",
    "dcn_latency": "dcn_latency",
}


@dataclasses.dataclass(frozen=True)
class SystemSpec:
    """The full target: ``num_pods`` pods joined by DCN.

    The production configuration for this repo is 2 pods x 256 chips
    (the multi-pod dry-run mesh); ``num_pods`` scales to thousands of
    nodes for planner what-ifs.

    ``provenance`` maps each :data:`CALIBRATED_TERMS` name to
    ``spec | measured | override`` (absent -> ``spec``).  Instances are
    immutable: :meth:`with_measurements` / :meth:`with_overrides` derive
    a new spec with the terms rewritten and tagged.
    """

    pod: PodSpec = dataclasses.field(default_factory=PodSpec)
    num_pods: int = 2
    provenance: Mapping[str, str] = dataclasses.field(default_factory=dict)

    @property
    def num_chips(self) -> int:
        return self.pod.num_chips * self.num_pods

    @property
    def chip(self) -> ChipSpec:
        return self.pod.chip

    def link_bandwidth(self, link: Link) -> float:
        c = self.chip
        return {
            Link.HBM_BUS: c.hbm_bandwidth,
            Link.VMEM_BUS: c.vmem_bandwidth,
            Link.PCIE: c.pcie_bandwidth,
            Link.ICI: c.ici_link_bandwidth * c.ici_links_per_axis,
            Link.DCN: c.dcn_bandwidth,
        }[link]

    def link_latency(self, link: Link) -> float:
        c = self.chip
        return {
            Link.HBM_BUS: c.hbm_latency,
            Link.VMEM_BUS: c.vmem_latency,
            Link.PCIE: c.pcie_latency,
            Link.ICI: c.ici_hop_latency,
            Link.DCN: c.dcn_latency,
        }[link]

    # -- calibration surface ----------------------------------------------
    def term_value(self, term: str) -> float:
        """Current value of a calibratable term."""
        return getattr(self.chip, _term_field(term))

    def provenance_of(self, term: str) -> str:
        """``spec | measured | override`` for ``term`` (spec when never
        rewritten)."""
        _term_field(term)  # validate
        return self.provenance.get(term, "spec")

    def _derive(self, provenance: str, terms: Mapping[str, float]
                ) -> "SystemSpec":
        if provenance not in PROVENANCES:
            raise ValueError(
                f"unknown provenance {provenance!r}; one of {PROVENANCES}"
            )
        chip_updates = {}
        for term, value in terms.items():
            field = _term_field(term)
            value = float(value)
            if not value > 0.0:
                raise ValueError(
                    f"calibrated term {term} must be > 0, got {value!r}"
                )
            chip_updates[field] = value
        new_chip = dataclasses.replace(self.chip, **chip_updates)
        new_pod = dataclasses.replace(self.pod, chip=new_chip)
        new_prov = dict(self.provenance)
        new_prov.update({t: provenance for t in terms})
        return dataclasses.replace(self, pod=new_pod, provenance=new_prov)

    def with_measurements(self, **terms: float) -> "SystemSpec":
        """A new spec with ``terms`` rewritten from measurements and
        tagged ``measured`` — the derivation :func:`repro.core.
        calibration.calibrate` applies after running the membw/pingpong/
        collective kernels."""
        return self._derive("measured", terms)

    def with_overrides(self, **terms: float) -> "SystemSpec":
        """A new spec with ``terms`` pinned by hand (``override``)."""
        return self._derive("override", terms)

    def describe_terms(self) -> dict[str, dict]:
        """Per-term ``{value, provenance}`` — what ``calibration.json``
        records for every constant the scheduler acts on."""
        return {
            term: {
                "value": self.term_value(term),
                "provenance": self.provenance_of(term),
            }
            for term in CALIBRATED_TERMS
        }


def _term_field(term: str) -> str:
    try:
        return CALIBRATED_TERMS[term]
    except KeyError:
        raise KeyError(
            f"unknown calibratable term {term!r}; "
            f"one of {sorted(CALIBRATED_TERMS)}"
        ) from None


#: Spec-sheet baseline system (every term provenance ``spec``).
DEFAULT_SYSTEM = SystemSpec()

#: Spec sheets by ``device_kind`` as JAX reports it.  A TPU kind missing
#: here is an error, never priced off another chip's sheet.
SYSTEMS_BY_DEVICE_KIND: dict[str, SystemSpec] = {
    "TPU v5 lite": DEFAULT_SYSTEM,      # TPU v5e
}

#: The process-wide system consumers resolve through get_active_system();
#: None until first asked, then the attached device's sheet.
_ACTIVE_SYSTEM: SystemSpec | None = None


def system_for_device(device) -> SystemSpec:
    """The spec sheet of ``device``: by ``device_kind`` on TPU (an unknown
    kind raises ``ValueError``).  Other platforms — the CPU the tests run
    on — stand in for the v5e this repository targets, so they price
    against its sheet."""
    if device.platform != "tpu":
        return DEFAULT_SYSTEM
    try:
        return SYSTEMS_BY_DEVICE_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no SystemSpec for TPU device_kind {device.device_kind!r}; "
            f"known kinds: {sorted(SYSTEMS_BY_DEVICE_KIND)}"
        ) from None


def get_active_system() -> SystemSpec:
    """The system every pricing path uses when no explicit ``system=`` is
    passed: the attached device's spec sheet (:func:`system_for_device`)
    until :func:`set_active_system` installs a calibrated one (see
    :meth:`repro.api.Runtime.calibrate` and the launchers'
    ``--calibration`` flag)."""
    global _ACTIVE_SYSTEM
    if _ACTIVE_SYSTEM is None:
        import jax

        _ACTIVE_SYSTEM = system_for_device(jax.devices()[0])
    return _ACTIVE_SYSTEM


def set_active_system(system: SystemSpec) -> SystemSpec:
    """Install ``system`` as the process-wide default; returns the
    previous one (restore it in tests)."""
    global _ACTIVE_SYSTEM
    if not isinstance(system, SystemSpec):
        raise TypeError(f"expected SystemSpec, got {type(system).__name__}")
    prev = get_active_system()
    _ACTIVE_SYSTEM = system
    return prev


#: Mesh-axis -> link map for the production meshes (see launch/mesh.py).
#: 'model' and 'data' are intra-pod ICI axes; 'pod' crosses DCN; the
#: 'donor'/'donor_pod' memory-donor axes (core/placement.py) ride ICI and
#: DCN respectively.  This is the paper's "locality beats memory type"
#: lesson (Fig. 19) as data: the axis you put a collective on decides its
#: link, and therefore its bound.
AXIS_LINK: dict[str, Link] = {
    "model": Link.ICI,
    "data": Link.ICI,
    "pod": Link.DCN,
    "donor": Link.ICI,
    "donor_pod": Link.DCN,
}

def link_for_axis(axis: str, *, strict: bool = False) -> Link:
    """The physical link a mesh axis runs over.

    Unknown axes used to default silently to ICI — which priced the
    ``donor_pod`` DCN axis at ICI bandwidth.  Now ``strict=True`` raises
    ``KeyError`` and the default warns once per axis name before falling
    back to ICI, so a mispriced collective is never silent.
    """
    try:
        return AXIS_LINK[axis]
    except KeyError:
        if strict:
            raise KeyError(
                f"mesh axis {axis!r} has no AXIS_LINK entry; known axes: "
                f"{sorted(AXIS_LINK)} — register it so collectives on it "
                "are priced at the right link"
            ) from None
        from repro.analysis.warnings_registry import warn_once

        warn_once(
            f"axis_link:{axis}",
            f"mesh axis {axis!r} has no AXIS_LINK entry; pricing its "
            "collectives at ICI bandwidth (add it to "
            "repro.core.hardware.AXIS_LINK if it crosses another link)",
        )
        return Link.ICI


def axis_bandwidth(
    axis: str, system: SystemSpec | None = None, *, strict: bool = False
) -> float:
    """Per-chip bandwidth available to a collective running on ``axis``."""
    system = system if system is not None else get_active_system()
    return system.link_bandwidth(link_for_axis(axis, strict=strict))
