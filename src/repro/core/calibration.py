"""Calibrate the hardware model from the repo's own microbenchmarks.

The paper's method is to *measure* every datapath and report the achieved
fraction of its bound; this module closes the loop by rewriting the
roofline constants themselves from those measurements.  ``calibrate()``
runs in-process versions of the ``bench_membw`` (HBM + PCIe read
sweeps), ``bench_pingpong`` (neighbor ``ppermute``) and
``bench_collectives`` (``psum``) kernels, fits ``t = latency +
nbytes/bandwidth`` per link (:func:`repro.core.membench.linear_fit`),
and derives a :class:`repro.core.hardware.SystemSpec` whose terms carry
``measured`` provenance via :meth:`SystemSpec.with_measurements`.

The result is a :class:`Calibration`: per-term spec-vs-measured values
plus a :class:`repro.core.replay.ReplayLog` that replays every sweep
point against the *calibrated* bounds — a self-consistency check whose
per-term relative error drives the CI drift gate
(:meth:`ReplayLog.gate`).  ``Calibration.save`` persists the whole thing
as ``calibration.json``; ``load_or_calibrate`` makes the file the cache.

On this CPU container every "link" is host DRAM, so measured terms land
far from the TPU spec sheet — which is the point: the planner then
prices placements for the machine it is actually on, and the divergence
itself is visible in the provenance report.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Mapping, Sequence

from repro.core.hardware import (
    CALIBRATED_TERMS,
    Link,
    MemoryTier,
    SystemSpec,
    get_active_system,
    set_active_system,
)
from repro.core.membench import Measurement, linear_fit, measure
from repro.core.replay import ReplayLog

__all__ = [
    "TermCalibration",
    "Calibration",
    "calibrate",
    "load_or_calibrate",
]

#: default buffer-size sweep (bytes): small enough for CI, spread enough
#: for the latency/bandwidth fit to separate its two terms
DEFAULT_SIZES: tuple[int, ...] = (2**18, 2**21, 2**24)

FORMAT_VERSION = 1


@dataclasses.dataclass(frozen=True)
class TermCalibration:
    """One constant's spec-vs-measured record."""

    term: str
    spec: float
    measured: float
    unit: str                 # "B/s" | "s"
    source: str               # which kernel produced it
    detail: str = ""          # free-form: fit quality, device count, ...

    @property
    def ratio(self) -> float:
        """measured / spec — how far the machine is from the sheet."""
        return self.measured / self.spec if self.spec else float("inf")

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj: Mapping) -> "TermCalibration":
        return cls(**{f.name: obj[f.name] for f in dataclasses.fields(cls)
                      if f.name in obj})


@dataclasses.dataclass
class Calibration:
    """A full calibration run: measured terms + replay validation."""

    backend: str
    num_devices: int
    created: str                                   # ISO timestamp
    terms: dict[str, TermCalibration] = dataclasses.field(
        default_factory=dict
    )
    replay: ReplayLog = dataclasses.field(default_factory=ReplayLog)

    def apply(self, system: SystemSpec | None = None) -> SystemSpec:
        """Derive a system with every measured term rewritten (provenance
        ``measured``)."""
        system = system if system is not None else get_active_system()
        if not self.terms:
            return system
        return system.with_measurements(
            **{t: c.measured for t, c in self.terms.items()}
        )

    def summary(self) -> str:
        lines = [
            f"calibration: backend={self.backend} devices={self.num_devices}"
            f" created={self.created}",
            f"{'term':<22} {'spec':>12} {'measured':>12} {'ratio':>7} "
            f"source",
        ]
        for term in sorted(self.terms):
            c = self.terms[term]
            lines.append(
                f"{term:<22} {_si(c.spec, c.unit):>12} "
                f"{_si(c.measured, c.unit):>12} {c.ratio:>6.2f}x {c.source}"
            )
        uncal = sorted(set(CALIBRATED_TERMS) - set(self.terms))
        if uncal:
            lines.append(f"(spec provenance kept for: {', '.join(uncal)})")
        return "\n".join(lines)

    # -- persistence ------------------------------------------------------
    def to_json(self) -> dict:
        return {
            "format_version": FORMAT_VERSION,
            "backend": self.backend,
            "num_devices": self.num_devices,
            "created": self.created,
            "terms": {t: c.to_json() for t, c in sorted(self.terms.items())},
            "provenance": {t: "measured" for t in sorted(self.terms)},
            "replay": self.replay.to_json(),
        }

    @classmethod
    def from_json(cls, obj: Mapping) -> "Calibration":
        version = obj.get("format_version", 0)
        if version > FORMAT_VERSION:
            raise ValueError(
                f"calibration.json format {version} is newer than this "
                f"code understands ({FORMAT_VERSION}); re-run calibrate()"
            )
        return cls(
            backend=obj.get("backend", "unknown"),
            num_devices=int(obj.get("num_devices", 0)),
            created=obj.get("created", ""),
            terms={
                t: TermCalibration.from_json(c)
                for t, c in obj.get("terms", {}).items()
            },
            replay=ReplayLog.from_json(obj.get("replay", {})),
        )

    def save(self, path: str | pathlib.Path) -> pathlib.Path:
        path = pathlib.Path(path)
        path.write_text(json.dumps(self.to_json(), indent=2) + "\n")
        return path

    @classmethod
    def load(cls, path: str | pathlib.Path) -> "Calibration":
        return cls.from_json(json.loads(pathlib.Path(path).read_text()))


def _si(v: float, unit: str) -> str:
    if unit == "B/s":
        return f"{v / 1e9:.2f}GB/s"
    if unit == "s":
        return f"{v * 1e6:.2f}us"
    return f"{v:.3g}{unit}"


# ---------------------------------------------------------------------------
# Measurement kernels (in-process analogues of benchmarks/bench_*.py)
# ---------------------------------------------------------------------------

def _sweep_read(kind: str | None, sizes: Sequence[int], repeats: int
                ) -> list[Measurement]:
    """bench_membw's read kernel: jit sum over a buffer placed in
    ``kind`` memory (``None`` -> the backend's default memory)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    read = jax.jit(lambda x: jnp.sum(x))
    out = []
    dev = jax.devices()[0]
    sharding = (SingleDeviceSharding(dev) if kind is None
                else SingleDeviceSharding(dev, memory_kind=kind))
    kind = kind or "device"
    for nbytes in sizes:
        x = jax.device_put(jnp.ones((nbytes // 4,), jnp.float32), sharding)
        out.append(measure(
            lambda x=x: read(x), name=f"read[{kind},{nbytes}]",
            nbytes=nbytes, repeats=repeats,
        ))
        del x
    return out


def _sweep_permute(axis_name: str, mesh_shape: tuple[int, ...],
                   axis_names: tuple[str, ...], sizes: Sequence[int],
                   repeats: int) -> list[Measurement]:
    """bench_pingpong's kernel at bulk sizes: one-hop ``ppermute`` over
    ``axis_name``, measuring per-chip shard bytes through one link."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh_for

    mesh = make_mesh_for(mesh_shape, axis_names)
    axis_size = dict(zip(axis_names, mesh_shape))[axis_name]
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    f = jax.jit(jax.shard_map(
        lambda v: jax.lax.ppermute(v, axis_name, perm),
        mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
    ))
    out = []
    for nbytes in sizes:
        # per-chip shard of `nbytes` -> global buffer of axis_size * nbytes
        x = jnp.ones((axis_size * (nbytes // 4),), jnp.float32)
        out.append(measure(
            lambda x=x: f(x), name=f"ppermute[{axis_name},{nbytes}]",
            nbytes=nbytes, repeats=repeats,
        ))
        del x
    return out


def _measure_psum(mesh_shape: tuple[int, ...], axis_names: tuple[str, ...],
                  axis_name: str, nbytes: int, repeats: int) -> Measurement:
    """bench_collectives' psum kernel: replay-only observation."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh_for

    mesh = make_mesh_for(mesh_shape, axis_names)
    f = jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, axis_name),
        mesh=mesh, in_specs=P(None), out_specs=P(None), check_vma=False,
    ))
    x = jnp.ones((nbytes // 4,), jnp.float32)
    return measure(
        lambda: f(x), name=f"psum[{axis_name},{nbytes}]",
        nbytes=nbytes, repeats=repeats,
    )


# ---------------------------------------------------------------------------
# calibrate(): run kernels -> fit terms -> replay against calibrated bounds
# ---------------------------------------------------------------------------

def calibrate(
    system: SystemSpec | None = None,
    *,
    sizes: Sequence[int] = DEFAULT_SIZES,
    repeats: int = 5,
    include_collectives: bool = True,
) -> Calibration:
    """Measure every reachable link and build a :class:`Calibration`.

    Kernels are gated on what the runtime exposes: PCIe terms need a
    distinct host memory space (:func:`repro.core.placement.
    host_available`), ICI terms need >= 2 devices, DCN terms >= 4 (a
    (2, n/2) ("pod", "model") mesh, the bench_collectives layout).
    Unreachable terms keep ``spec`` provenance — the report says so
    rather than inventing numbers.
    """
    import jax

    from repro.core.datapath import collective_bound, read_bound
    from repro.core.placement import host_available

    system = system if system is not None else get_active_system()
    devices = jax.devices()
    ndev = len(devices)
    cal = Calibration(
        backend=devices[0].platform,
        num_devices=ndev,
        created=time.strftime("%Y-%m-%dT%H:%M:%S"),
    )
    sweeps: dict[str, list[Measurement]] = {}

    def fit(term_bw: str, term_lat: str, source: str,
            ms: list[Measurement], detail: str) -> None:
        latency, bandwidth = linear_fit(ms)
        spec_bw = system.term_value(term_bw)
        spec_lat = system.term_value(term_lat)
        cal.terms[term_bw] = TermCalibration(
            term=term_bw, spec=spec_bw, measured=bandwidth,
            unit="B/s", source=source, detail=detail,
        )
        # a fit intercept of ~0 (bulk-dominated sweep) would erase the
        # latency term entirely; keep spec latency unless the fit
        # resolved something above the timer floor.
        if latency > 1e-7:
            cal.terms[term_lat] = TermCalibration(
                term=term_lat, spec=spec_lat, measured=latency,
                unit="s", source=source, detail=detail,
            )

    # 1. HBM bus: default-memory read sweep ("device" on TPU; the CPU
    # backend's only memory otherwise)
    ms = _sweep_read(None, sizes, repeats)
    sweeps["hbm_bandwidth"] = ms
    fit("hbm_bandwidth", "hbm_latency", "bench_membw.read[device]", ms,
        f"sizes={list(sizes)}")

    # 2. PCIe: pinned-host read sweep, only when a real host space exists
    if host_available():
        ms = _sweep_read("pinned_host", sizes, repeats)
        sweeps["pcie_bandwidth"] = ms
        fit("pcie_bandwidth", "pcie_latency",
            "bench_membw.read[pinned_host]", ms, f"sizes={list(sizes)}")

    # 3. ICI: one-hop ppermute sweep over a flat mesh
    if ndev >= 2:
        ms = _sweep_permute("x", (ndev,), ("x",), sizes, repeats)
        sweeps["ici_link_bandwidth"] = ms
        fit("ici_link_bandwidth", "ici_hop_latency",
            "bench_pingpong.ppermute", ms, f"devices={ndev}")

    # 4. DCN: ppermute over the 'pod' axis of the bench_collectives mesh
    if ndev >= 4:
        pod_mesh = (2, ndev // 2)
        ms = _sweep_permute("pod", pod_mesh, ("pod", "model"), sizes,
                            repeats)
        sweeps["dcn_bandwidth"] = ms
        fit("dcn_bandwidth", "dcn_latency", "bench_pingpong.ppermute[pod]",
            ms, f"mesh={pod_mesh}")

    calibrated = cal.apply(system)

    # Replay: every sweep point predicted under the *calibrated* bounds.
    bound_of = {
        "hbm_bandwidth": read_bound(MemoryTier.HBM, calibrated),
        "pcie_bandwidth": read_bound(MemoryTier.HOST, calibrated),
    }
    for term, ms in sweeps.items():
        if term in bound_of:
            b = bound_of[term]
            for m in ms:
                cal.replay.record(
                    term, m.name, b.time(m.nbytes), m.mean_s,
                    nbytes=int(m.nbytes), limiting_link=str(b.limiting_link),
                    source="calibrate",
                )
        else:
            link = Link.ICI if term == "ici_link_bandwidth" else Link.DCN
            lat = calibrated.link_latency(link)
            bw = calibrated.link_bandwidth(link)
            for m in ms:
                cal.replay.record(
                    term, m.name, lat + m.nbytes / bw, m.mean_s,
                    nbytes=int(m.nbytes), limiting_link=str(link),
                    source="calibrate",
                )

    # psum observations validate the ring-collective pricing end to end
    # (replay-only: they rewrite no constant).
    if include_collectives and ndev >= 2:
        axis_names = ("x",)
        mesh_shape = (ndev,)
        m = _measure_psum(mesh_shape, axis_names, "x", max(sizes), repeats)
        bw = collective_bound(ndev, Link.ICI, "all_reduce", calibrated)
        cal.replay.record(
            "all_reduce", m.name,
            calibrated.link_latency(Link.ICI) + m.nbytes / bw, m.mean_s,
            nbytes=int(m.nbytes), limiting_link=str(Link.ICI),
            source="calibrate",
        )

    return cal


def load_or_calibrate(
    path: str | pathlib.Path | None,
    *,
    activate: bool = False,
    system: SystemSpec | None = None,
    **kwargs,
) -> Calibration:
    """Load ``calibration.json`` if it exists, else calibrate and save.

    ``path=None`` always calibrates (nothing persisted).  With
    ``activate=True`` the calibrated system is installed process-wide via
    :func:`repro.core.hardware.set_active_system` — what the launchers'
    ``--calibration`` flag does.
    """
    if path is not None and pathlib.Path(path).exists():
        cal = Calibration.load(path)
    else:
        cal = calibrate(system, **kwargs)
        if path is not None:
            cal.save(path)
    if activate:
        set_active_system(cal.apply(system))
    return cal
