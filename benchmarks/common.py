"""Shared benchmark infra: CSV emission + the multi-device guard.

Every benchmark prints ``name,us_per_call,derived`` rows (one per measured
or derived point).  Measured rows run on the available devices (CPU here);
``analytic`` rows evaluate the TPU datapath model — the two modes the
hardware-adaptation note in DESIGN.md §2.1 prescribes.
"""

from __future__ import annotations


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.2f},{derived}")


def emit_measurement(m, derived: str | None = None) -> None:
    print(m.csv(derived))


def multi_device_count(minimum: int = 2) -> int:
    """Number of devices present; raises with fewer than ``minimum``.

    The collective benches measure in this process, on the devices it
    sees: a single-device run has nothing to measure.  On a CPU host,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` provides eight
    devices.
    """
    import jax

    n = len(jax.devices())
    if n < minimum:
        raise RuntimeError(
            f"this benchmark needs >= {minimum} devices, found {n} "
            f"({jax.devices()[0].platform})"
        )
    return n
