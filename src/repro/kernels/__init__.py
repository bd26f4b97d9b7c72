"""Pallas TPU kernels for the data-movement hot spots.

Per-kernel modules hold ``pl.pallas_call`` + BlockSpec tiling; ``ref.py``
holds the pure-jnp oracles; ``ops.py`` is the public jit-able API, which
runs the compiled kernels on TPU and the oracles on every other platform.
The kernels are validated in interpret mode on CPU (tests/test_kernels)
and compiled for a described TPU v5e (tests/test_tpu_compile).
"""

from repro.kernels import ops  # noqa: F401
from repro.kernels.ref import NEG_INF  # noqa: F401
