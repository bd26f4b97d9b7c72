"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A kind not in the table is an error:
a roofline against a guessed peak is no roofline."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud TPU v5e documentation",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add the "
            f"chip to benchmarks/chip/peaks.py (known: {sorted(PEAKS)})"
        ) from None
