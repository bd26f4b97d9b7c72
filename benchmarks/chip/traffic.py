"""The one traffic generator: every mix is a data file it reads.

A mix (``traffic/<name>.json``) states its arrival ``kind`` and the
distributions of prompt and output lengths.  Sizes and arrival gaps are
drawn once from the mix's own ``shape_seed``, so every run seed serves
the same multiset of work; the run seed draws the token ids and, unless
the mix says ``"order": "fixed"``, the order of sizes and of gaps.  Runs
of different seeds then differ in what they compute, not in how much.

Kinds:

- ``offline``: ``requests`` requests, all due at t = 0.
- ``poisson``: open loop at ``rate_per_s``; round(rate x seconds)
  requests whose exponential gaps are scaled to end inside the window.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""

    prompt: np.ndarray        # (L,) int32 token ids
    max_new_tokens: int
    due_s: float              # seconds after the window opens


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, spec["value"], np.float64)
    elif dist == "uniform":
        out = rng.integers(spec["lo"], spec["hi"] + 1, n).astype(np.float64)
    elif dist == "lognormal":
        out = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("lo", 1), spec.get("hi", np.inf)
    return np.clip(np.rint(out), lo, hi).astype(np.int64)


def request_count(mix: dict, seconds: float) -> int:
    if mix["kind"] == "offline":
        return int(mix["requests"])
    if mix["kind"] == "poisson":
        return max(int(round(mix["rate_per_s"] * seconds)), 1)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def arrival_gaps(mix: dict, n: int, seconds: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Gaps between arrivals, before the run seed orders them."""
    if mix["kind"] == "offline":
        return np.zeros(n)
    gaps = rng.exponential(1.0 / mix["rate_per_s"], n)
    # n arrivals of a Poisson process conditioned to fall in the window
    # end, on average, one gap before its close
    return gaps * (seconds * n / (n + 1) / gaps.sum())


def generate(mix: dict, seconds: float, seed: int, vocab: int,
             rate_per_s: float | None = None) -> list[Planned]:
    """The planned requests of one run, sorted by due time."""
    if rate_per_s is not None:
        mix = dict(mix, rate_per_s=rate_per_s)
    n = request_count(mix, seconds)
    shape = np.random.default_rng(mix.get("shape_seed", 0))
    prompts = draw_lengths(shape, mix["prompt"], n)
    outputs = draw_lengths(shape, mix["output"], n)
    gaps = arrival_gaps(mix, n, seconds, shape)
    rng = np.random.default_rng(seed)
    if mix.get("order", "seeded") == "fixed":
        order = np.arange(n)
        due = np.cumsum(gaps)
    else:
        order = rng.permutation(n)
        due = np.cumsum(gaps[rng.permutation(n)])
    return [
        Planned(
            prompt=rng.integers(0, vocab, int(prompts[k])).astype(np.int32),
            max_new_tokens=int(outputs[k]),
            due_s=float(due[j]),
        )
        for j, k in enumerate(order)
    ]
