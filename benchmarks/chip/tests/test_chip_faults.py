"""A whole run at smoke size on the CPU, past the harness's look for a
chip: sound, it is correct; with the timed path broken underneath, the
comparison turns it to not correct, once for each fault a served cell
can have."""

import json
import time
from pathlib import Path

import pytest

from benchmarks.chip import harness
from benchmarks.chip.cell import load_cell

ROOT = Path(__file__).resolve().parents[3]
SEED = 2**31 + 4242


def offline_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return load_cell(bench, "olmo-1b.offline-long").end_to_end


def run(cell):
    return harness.run(cell, SEED, 2.0, False, started=time.perf_counter(),
                       require_chip=False)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """A run turns JAX's persistent cache on; keep this process's tests
    off it, as they found it."""
    import jax
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was)


@pytest.fixture
def cell(tiny_cell):
    c = tiny_cell("olmo-1b")
    c.end_to_end = offline_metrics()
    return c


def test_sound_run_is_correct_and_well_formed(cell):
    line = run(cell)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    # every watched request's every dispatch was compared
    assert line["compared"]["dispatch_mismatch"]["value"] == 0
    assert line["compared"]["decode_rel_rms"]["value"] < 1e-3
    json.dumps(line, allow_nan=False)


def test_an_altered_token_is_not_correct(cell, monkeypatch):
    from repro.serve import Executor

    decode = Executor.decode
    vocab = cell.model["vocab_size"]

    def altered(self, state):
        tokens, stopped, state = decode(self, state)
        return (tokens + 1) % vocab, stopped, state

    monkeypatch.setattr(Executor, "decode", altered)
    line = run(cell)
    assert line["correct"] is False
    gap = line["compared"]["token_gap"]
    assert gap["value"] > gap["limit"]


def test_a_prefill_that_leaves_the_cache_unchanged_is_not_correct(
        cell, monkeypatch):
    from repro.serve import Executor

    def unchanged(self, new, table):
        for i, prompt in new:
            table.lengths[i] += len(prompt) - 1

    monkeypatch.setattr(Executor, "_chunked_prefill", unchanged)
    line = run(cell)
    assert line["correct"] is False
    for name in ("token_gap", "decode_rel_rms", "dispatch_mismatch"):
        c = line["compared"][name]
        assert c["value"] > c["limit"], name


def test_no_chip_no_result(cell):
    with pytest.raises(SystemExit, match="needs 1 TPU"):
        harness.run(cell, SEED, 1.0, False, started=time.perf_counter())
