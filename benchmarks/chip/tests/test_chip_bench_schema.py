"""BENCHMARK.json and the files it names: every cell, config, mix and
per-layer reader is found by name, and the result line has its keys."""

import json
import re
from pathlib import Path

import pytest

from benchmarks.chip import traffic
from benchmarks.chip.cell import HERE, load_cell, metric_reader

ROOT = Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert (ROOT / BENCH["command"][1]).is_file()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_and_plan(cell):
    c = load_cell(BENCH, cell)
    planned = traffic.generate(c.traffic, BENCH["run_seconds"], 1,
                               c.model["vocab_size"])
    assert planned
    longest = max(len(p.prompt) + p.max_new_tokens for p in planned)
    assert longest < c.serve["max_len"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entries_match_their_files(config):
    f = json.loads((ROOT / config["file"]).read_text())
    assert f["source"] == config["source"]
    assert f["reduced"] == config["reduced"]
    assert {"model", "serve", "chips", "correct", "assumed"} <= set(f)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(metric_reader(metric["name"]))
    moves = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    # every cell that reports this metric reports what it moves
    for cell in metric.get("workloads", CELLS):
        assert cell in moves.get("workloads", CELLS)


def test_no_stray_files():
    readers = {p.name[:-3] for p in (HERE / "metrics").glob("*.py")}
    assert readers == {m["name"].split(".")[0] for m in BENCH["per_layer"]}
    mixes = {p.stem for p in (HERE / "traffic").glob("*.json")}
    assert mixes == {w["traffic"] for w in BENCH["workloads"]}
