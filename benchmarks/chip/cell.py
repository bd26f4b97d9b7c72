"""A cell of ``BENCHMARK.json`` and the files it names.

``configs/<config>.json`` holds the model as it is run (its sizes under
the source's own keys), the server's settings and the correctness limit;
``traffic/<traffic>.json`` the mix; ``metrics/<metric>.py`` the reader of
each per-layer metric, or ``metrics/<base>.py`` for a metric named
``<base>.<cells>`` (one reader serves the split of a quantity by the
end-to-end metric it moves).  Everything is found by name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def serve(self) -> dict:
        return self.config["serve"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str, home: Path = HERE) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    config = load_json(home / "configs" / f"{w['config']}.json")
    if config["chips"] != w["chips"]:
        raise ValueError(f"{workload}: config {w['config']} is for "
                         f"{config['chips']} chips, the cell asks {w['chips']}")
    return Cell(
        name=workload,
        chips=w["chips"],
        config=config,
        traffic=load_json(home / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


def metric_reader(name: str, home: Path = HERE):
    """``read(run) -> float | None`` of ``metrics/<name>.py``, else of
    ``metrics/<base>.py`` with ``base`` the name before its first dot."""
    path = home / "metrics" / f"{name}.py"
    if not path.is_file():
        path = home / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"chip_metric_{name.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def arch_config(config: dict):
    """The program's ``ArchConfig`` with the sizes the file states; any
    size the program would run differently is an error."""
    from repro.configs import get_config

    m = config["model"]
    base = get_config(config["arch"])
    cfg = dataclasses.replace(
        base,
        n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"],
        d_ff=m["intermediate_size"],
        vocab=m["vocab_size"],
        norm=m["norm"],
        tie_embeddings=m["tie_word_embeddings"],
        dtype=m["dtype"],
        act={"silu": "silu"}[m["hidden_act"]],
        attention=dataclasses.replace(
            base.attention,
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"],
            d_head=m["head_dim"],
            rope_theta=float(m["rope_theta"]),
        ),
    )
    if cfg.layer_pattern != "F" or cfg.family != "dense":
        raise NotImplementedError(
            f"{cfg.name}: the harness serves dense full-attention decoders"
        )
    return cfg
