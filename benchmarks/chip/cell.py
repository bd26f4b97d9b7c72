"""A cell of ``BENCHMARK.json`` and the files it names.

``configs/<config>.json`` holds the model as it is run (its sizes under
the source's own keys), its ``family``, the server's settings and the
correctness limit; ``families/<family>.py`` what depends on the kind of
model (the program's config, the needed work, the reference and the
weights' scale: ``families/dense.py`` says what it gives);
``traffic/<traffic>.json`` the mix; ``metrics/<metric>.py`` the reader of
each per-layer metric, or ``metrics/<base>.py`` for a metric named
``<base>.<cells>`` (one reader serves the split of a quantity by the
end-to-end metric it moves).  Everything is found by name.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    family: ModuleType      # families/<config's family>.py
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
        family=load_family(config["family"], home),
        traffic=load_json(home / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


@functools.cache
def _module(path: Path, name: str) -> ModuleType:
    """The module in the file ``path``, loaded once per process."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(family: str, home: Path = HERE) -> ModuleType:
    """``families/<family>.py``."""
    path = home / "families" / f"{family}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no family module {path} for family "
                                f"{family!r}")
    return _module(path, f"chip_family_{family}")


def metric_reader(name: str, home: Path = HERE):
    """``read(run) -> float | None`` of ``metrics/<name>.py``, else of
    ``metrics/<base>.py`` with ``base`` the name before its first dot."""
    path = home / "metrics" / f"{name}.py"
    if not path.is_file():
        path = home / "metrics" / f"{name.split('.')[0]}.py"
    return _module(path, f"chip_metric_{path.stem}").read
