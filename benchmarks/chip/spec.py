"""Find a cell's pieces by name: its configuration, traffic mix and metrics.

``BENCHMARK.json`` names each cell's configuration and traffic; the files
sit beside this module (``configs/``, ``traffic/``, ``metrics/``), so a
cell, a configuration or a per-layer metric is added by adding files and
an entry, and no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
#: root of the checkout: ``BENCHMARK.json`` and the program's ``src/``
CHECKOUT = HERE.parents[1]


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    #: per-layer metrics only: the reader, ``read(ctx) -> float | None``
    read: Optional[Callable] = None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_reader(path: Path) -> Callable:
    """The ``read`` function of one metric's module, loaded from its file
    (metric names hold dots, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(
        "metric_" + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def find_cell(name: str, base: Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``base/BENCHMARK.json`` with everything it
    names loaded: raises ``KeyError`` for an unknown cell and
    ``FileNotFoundError`` for a missing file."""
    bench = json.loads((base / "BENCHMARK.json").read_text())
    here = base / HERE.relative_to(CHECKOUT)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs: Dict[str, dict] = {c["name"]: c for c in bench["configs"]}
    config = json.loads((base / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((here / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, name)]
    layer = [Metric(m["name"], m["unit"],
                    load_reader(here / "metrics" / f"{m['name']}.py"))
             for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer)
