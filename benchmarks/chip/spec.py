"""Find a cell's pieces by name: its configuration, traffic mix, generator
and metrics.

``BENCHMARK.json`` names each cell's configuration and traffic; the files
sit beside this module (``configs/``, ``traffic/``, ``generators/``,
``metrics/``).  A traffic file may name the generator module that drives
it (``"generator": "<name>"``, ``generators/<name>.py``; ``fields`` where
it names none).  So a cell, a configuration, a per-layer metric or a
whole deployment with its own store and clients is added by adding files
and an entry, and no code here or in ``run.py`` changes.

A generator module exposes the four names of :data:`GENERATOR_NAMES`,
which is all that ``run.py`` calls:

* ``make_data(config, seed) -> {name: jax.Array}``: the cell's data, made
  on the device from the seed;
* ``Store(config, tracer)``: the deployment under test, with ``close()``;
* ``Traffic(spec, store, host, seed)``: the clients of the traffic file
  over the data copied to the host, with ``setup()``, ``run(seconds) ->
  (t0, t1)``, ``read_back()``, ``collect() -> [Answer]``,
  ``stored_ratio()`` and the attributes ``work`` (``[Work]``, whose kinds
  ``step``, ``read`` and ``sample`` the end-to-end metrics count),
  ``unfinished``, ``unreadable`` and ``first_error``;
* ``References(store, host, bits_of=None)``: the plain reference,
  ``refs[answer.array].select(answer.sel) -> (truth, reference)`` and
  ``refs.codec(answer.array) -> str``, which names the limit an answer is
  held to; ``bits_of`` maps declared bits to the control's.

``Answer`` and ``Work`` are :mod:`.load`'s.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
#: root of the checkout: ``BENCHMARK.json`` and the program's ``src/``
CHECKOUT = HERE.parents[1]
#: the generator of a traffic file that names none
DEFAULT_GENERATOR = "fields"
GENERATOR_NAMES = ("make_data", "Store", "Traffic", "References")


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
    #: the module that makes the data and drives the traffic
    generator: ModuleType


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_module(path: Path, prefix: str) -> ModuleType:
    """The module of one file, loaded by its path (metric names hold dots,
    so they are not importable by name); ``FileNotFoundError`` where the
    file is missing."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(path: Path) -> Callable:
    """The ``read`` function of one metric's module."""
    return load_module(path, "metric_").read


def load_generator(path: Path) -> ModuleType:
    """One generator module, which must expose :data:`GENERATOR_NAMES`."""
    module = load_module(path, "generator_")
    missing = [n for n in GENERATOR_NAMES if not hasattr(module, n)]
    if missing:
        raise AttributeError(f"generator {path} lacks {missing}")
    return module


def find_cell(name: str, base: Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``base/BENCHMARK.json`` with everything it
    names loaded: raises ``KeyError`` for an unknown cell and
    ``FileNotFoundError`` for a missing file, a generator's among them."""
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
    generator = load_generator(
        here / "generators"
        / f"{traffic.get('generator', DEFAULT_GENERATOR)}.py")
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer,
                generator)
