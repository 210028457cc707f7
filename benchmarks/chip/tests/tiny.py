"""A tiny copy of the benchmark's tree for CPU tests: the same traffic
kinds and metric readers on small configurations."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]

STEP_CONFIG = {
    "name": "tiny-step", "source": "test", "layout": "array_per_step",
    "fields": [
        {"name": "t", "maker": "temperature", "shape": [3, 40960],
         "chunks": [1, 16384], "codec": "field16"},
        {"name": "z", "maker": "geopotential", "shape": [3, 40960],
         "chunks": [3, 8000], "codec": "field8"}],
    "distinct_steps": 2, "keep_steps": 2,
    "fdb": {"backend": "daos", "io_parallelism": 2},
    "cache_bytes": 1 << 20,
    "check": {"err_ratio_limit": {"field16": 16.0, "field8": 5.0}},
    "reduced": []}

TIME_CONFIG = {
    "name": "tiny-time", "source": "test", "layout": "time_axis",
    "fields": [
        {"name": "u", "maker": "wind_u", "shape": [3, 9, 40],
         "chunks": [3, 9, 40], "codec": "field16"},
        {"name": "q", "maker": "humidity", "shape": [3, 9, 40],
         "chunks": [3, 9, 40], "codec": "field16"}],
    "distinct_steps": 2, "keep_steps": 2,
    "fdb": {"backend": "daos", "io_parallelism": 2},
    "cache_bytes": 1 << 16,
    "check": {"err_ratio_limit": {"field16": 16.0}},
    "reduced": []}

KINDS = [
    {"field": "t", "axes": [{"frac": 1.0}, {"frac": 0.3}]},
    {"field": "z", "axes": [{"index": True}, {"frac": 1.0}]},
    {"field": "t", "axes": [{"frac": 1.0, "step": 2},
                            {"frac": 1.0, "step": 97}]}]

TRAFFIC = {
    "archive": {"prefill_steps": 1, "writer": {"loop": "closed"},
                "check": {"stored_chunks": 4}},
    "pgen": {"prefill_steps": 1, "writer": {"interval_s": 0.3},
             "readers": {"clients": 2, "kinds": KINDS},
             "check": {"stored_chunks": 1, "keep_p": 0.5,
                       "keep_per_client": 2}},
    "train": {"loaders": {"clients": 2},
              "check": {"keep_p": 1.0, "keep_per_client": 1}},
    "hot": {"prefill_steps": 1,
            "readers": {"clients": 2, "kinds": [
                {"field": "t", "axes": [{"frac": 1.0}, {"len": 1000,
                                         "within": [16384, 32768]}]},
                {"field": "z", "weight": 0.1,
                 "axes": [{"index": True}, {"len": 100}]}]},
            "check": {"keep_p": 0.5, "keep_per_client": 1}},
}

CELLS = {"archive": "tiny-step", "pgen": "tiny-step", "train": "tiny-time",
         "hot": "tiny-step"}


def make_tree(base: Path, end_to_end=None, per_layer=None) -> Path:
    """Write ``base/BENCHMARK.json`` and the benchmark's directory with the
    tiny configurations and traffic, and the real generators and metric
    readers."""
    chip = base / "benchmarks" / "chip"
    for sub in ("configs", "traffic"):
        (chip / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("generators", "metrics"):
        shutil.copytree(CHIP / sub, chip / sub, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for cfg in (STEP_CONFIG, TIME_CONFIG):
        (chip / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    for name, traffic in TRAFFIC.items():
        (chip / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    real = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    real_cells = {"archive": "o1280-archive", "pgen": "o1280-pgen",
                  "train": "era5-train-read", "hot": "o1280-pgen-hot"}

    def rename(entries):
        out = []
        for m in entries:
            m = dict(m)
            if "workloads" in m:
                m["workloads"] = [k for k, v in real_cells.items()
                                  if v in m["workloads"]]
            out.append(m)
        return out

    bench = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 1,
        "configs": [{"name": c, "source": "test",
                     "file": f"benchmarks/chip/configs/{c}.json",
                     "reduced": [], "why": "test"}
                    for c in ("tiny-step", "tiny-time")],
        "workloads": [{"name": k, "config": v, "traffic": k, "chips": 1,
                       "why": "test"} for k, v in CELLS.items()],
        "end_to_end": rename(end_to_end or real["end_to_end"]),
        "per_layer": rename(per_layer or real["per_layer"]),
    }
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    return base
