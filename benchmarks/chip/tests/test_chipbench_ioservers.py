"""The I/O-server cell driven end to end on the CPU at a tiny size
(interpret mode): six variables written by three writer clients into
declared step arrays. It runs ``correct`` traced and untraced with the
metrics ``BENCHMARK.json`` lists for ``o1280-ioservers``; the control, an
altered answer and a writer's lost share make it not ``correct``."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from benchmarks.chip import run  # noqa: E402
from benchmarks.chip.spec import find_cell  # noqa: E402
from test_chipbench_cells import (  # noqa: E402,F401
    _alter_decode, no_compile_cache)

#: six variables on the step layout, written by three I/O-server clients
FULL_STEP_CONFIG = {
    "name": "tiny-full-step", "source": "test", "layout": "array_per_step",
    "writers": 3,
    "fields": [{"name": n, "maker": m, "shape": [3, 40960],
                "chunks": [1, 16384], "codec": c} for n, m, c in (
        ("z", "geopotential", "field8"), ("t", "temperature", "field16"),
        ("u", "wind_u", "field16"), ("v", "wind_v", "field16"),
        ("q", "humidity", "field16"), ("w", "vertical_velocity", "field16"))],
    "distinct_steps": 2, "keep_steps": 2,
    "fdb": {"backend": "daos", "io_parallelism": 2},
    "cache_bytes": 1 << 20,
    "check": {"err_ratio_limit": {"field16": 16.0, "field8": 5.0}},
    "reduced": []}

TRAFFIC = {"generator": "ioservers", "prefill_steps": 1,
           "writer": {"loop": "closed"}, "check": {"stored_chunks": 2}}

CELL, REAL_CELL = "ioservers", "o1280-ioservers"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The tiny tree, with the cell added as files and entries: the metrics
    that list ``o1280-ioservers`` in the real ``BENCHMARK.json`` list the
    tiny cell."""
    base = tiny.make_tree(tmp_path_factory.mktemp("bench"))
    chip = base / "benchmarks" / "chip"
    (chip / "configs" / "tiny-full-step.json").write_text(
        json.dumps(FULL_STEP_CONFIG))
    (chip / "traffic" / f"{CELL}.json").write_text(json.dumps(TRAFFIC))
    real = json.loads((tiny.CHIP.parents[1] / "BENCHMARK.json").read_text())
    bench = json.loads((base / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-full-step", "source": "test",
        "file": "benchmarks/chip/configs/tiny-full-step.json",
        "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-full-step",
                               "traffic": CELL, "chips": 1, "why": "test"})
    for key in ("end_to_end", "per_layer"):
        listed = {m["name"] for m in real[key]
                  if REAL_CELL in m.get("workloads", ())}
        for m in bench[key]:
            if m["name"] in listed:
                m["workloads"].append(CELL)
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    return base


def _run(tree, trace=False, control=False, seed=2 ** 31 + 5):
    return run.run_cell(find_cell(CELL, tree), seed=seed, seconds=0.6,
                        trace=trace, control=control)


def _listed(tree):
    """The end-to-end and per-layer metrics the tree's BENCHMARK.json lists
    for the cell, the per-layer ones with their sources."""
    bench = json.loads((tree / "BENCHMARK.json").read_text())

    def applies(m):
        return CELL in m.get("workloads", [CELL])
    return ({m["name"] for m in bench["end_to_end"] if applies(m)},
            {m["name"]: m["source"] for m in bench["per_layer"]
             if applies(m)})


@pytest.mark.parametrize("trace", [False, True])
def test_ioservers_runs_and_is_correct(tree, trace):
    e2e, layer = _listed(tree)
    assert e2e == {"setup_s", "write_gbps", "stored_ratio"}
    assert len(layer) == 5
    r = _run(tree, trace=trace)
    assert r["correct"], (r["checks"], r["window"]["first_error"])
    assert r["window"]["steps"] > 0 and r["failed"] == 0
    if trace:
        # no device plane on the CPU: the device metrics say nothing
        assert set(r["metrics"]) == {n for n, src in layer.items()
                                     if src != "device_trace"}
        for m in r["metrics"].values():
            assert m["value"] > 0
    else:
        assert set(r["metrics"]) == e2e
    # the seeded sample (2 chunks a field) and a chunk of each of the 3
    # writers' shares, of every kept step
    kept = min(r["window"]["steps"] + 1, 2)
    assert r["checks"]["checked"]["value"] == kept * (2 * 6 + 3)


def test_ioservers_control_is_not_correct(tree):
    r = _run(tree, control=True)
    assert not r["correct"]
    assert r["checks"]["err_field16"]["value"] > 100
    assert r["checks"]["err_field8"]["value"] > 10


def test_ioservers_altered_answer_is_not_correct(tree, monkeypatch):
    _alter_decode(monkeypatch)
    r = _run(tree)
    assert not r["correct"], r["checks"]


def test_lost_writer_share_is_not_correct(tree, monkeypatch):
    """Once the window opens, every archive of I/O server ``io1`` fails
    for good (``core/faults.py``): its share of each step is lost, and the
    run counts the steps as failed."""
    from benchmarks.chip import load
    from repro.core.faults import FaultInjector, PermanentStorageError
    from repro.data.pipeline import ChunkedFieldStore
    faults = FaultInjector(seed=1)
    real_writer, real_run = ChunkedFieldStore.writer, load.Traffic.run

    def writer(self, writer_id, **kw):
        if writer_id == "io1":
            self.fdb.store, self.fdb.catalogue = faults.wrap(
                self.fdb.store, self.fdb.catalogue)
        return real_writer(self, writer_id, **kw)

    def run_(self, seconds):
        faults.fail("store.archive", first=10 ** 9,
                    error=PermanentStorageError)
        return real_run(self, seconds)
    monkeypatch.setattr(ChunkedFieldStore, "writer", writer)
    monkeypatch.setattr(load.Traffic, "run", run_)
    r = _run(tree)
    assert not r["correct"]
    assert r["checks"]["failed"]["value"] >= r["window"]["steps"] >= 1
    assert "PermanentStorageError" in r["window"]["first_error"]
    assert faults.injected > 0
