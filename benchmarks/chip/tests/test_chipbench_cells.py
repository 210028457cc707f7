"""Every traffic kind of the benchmark, driven end to end on the CPU at a
tiny size (interpret mode); the control and the faults that ``correct``
must catch; the refusal to run without a TPU; and a cell, configuration
and metric added as files only."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from benchmarks.chip import run  # noqa: E402
from benchmarks.chip.spec import find_cell  # noqa: E402


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    # a test process keeps JAX's compilation cache as it was
    import jax
    import repro.launch.cache
    monkeypatch.setattr(repro.launch.cache, "enable_compile_cache",
                        lambda: "")
    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def _run(tree, cell, trace=False, control=False, seed=2 ** 31 + 5):
    return run.run_cell(find_cell(cell, tree), seed=seed, seconds=0.6,
                        trace=trace, control=control)


@pytest.mark.parametrize("cell,metrics", [
    ("archive", {"setup_s", "write_gbps", "stored_ratio"}),
    ("pgen", {"setup_s", "read_p95_ms", "read_gbps"}),
    ("train", {"setup_s", "read_gbps", "stored_ratio"}),
    ("hot", {"setup_s", "read_p95_ms", "read_gbps"}),
])
def test_each_traffic_runs_and_is_correct(tree, cell, metrics):
    r = _run(tree, cell)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == metrics
    assert r["attempted"] > 0 and r["failed"] == 0
    for codec in ("err_field16", "err_field8"):
        assert r["checks"].get(codec, {"value": 0})["value"] <= 1.01
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    json.dumps(r, allow_nan=False)


def test_traced_run_reads_spans_and_counters(tree):
    r = _run(tree, "pgen", trace=True)
    assert r["correct"]
    assert {"decode_ms_per_req.pgen", "cache_hit_rate.pgen",
            "writer_late_ms.pgen"} <= set(r["metrics"])
    # no device plane on the CPU: the device metrics say nothing
    assert "device_idle_share.pgen" not in r["metrics"]
    assert r["window"]["codec"]["decode_elements"] > 0


def test_same_seed_same_fields():
    from benchmarks.chip import fields
    a = fields.make_fields(tiny.STEP_CONFIG, 2 ** 31 + 9)
    b = fields.make_fields(tiny.STEP_CONFIG, 2 ** 31 + 9)
    c = fields.make_fields(tiny.STEP_CONFIG, 2 ** 31 + 10)
    for k in a:
        assert np.array_equal(a[k], b[k])
        assert not np.array_equal(a[k], c[k])
        shape = {f["name"]: tuple(f["shape"])
                 for f in tiny.STEP_CONFIG["fields"]}[k]
        assert a[k].shape == (2,) + shape


@pytest.mark.parametrize("cell", ["archive", "train"])
def test_control_is_not_correct(tree, cell):
    r = _run(tree, cell, control=True)
    assert not r["correct"]
    assert r["checks"]["err_field16"]["value"] > 100
    if cell == "archive":
        assert r["checks"]["err_field8"]["value"] > 10


def _alter_decode(monkeypatch):
    from repro.tensorstore.codec import FieldQuantCodec
    real = FieldQuantCodec.decode_batch

    def decode_batch(self, datas, shapes, dtype):
        out = real(self, datas, shapes, dtype)
        out[0] = out[0] + np.float32(1e3)       # an answer altered
        return out
    monkeypatch.setattr(FieldQuantCodec, "decode_batch", decode_batch)


def _half_encode(monkeypatch):
    from repro.tensorstore.codec import FieldQuantCodec
    real = FieldQuantCodec.encode_batch

    def encode_batch(self, arrs):
        half = real(self, arrs[:(len(arrs) + 1) // 2])
        return (half * 2)[:len(arrs)]   # the rest reuse the first half's
    monkeypatch.setattr(FieldQuantCodec, "encode_batch", encode_batch)


def _drop_writes(monkeypatch):
    from repro.data.pipeline import ChunkedFieldStore
    real = ChunkedFieldStore.put_field
    seen = set()

    def put_field(self, name, values, chunks=None, codec=None):
        if name in seen or name.endswith("_s0") or "_s" not in name:
            return real(self, name, values, chunks, codec)
        seen.add(name)                  # every later step: state unchanged
    monkeypatch.setattr(ChunkedFieldStore, "put_field", put_field)


@pytest.mark.parametrize("cell,fault", [
    ("archive", _alter_decode), ("archive", _half_encode),
    ("archive", _drop_writes), ("pgen", _alter_decode),
    ("pgen", _drop_writes), ("train", _alter_decode),
    ("hot", _alter_decode)])
def test_faults_are_not_correct(tree, monkeypatch, cell, fault):
    fault(monkeypatch)
    r = _run(tree, cell)
    assert not r["correct"], r["checks"]


def test_cli_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", "o1280-archive", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_cell_config_and_metric_added_as_files(tmp_path):
    base = tiny.make_tree(tmp_path)
    chip = base / "benchmarks" / "chip"
    cfg = dict(tiny.STEP_CONFIG, name="tiny-wide")
    cfg["fields"] = [dict(cfg["fields"][0], chunks=[3, 8192])]
    (chip / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    (chip / "traffic" / "wide.json").write_text(json.dumps(
        {"prefill_steps": 1, "readers": {"clients": 1, "kinds": [
            {"field": "t", "axes": [{"index": True}, {"frac": 0.5}]}]},
         "check": {"keep_p": 1.0, "keep_per_client": 1}}))
    (chip / "metrics" / "reads_per_s.wide.py").write_text(
        "def read(ctx):\n    return len(ctx.done('read')) / ctx.window_s\n")
    bench = json.loads((base / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "wide", "config": "tiny-wide",
                               "traffic": "wide", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "reads_per_s.wide", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "read_p95_ms", "workloads": ["wide"]})
    for m in bench["end_to_end"]:
        if m["name"] == "read_p95_ms":
            m["workloads"].append("wide")
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = find_cell("wide", base)
    assert [m.name for m in cell.per_layer] == ["reads_per_s.wide"]
    r = run.run_cell(cell, seed=3, seconds=0.4, trace=True)
    assert r["correct"] and r["metrics"]["reads_per_s.wide"]["value"] > 0
    r = run.run_cell(cell, seed=3, seconds=0.4)
    assert set(r["metrics"]) == {"setup_s", "read_p95_ms"}
    with pytest.raises(KeyError):
        find_cell("no-such-cell", base)
