"""Every traffic kind of the benchmark, driven end to end on the CPU at a
tiny size (interpret mode); the control and the faults that ``correct``
must catch; the refusal to run without a TPU; a cell, configuration and
metric added as files only; and a generator added as files only, with the
default generator the same as the one a traffic file names."""
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
    ("pgen", {"setup_s", "read_p95_ms"}),
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
            "writer_late_ms.pgen", "read_gbps.pgen"} <= set(r["metrics"])
    assert r["metrics"]["read_gbps.pgen"]["value"] > 0
    # no device plane on the CPU: the device metrics say nothing
    assert "device_idle_share.pgen" not in r["metrics"]
    assert r["window"]["codec"]["decode_elements"] > 0


def test_pgen_read_gbps_reader():
    from benchmarks.chip.context import Context
    from benchmarks.chip.load import Work
    from benchmarks.chip.spec import load_reader
    read = load_reader(
        Path(run.__file__).parent / "metrics" / "read_gbps.pgen.py")
    work = [Work("read", 0.0, 0.0, 1.0, 3 * 10 ** 9),
            Work("read", 0.0, 0.0, 1.0, 10 ** 9, error="lost"),
            Work("step", 0.0, 0.0, 1.0, 5 * 10 ** 9)]
    ctx = Context(trace=None, spans=[], counters={}, codec={}, work=work,
                  window_s=2.0, peaks={})
    assert read(ctx) == 1.5
    ctx.work = work[1:]
    assert read(ctx) is None


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


#: A generator that is not shipped: seeded arrays archived whole, as raw
#: bytes, through ``repro.core.FDB`` under the checkpoint schema; one
#: closed-loop writer archives every array of the next step and flushes,
#: one closed-loop reader retrieves a random array of the newest step.
BLOBS = '''
import threading
import time
import traceback
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.fields import key_from_seed
from benchmarks.chip.load import Answer, Work


def make_data(config, seed):
    shape = (config["distinct_steps"], config["arrays"], config["size"])
    make = jax.jit(lambda key: jax.random.normal(key, shape, jnp.float32))
    return {"w": make(key_from_seed(seed))}


class Store:
    def __init__(self, config, tracer=None):
        from repro.core import FDB, FDBConfig
        fdb = FDBConfig(backend="daos", schema="ckpt")
        self.producer = FDB(fdb, tracer=tracer)
        self.consumer = FDB(fdb, tracer=tracer)

    @staticmethod
    def ident(step, i):
        return {"run": "r", "kind": "state", "step": step, "host": "h0",
                "tensor": i, "shard": 0}

    def close(self):
        self.producer.close()
        self.consumer.close()


class Traffic:
    def __init__(self, spec, store, host, seed):
        self.spec, self.store, self.values = spec, store, host["w"]
        self.rng = np.random.default_rng([int(seed), 1])
        self.committed, self.work, self.answers = [], [], []
        self.unfinished = self.unreadable = 0
        self.first_error = None
        self._lock = threading.Lock()

    def write_step(self, k):
        rows = self.values[k % len(self.values)]
        for i, row in enumerate(rows):
            self.store.producer.archive(self.store.ident(k, i), row.tobytes())
        self.store.producer.flush()
        with self._lock:
            self.committed.append(k)
        return rows.nbytes

    def read(self, step, i):
        handle = self.store.consumer.retrieve(self.store.ident(step, i))
        return np.frombuffer(handle.read(), np.float32)

    def _answer(self, step, i, out):
        self.answers.append(Answer((step % len(self.values), i), (), out))

    def setup(self):
        self.write_step(0)
        self.read(0, 0)

    def _do(self, w, fn):
        try:
            out = fn()
        except Exception as exc:
            w.error, out = repr(exc), None
            self.first_error = self.first_error or traceback.format_exc()
        w.done = time.perf_counter()
        with self._lock:
            self.work.append(w)
        return out

    def _writer(self, deadline):
        k = 1
        while time.perf_counter() < deadline:
            w = Work("step", time.perf_counter(), time.perf_counter())
            w.nbytes = self._do(w, lambda: self.write_step(k)) or 0
            k += 1

    def _reader(self, deadline):
        while time.perf_counter() < deadline:
            with self._lock:
                step = self.committed[-1]
            i = int(self.rng.integers(self.values.shape[1]))
            w = Work("read", time.perf_counter(), time.perf_counter())
            out = self._do(w, lambda: self.read(step, i))
            if out is not None:
                w.nbytes = out.nbytes
                if len(self.answers) < self.spec["keep"]:
                    self._answer(step, i, out)

    def run(self, seconds):
        t0 = time.perf_counter()
        threads = [threading.Thread(target=f, args=(t0 + seconds,))
                   for f in (self._writer, self._reader)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return t0, max(w.done for w in self.work)

    def read_back(self):
        step = self.committed[-1]
        for i in range(self.values.shape[1]):
            self._answer(step, i, self.read(step, i))

    def collect(self):
        return self.answers

    def stored_ratio(self):
        stored = sum(loc.length for _ident, loc in
                     self.store.producer.list({"run": "r", "kind": "state"}))
        return stored / (len(self.committed) * self.values[0].nbytes)


class References:
    def __init__(self, store, host, bits_of=None):
        self.values = host["w"]

    def codec(self, key):
        return "raw"

    def __getitem__(self, key):
        values = self.values[key[0]][key[1]]
        return SimpleNamespace(select=lambda sel: (values[sel], values[sel]))
'''


def _blobs_tree(base: Path) -> Path:
    """The tiny tree with a ``blobs`` cell whose generator, configuration,
    traffic and per-layer metric are files added to it."""
    tiny.make_tree(base)
    chip = base / "benchmarks" / "chip"
    (chip / "generators" / "blobs.py").write_text(BLOBS)
    (chip / "configs" / "tiny-blobs.json").write_text(json.dumps(
        {"name": "tiny-blobs", "distinct_steps": 2, "arrays": 4,
         "size": 4096, "check": {"err_ratio_limit": {"raw": 0}}}))
    (chip / "traffic" / "blobs.json").write_text(json.dumps(
        {"generator": "blobs", "keep": 4}))
    (chip / "metrics" / "steps_per_s.blobs.py").write_text(
        "def read(ctx):\n    return len(ctx.done('step')) / ctx.window_s\n")
    bench = json.loads((base / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-blobs", "source": "test",
                             "file": "benchmarks/chip/configs/tiny-blobs.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "blobs", "config": "tiny-blobs",
                               "traffic": "blobs", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "steps_per_s.blobs", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "write_gbps", "workloads": ["blobs"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("write_gbps", "read_p95_ms", "read_gbps"):
            m["workloads"].append("blobs")
    (base / "BENCHMARK.json").write_text(json.dumps(bench))
    return base


def _no_field_store(monkeypatch):
    from repro.data.pipeline import ChunkedFieldStore

    def refuse(*_a, **_kw):
        raise AssertionError("the blobs cell built a ChunkedFieldStore")
    monkeypatch.setattr(ChunkedFieldStore, "__init__", refuse)


def test_generator_added_as_files(tmp_path, monkeypatch):
    base = _blobs_tree(tmp_path)
    _no_field_store(monkeypatch)
    cell = find_cell("blobs", base)
    assert cell.generator.__file__.endswith("blobs.py")
    e2e = {"setup_s", "write_gbps", "read_p95_ms", "read_gbps"}
    assert {m.name for m in cell.end_to_end} == e2e
    assert [m.name for m in cell.per_layer] == ["steps_per_s.blobs"]
    r = run.run_cell(cell, seed=2 ** 31 + 33, seconds=0.4)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == e2e
    assert r["checks"]["err_raw"] == {"value": 0.0, "limit": 0}
    assert r["checks"]["checked"]["value"] >= 4
    assert r["window"]["steps"] > 0 and r["window"]["reads"] > 0
    assert r["window"]["stored_ratio"] == 1.0
    r = run.run_cell(cell, seed=2 ** 31 + 34, seconds=0.4, trace=True)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"steps_per_s.blobs"}
    assert r["metrics"]["steps_per_s.blobs"]["value"] > 0


def test_generator_added_as_files_catches_an_altered_answer(
        tmp_path, monkeypatch):
    from repro.core.handle import MultiHandle
    base = _blobs_tree(tmp_path)
    real = MultiHandle.read

    def read(self):
        data = bytearray(real(self))
        data[1] ^= 0x40                 # an answer altered
        return bytes(data)
    monkeypatch.setattr(MultiHandle, "read", read)
    r = run.run_cell(find_cell("blobs", base), seed=2 ** 31 + 35,
                     seconds=0.4)
    assert not r["correct"]
    assert r["checks"]["err_raw"]["value"] > 0


def test_missing_generator_file_is_refused(tmp_path):
    base = _blobs_tree(tmp_path)
    chip = base / "benchmarks" / "chip"
    (chip / "generators" / "blobs.py").unlink()
    with pytest.raises(FileNotFoundError):
        find_cell("blobs", base)
    (chip / "generators" / "blobs.py").write_text("make_data = None\n")
    with pytest.raises(AttributeError):
        find_cell("blobs", base)


@pytest.fixture(scope="module")
def steady_trees(tmp_path_factory):
    """Two tiny trees, alike but that one's traffic files name the
    ``fields`` generator.  Every step holds the same fields, so the answers
    kept do not hang on how many steps a window commits."""
    trees = []
    for named in (False, True):
        base = tiny.make_tree(tmp_path_factory.mktemp("steady"))
        chip = base / "benchmarks" / "chip"
        (chip / "configs" / "tiny-step.json").write_text(
            json.dumps(dict(tiny.STEP_CONFIG, distinct_steps=1)))
        if named:
            for name, traffic in tiny.TRAFFIC.items():
                (chip / "traffic" / f"{name}.json").write_text(
                    json.dumps(dict(traffic, generator="fields")))
        trees.append(base)
    return trees


@pytest.mark.parametrize("cell", ["archive", "pgen", "train", "hot"])
def test_named_fields_generator_runs_as_the_default(steady_trees, cell):
    runs = []
    for base in steady_trees:
        c = find_cell(cell, base)
        assert c.generator.__file__.endswith("fields.py")
        runs.append(run.run_cell(c, seed=2 ** 31 + 41, seconds=0.6))
    default, named = runs
    assert default["correct"] and named["correct"]
    assert set(default["metrics"]) == set(named["metrics"])
    assert default["window"]["stored_ratio"] == \
        named["window"]["stored_ratio"]
    errs = {k: v for k, v in default["checks"].items()
            if k.startswith("err_")}
    assert errs and errs == {k: v for k, v in named["checks"].items()
                             if k.startswith("err_")}
