"""repro.obs: spans, trace-context propagation, metrics, exporters.

Covers the observability acceptance criteria: parentage surviving the
executor's thread hand-off, reshard nesting, the disabled fast path
recording nothing, Chrome trace_event export validity, phase-attributed
totals, the bounded Meter.ops cap, and tracing changing no stored bytes.
"""
import json
import threading

import numpy as np
import pytest

from repro.core import FDB, FDBConfig, LeaseConflictError, Meter
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Tracer,
                       TraceBuffer)
from repro.obs.trace import (_NOOP, CPU_TIMED_SPANS, PHASE_SPANS, count,
                             current_span, set_span_mirror, span)
from repro.tensorstore import TensorStore
from repro.tensorstore.codec import get_codec

ENCODE_STAGES = {f"codec.encode.{s}"
                 for s in ("stack", "launch", "d2h", "pack")}
DECODE_STAGES = {f"codec.decode.{s}"
                 for s in ("stack", "launch", "d2h", "unpack")}

BACKENDS = ["daos", "rados", "posix", "s3"]


def make_store(backend, tmp_path, tracer=None, array="a", **kw):
    fdb = FDB(FDBConfig(backend=backend, schema="tensor",
                        root=str(tmp_path / "fdb"), **kw), tracer=tracer)
    return fdb, TensorStore(fdb, {"store": "s", "array": array,
                                  "writer": "w0"})


def span_index(spans):
    return {s.span_id: s for s in spans}


def ancestry(s, by_id):
    names = []
    while s is not None:
        names.append(s.name)
        s = by_id.get(s.parent_id)
    return names


# ---------------------------------------------------------------------------
# tracer primitives
# ---------------------------------------------------------------------------

def test_span_nesting_builds_parent_chain():
    tr = Tracer(enabled=True)
    with tr.span("outer", k=1) as a:
        assert current_span() is a
        with tr.span("inner") as b:
            assert b.parent_id == a.span_id
        with tr.span("inner2") as c:
            assert c.parent_id == a.span_id
    assert current_span() is None
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "inner2", "outer"]
    assert spans[-1].parent_id is None
    assert spans[-1].t1_ns >= spans[-1].t0_ns
    assert spans[-1].attrs == {"k": 1}


def test_span_records_error_attr():
    tr = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    (s,) = tr.spans()
    assert s.attrs["error"] == "ValueError"


def test_disabled_tracer_is_noop_fast_path():
    tr = Tracer(enabled=False)
    cm = tr.span("anything", k=1)
    assert cm is _NOOP                      # shared object, no allocation
    with cm as s:
        assert s is None
        assert current_span() is None
    assert tr.spans() == []
    assert tr.record_complete("x", 0, 10) is None
    assert tr.spans() == []
    # the ambient helper is also a no-op outside any traced span
    assert span("ambient") is _NOOP


def test_ambient_span_joins_active_tracer():
    tr = Tracer(enabled=True)
    with tr.span("outer") as a:
        with span("ambient", nbytes=3) as b:
            assert b.tracer is tr and b.parent_id == a.span_id
    assert [s.name for s in tr.spans()] == ["ambient", "outer"]


def test_ambient_count_joins_active_tracer():
    tr = Tracer(enabled=True)
    count("ambient.n", 5)                   # no traced operation: no-op
    with tr.span("outer"):
        count("ambient.n", 2)
        count("ambient.n")
    assert tr.metrics.snapshot()["ambient.n"]["value"] == 3
    off = Tracer(enabled=False)
    with off.span("outer"):
        count("ambient.n")
    assert "ambient.n" not in off.metrics.snapshot()


def test_foreign_tracer_parent_treated_as_root():
    tr1, tr2 = Tracer(enabled=True), Tracer(enabled=True)
    with tr1.span("outer"):
        with tr2.span("other") as b:
            assert b.parent_id is None      # tr1's span would dangle in tr2


def test_trace_buffer_bounded_and_windowed():
    tr = Tracer(enabled=True, capacity=8)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert tr.buffer.total == 20 and tr.dropped == 12
    assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]
    # a mark inside the evicted region clamps to the oldest retained span
    assert [s.name for s in tr.spans(since=5)][0] == "s12"
    mark = tr.mark()
    with tr.span("fresh"):
        pass
    assert [s.name for s in tr.spans(since=mark)] == ["fresh"]
    tr.clear()
    assert tr.spans() == [] and tr.buffer.total == 0


def test_record_complete_interval():
    tr = Tracer(enabled=True)
    with tr.span("parent") as p:
        s = tr.record_complete("queue.wait", 1000, 5000, parent=p, depth=2)
    assert s.parent_id == p.span_id
    assert s.duration_us == 4.0 and s.attrs == {"depth": 2}


def test_chrome_trace_export_shape():
    tr = Tracer(enabled=True)
    with tr.span("a", nbytes=3, arr=np.int64(7)):
        with tr.span("b"):
            pass
    doc = tr.chrome_trace(process_name="test")
    blob = json.dumps(doc)                  # must be JSON-serialisable
    doc = json.loads(blob)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert meta[0]["args"]["name"] == "test"
    assert {e["name"] for e in xs} == {"a", "b"}
    for e in xs:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    # non-JSON attr values are stringified, not dropped
    a = next(e for e in xs if e["name"] == "a")
    assert a["args"]["nbytes"] == 3 and a["args"]["arr"] == "7"


def test_phase_totals_counts_exact_leaf_names_only():
    tr = Tracer(enabled=True)
    with tr.span("plan.execute"):           # wrapper: must not count
        with tr.span("io.fetch"):
            pass
        with tr.span("codec.decode"):
            pass
        with tr.span("io.archive"):
            pass
    pt = tr.phase_totals()
    assert pt["io"] > 0 and pt["decode"] > 0 and pt["encode"] == 0
    total = sum(pt.values())
    wrapper = next(s for s in tr.spans() if s.name == "plan.execute")
    assert total < wrapper.duration_us      # nested leaves < wrapper alone
    # every phase name set is exact (no prefixes), so wrappers never leak in
    for names in PHASE_SPANS.values():
        assert "plan.execute" not in names


def test_rollup_table_and_store_latency_histograms():
    tr = Tracer(enabled=True)
    with tr.span("store.daos.archive"):
        pass
    with tr.span("store.daos.archive"):
        pass
    text = tr.rollup()
    assert "store.daos.archive" in text and "count" in text
    h = tr.metrics.get("store.daos.archive_us")
    assert isinstance(h, Histogram) and h.count == 2


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("ops").inc()
    reg.counter("ops").inc(4)
    assert reg.counter("ops").value == 5
    g = reg.gauge("depth")
    g.set(3)
    g.add(2)
    assert g.value == 5 and g.max == 5
    g.set(1)
    assert g.value == 1 and g.max == 5      # high-water mark sticks
    h = reg.histogram("lat_us", buckets=(10, 100))
    for v in (5, 50, 500):
        h.observe(v)
    assert h.count == 3 and h.sum == 555
    assert h.mean == pytest.approx(185.0)
    assert h.percentile(50) <= 100
    snap = reg.snapshot()
    assert snap["ops"]["value"] == 5
    assert snap["lat_us"]["count"] == 3
    assert snap["lat_us"]["buckets"]["gt_100"] == 1
    with pytest.raises(TypeError):
        reg.counter("depth")                # name already bound to a Gauge
    reg.clear()
    assert reg.counter("ops").value == 0


def test_metrics_thread_safety_smoke():
    reg = MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.counter("c").inc()
            reg.histogram("h").observe(1.0)

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("c").value == 4000
    assert reg.histogram("h").count == 4000


# ---------------------------------------------------------------------------
# span mirror, per-span CPU time
# ---------------------------------------------------------------------------

class _LoggedMirror:
    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def mirror_log(monkeypatch):
    """A logging mirror for one test; the mirror installed before it (the
    profiler's, once ``repro.kernels.ops`` is imported) comes back after."""
    import repro.obs.trace as trace_mod
    monkeypatch.setattr(trace_mod, "_MIRROR", None)
    log = []
    set_span_mirror(lambda name: _LoggedMirror(log, name))
    return log


def test_span_mirror_entered_and_exited_in_span_order(mirror_log):
    tr = Tracer(enabled=True)
    with tr.span("outer") as outer:
        with span("inner"):
            pass
        with pytest.raises(ValueError):
            with tr.span("failing"):
                raise ValueError("x")
        # measured after the fact: nothing to mirror
        tr.record_complete("executor.queue", 0, 10, parent=outer)
    assert mirror_log == [("enter", "outer"), ("enter", "inner"),
                          ("exit", "inner"), ("enter", "failing"),
                          ("exit", "failing"), ("exit", "outer")]


def test_disabled_tracer_never_touches_the_mirror(mirror_log):
    tr = Tracer(enabled=False)
    with tr.span("off") as sp:
        with span("ambient"):
            assert sp is None
    assert mirror_log == []


def test_cpu_timed_spans_count_cpu_time():
    assert CPU_TIMED_SPANS == {"codec.encode", "io.archive"}
    tr = Tracer(enabled=True)
    with tr.span("codec.encode", k=1) as sp:
        with tr.span("codec.encode.stack") as inner:
            assert int(np.ones(1 << 22, np.uint8).sum()) == 1 << 22
    assert 0 < sp.cpu_ns
    assert inner.cpu_ns is None         # not a CPU-timed name
    q = tr.record_complete("io.archive", 0, 10)
    assert q.cpu_ns is None             # measured after the fact
    off = Tracer(enabled=False)
    with off.span("io.archive") as none:
        assert none is None
    # the slot stays out of attrs, and reaches the exported args
    assert sp.attrs == {"k": 1}
    events = {e["name"]: e["args"] for e in tr.chrome_events()}
    assert events == {"codec.encode": {"k": 1, "cpu_ns": sp.cpu_ns},
                      "codec.encode.stack": {}, "io.archive": {}}


def test_mirrored_spans_reach_the_profiler_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    import repro.obs.trace as trace_mod
    from repro.kernels import ops  # noqa: F401  (installs the mirror)
    assert trace_mod._MIRROR is jax.profiler.TraceAnnotation
    tr = Tracer(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("plan.execute"):
            with span("codec.decode.launch"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"plan.execute", "codec.decode.launch"} <= names


# ---------------------------------------------------------------------------
# instrumentation: context propagation through the I/O stack
# ---------------------------------------------------------------------------

def test_executor_thread_spans_parent_under_plan(tmp_path):
    """io.fetch / codec.decode run on pool threads, but their ancestry
    chains reach the plan.execute span of the submitting thread — the
    contextvars hand-off across the ChunkExecutor."""
    tracer = Tracer(enabled=True)
    fdb, ts = make_store("daos", tmp_path, tracer=tracer)
    x = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    mark = tracer.mark()
    np.testing.assert_array_equal(arr[8:40, :], x[8:40, :])
    spans = tracer.spans(since=mark)
    by_id = span_index(spans)
    main = threading.get_ident()
    fetches = [s for s in spans if s.name == "io.fetch"]
    assert fetches
    assert any(s.thread_id != main for s in fetches)    # really off-thread
    for s in fetches:
        assert "plan.execute" in ancestry(s, by_id)
    # queue-wait intervals also attach under the plan
    queued = [s for s in spans if s.name == "executor.queue"]
    assert queued
    for s in queued:
        assert "plan.execute" in ancestry(s, by_id)
    assert tracer.metrics.histogram("executor.queue_us").count >= len(queued)
    fdb.close()


def test_reshard_spans_nest_inner_plans(tmp_path):
    tracer = Tracer(enabled=True)
    fdb, ts = make_store("posix", tmp_path, tracer=tracer)
    x = np.random.default_rng(1).normal(size=(64, 64)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    mark = tracer.mark()
    arr.reshard((32, 64))
    spans = tracer.spans(since=mark)
    by_id = span_index(spans)
    roots = [s for s in spans if s.name == "plan.reshard"]
    assert len(roots) == 1
    inner = [s for s in spans if s.name == "plan.execute"]
    assert inner
    for s in inner:
        chain = ancestry(s, by_id)
        assert "reshard.batch" in chain and "plan.reshard" in chain
    fdb.close()


def test_disabled_tracing_records_nothing_through_the_stack(tmp_path):
    fdb, ts = make_store("daos", tmp_path)      # default: GLOBAL_TRACER off
    x = np.arange(256, dtype=np.float32).reshape(16, 16)
    ts.save(x, chunks=(8, 8))
    np.testing.assert_array_equal(ts.open()[:, :], x)
    assert fdb.trace() == []
    # spans are gated off; coarse counters still count (exact, cheap)
    assert fdb.metrics().get("codec.bytes_encoded", {}).get("value", 0) > 0
    fdb.close()


def test_fdb_trace_and_metrics_accessors(tmp_path):
    tracer = Tracer(enabled=True)
    fdb, ts = make_store("rados", tmp_path, tracer=tracer)
    x = np.ones((8, 8), np.float32)
    ts.save(x, chunks=(4, 4))
    mark = tracer.mark()
    ts.open().read()
    names = {s.name for s in fdb.trace(since=mark)}
    assert "io.fetch" in names and "codec.decode" in names
    m = fdb.metrics()
    assert m["codec.bytes_decoded"]["value"] >= x.nbytes
    assert "store.rados.archive_us" in m
    fdb.close()


def test_lease_conflict_and_session_metrics(tmp_path):
    tracer = Tracer(enabled=True)
    fdb, ts = make_store("daos", tmp_path, tracer=tracer)
    ts.create((32, 32), np.float32, chunks=(8, 8))
    fdb.flush()
    s1, s2 = fdb.session("w1"), fdb.session("w2")
    a1 = TensorStore(None, {"store": "s", "array": "a", "writer": "w0"},
                     session=s1).open()
    a2 = TensorStore(None, {"store": "s", "array": "a", "writer": "w0"},
                     session=s2).open()
    a1.write_plan((slice(0, 16), slice(None)),
                  np.zeros((16, 32), np.float32)).execute(flush=False)
    with pytest.raises(LeaseConflictError):
        a2.write_plan((slice(8, 24), slice(None)),
                      np.ones((16, 32), np.float32))
    assert tracer.metrics.counter("lease.conflicts").value == 1
    assert tracer.metrics.counter("lease.acquired").value >= 1
    s1.close()
    s2.close()
    names = {s.name for s in tracer.spans()}
    assert {"lease.acquire", "session.close"} <= names
    fdb.close()


def test_codec_stages_nest_under_codec_spans(tmp_path):
    tracer = Tracer(enabled=True)
    fdb, ts = make_store("daos", tmp_path, tracer=tracer)
    x = np.random.default_rng(3).normal(size=(64, 512)).astype(np.float32)
    ts.save(x, chunks=(32, 512), codec="field8")
    np.testing.assert_allclose(ts.open()[8:40, :], x[8:40, :], atol=0.05)
    spans = tracer.spans()
    by_id = span_index(spans)
    names = {s.name for s in spans}
    assert ENCODE_STAGES | DECODE_STAGES | {"plan.assemble"} <= names
    for s in spans:
        parent = by_id.get(s.parent_id)
        if s.name in ENCODE_STAGES:
            assert parent.name == "codec.encode"
        elif s.name in DECODE_STAGES:
            assert parent.name == "codec.decode"
        elif s.name == "plan.assemble":
            assert parent.name == "plan.execute"
    fdb.close()


def test_codec_stages_change_no_bytes():
    """Encode and decode give the same bytes with tracing on and off."""
    codec = get_codec("field16")
    rng = np.random.default_rng(5)
    arrs = ([rng.normal(size=(4, 384)).astype(np.float32) for _ in range(3)]
            + [rng.normal(size=(3, 300)), np.ones(7, np.float32)])
    shapes = [a.shape for a in arrs]
    off = codec.encode_batch(arrs)
    decoded_off = codec.decode_batch(off, shapes, np.float32)
    tr = Tracer(enabled=True)
    with tr.span("codec.encode"):
        on = codec.encode_batch(arrs)
    assert on == off
    with tr.span("codec.decode"):
        decoded_on = codec.decode_batch(on, shapes, np.float32)
    for a, b in zip(decoded_off, decoded_on):
        assert a.tobytes() == b.tobytes()
    assert ENCODE_STAGES | DECODE_STAGES <= {s.name for s in tr.spans()}


# ---------------------------------------------------------------------------
# tracing must not change what is stored
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["posix", "daos"])
def test_tracing_changes_no_stored_bytes(backend, tmp_path):
    """Byte-identical archives with tracing on vs off: observability is
    read-only with respect to the data path."""
    x = np.random.default_rng(7).normal(size=(37, 53)).astype(np.float32)

    def stored_bytes(sub, tracer):
        from repro.core import reset_engines
        reset_engines()
        fdb = FDB(FDBConfig(backend=backend, schema="tensor",
                            root=str(tmp_path / sub)), tracer=tracer)
        ts = TensorStore(fdb, {"store": "s", "array": "a", "writer": "w0"})
        ts.save(x, chunks=(16, 16))
        arr = ts.open()
        arr[0:10, 0:10] = 2.5               # exercise RMW too
        blobs = {}
        for ident, _loc in fdb.list({"store": "s", "array": "a"}):
            key = tuple(sorted(ident.items()))
            blobs[key] = fdb.retrieve(ident).read()
        fdb.close()
        return blobs

    off = stored_bytes("off", Tracer(enabled=False))
    on = stored_bytes("on", Tracer(enabled=True))
    assert off.keys() == on.keys()
    for k in off:
        assert off[k] == on[k], f"stored bytes differ under tracing: {k}"


# ---------------------------------------------------------------------------
# Meter.ops cap (bounded trace, exact counters)
# ---------------------------------------------------------------------------

def test_meter_ops_bounded_with_exact_rollup():
    from repro.core import client_context
    m = Meter(max_ops=10)
    for i in range(15):
        with client_context(f"c{i % 2}@n0"):
            m.record("target:0", "write", nbytes=100)
    assert len(m.snapshot()) == 10          # trace truncated at the cap
    assert m.dropped_ops == 5
    s = m.summary()
    # counters stay exact past the cap — and the truncation is reported
    assert s["total_ops"] == 15
    assert s["ops_by_kind"]["write"] == 15
    assert s["bytes_by_kind"]["write"] == 1500
    assert s["clients"] == 2
    assert s["dropped_ops"] == 5 and s["trace_truncated"] is True
    m.reset()
    assert m.dropped_ops == 0 and len(m.snapshot()) == 0
    m.record("target:0", "read", nbytes=1)
    assert m.summary()["total_ops"] == 1
    assert "trace_truncated" not in m.summary()


def test_meter_windowing_below_cap_unchanged():
    m = Meter()
    m.record("target:0", "write", nbytes=1)
    before = m.snapshot()
    m.record("target:0", "read", nbytes=2)
    new = m.snapshot()[len(before):]
    assert len(new) == 1 and new[0].kind == "read"
