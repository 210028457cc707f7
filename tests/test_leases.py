"""Writer sessions + chunk-range leases: the multi-writer tensorstore.

Covers the PR acceptance criteria: two ``WriterSession``\\ s on disjoint
chunk ranges of one array produce byte-identical results to a single
sequential writer on all four backends; overlapping sessions
deterministically raise ``LeaseConflictError`` at *plan* time; a fenced
stale writer cannot commit after its lease is broken and re-acquired
(``StaleLeaseError``); plus the catalogue-level lease table contract
(cross-client visibility, epoch monotonicity), per-session dirty/flush
barriers, the ``ChunkedFieldStore.writer`` facade, the checkpointer's
``save_sharded``, and a threaded two-writer stress loop (marked slow).
"""
import threading

import numpy as np
import pytest

from repro.core import (FDB, FDBConfig, LeaseConflictError, StaleLeaseError)
from repro.obs.trace import GLOBAL_TRACER
from repro.tensorstore import TensorStore

BASE = {"store": "s", "array": "a", "writer": "w0"}


# ---------------------------------------------------------------------------
# catalogue-level lease table contract
# ---------------------------------------------------------------------------

def test_lease_table_contract(backend, tmp_path, make_fdb):
    """Acquire/conflict/idempotence/release/holders + epoch fencing, seen
    identically from two FDB clients of one deployment."""
    fdb, fdb2 = make_fdb(backend), make_fdb(backend)
    with fdb.session("A") as a:
        e1 = a.acquire_lease(BASE, "g0", 0, 4)
        assert a.acquire_lease(BASE, "g0", 0, 4) == e1   # idempotent
        b = fdb2.session("B")
        with pytest.raises(LeaseConflictError, match=r"\[2, 6\)"):
            b.acquire_lease(BASE, "g0", 2, 6)            # overlap, fast
        e2 = b.acquire_lease(BASE, "g0", 4, 8)           # disjoint is fine
        assert e2 > e1                                   # epochs monotonic
        holders = fdb.lease_holders(BASE, "g0")          # cross-client view
        assert [(l.owner, l.lo, l.hi) for l in holders] == \
            [("A", 0, 4), ("B", 4, 8)]
        # a third party breaks A's lease; B re-acquires; A is fenced
        fdb2.release_lease(BASE, "g0", 0, 4, owner="A")
        e3 = b.acquire_lease(BASE, "g0", 0, 4)
        assert e3 > e2
        with pytest.raises(StaleLeaseError, match="no longer current"):
            a.check_lease(BASE, "g0", 0, 4, e1)
        b.check_lease(BASE, "g0", 0, 4, e3)              # current holder ok
        b.close()
        assert fdb.lease_holders(BASE, "g0") == []       # close releases
    fdb.close()
    fdb2.close()


def test_lease_identifier_requires_dataset_and_collocation(tmp_path, make_fdb):
    fdb = make_fdb("daos")
    with pytest.raises(KeyError, match="missing dims"):
        fdb.acquire_lease({"store": "s"}, "g0", 0, 1, owner="A")
    # element dims are ignored (leases cover ranges, not keys)
    fdb.acquire_lease({**BASE, "chunk": "c0"}, "g0", 0, 1, owner="A")
    assert len(fdb.lease_holders(BASE, "g0")) == 1
    with pytest.raises(ValueError, match="half-open"):
        fdb.acquire_lease(BASE, "g0", 3, 3, owner="A")
    fdb.close()


# ---------------------------------------------------------------------------
# two writers, one array (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_two_writers_disjoint_byte_identical(backend, tmp_path, make_fdb):
    """Two sessions writing disjoint chunk ranges of one array ==
    byte-identical to a single sequential writer — per chunk object, not
    just per read."""
    fdb = make_fdb(backend)
    x = np.random.default_rng(0).normal(size=(64, 48)).astype(np.float32)
    ts = TensorStore(fdb, BASE)
    arr = ts.create(x.shape, x.dtype, chunks=(16, 16))
    fdb.flush()                          # publish the metadata (rule 3)
    sa, sb = fdb.session("A"), fdb.session("B")
    aa = TensorStore(None, BASE, session=sa).open()
    ab = TensorStore(None, BASE, session=sb).open()
    pa = aa.write_plan((slice(0, 32), slice(None)), x[:32])
    pb = ab.write_plan((slice(32, 64), slice(None)), x[32:])
    # disjoint linear chunk-id ranges were leased: rows 0-1 and 2-3 of a
    # (4, 3) chunk grid -> [0, 6) and [6, 12)
    assert [(lo, hi) for lo, hi, _e, _c in pa.leases] == [(0, 6)]
    assert [(lo, hi) for lo, hi, _e, _c in pb.leases] == [(6, 12)]
    pa.execute(flush=False)
    pb.execute(flush=False)
    sa.flush()                           # one barrier publishes both
    np.testing.assert_array_equal(arr.read(), x)
    sa.close()
    sb.close()
    # sequential single-writer reference on a second array slot
    ref_base = dict(BASE, array="ref")
    ref = TensorStore(fdb, ref_base).save(x, chunks=(16, 16))
    for idx in arr.grid.all_indices():
        multi = fdb.retrieve(arr.chunk_ident(idx)).read()
        single = fdb.retrieve(ref.chunk_ident(idx)).read()
        assert multi == single, f"chunk {idx} bytes differ"
    fdb.close()


def test_overlapping_writers_rejected_at_plan_time(tmp_path, make_fdb):
    """The second writer fails fast — before any byte moves — and the
    array is untouched by the failed plan."""
    fdb = make_fdb("daos")
    x = np.ones((32, 32), np.float32)
    arr = TensorStore(fdb, BASE).save(x, chunks=(8, 8))
    sa, sb = fdb.session("A"), fdb.session("B")
    aa = TensorStore(None, BASE, session=sa).open()
    ab = TensorStore(None, BASE, session=sb).open()
    aa.write_plan((slice(0, 16), slice(None)), 2 * x[:16])
    with pytest.raises(LeaseConflictError, match="leased by"):
        ab.write_plan((slice(8, 24), slice(None)), 3 * x[:16])
    # the failed plan holds nothing: B can still lease the disjoint rest
    pb = ab.write_plan((slice(16, 32), slice(None)), 3 * x[:16])
    pb.execute()
    np.testing.assert_array_equal(arr[0:16], x[:16])     # A never executed
    np.testing.assert_array_equal(arr[16:32], 3 * x[:16])
    sa.close()
    sb.close()
    fdb.close()


def test_partial_conflict_rolls_back_acquired_ranges(tmp_path, make_fdb):
    """A plan that conflicts on its second range must release the first —
    a failed plan leaves no leases behind."""
    fdb = make_fdb("daos")
    arr = TensorStore(fdb, BASE).save(np.zeros(64, np.float32), chunks=(8,))
    sa, sb = fdb.session("A"), fdb.session("B")
    sb.acquire_lease(BASE, "g0", 6, 7)   # B pre-holds chunk 6
    ab = TensorStore(None, BASE, session=sa).open()
    # strided write touching chunks 0,2,4,6 -> ranges [0,1),[2,3),[4,5),[6,7)
    with pytest.raises(LeaseConflictError):
        ab.write_plan((slice(None, None, 16),), np.zeros(4, np.float32))
    holders = fdb.lease_holders(BASE, "g0")
    assert [(l.owner, l.lo, l.hi) for l in holders] == [("B", 6, 7)]
    sa.close()
    sb.close()
    fdb.close()


def test_sibling_plan_release_is_exact_range(tmp_path, make_fdb):
    """A session may hold overlapping leases (two plans over intersecting
    windows); abandoning one plan must not sweep away its sibling's lease
    — holder-side release is exact-range."""
    fdb = make_fdb("daos")
    arr = TensorStore(fdb, BASE).save(np.zeros(64, np.float32), chunks=(8,))
    sa = fdb.session("A")
    aa = TensorStore(None, BASE, session=sa).open()
    p1 = aa.write_plan((slice(0, 32),), np.ones(32, np.float32))   # [0, 4)
    p1.execute(flush=False)              # archived, unflushed: lease held
    p2 = aa.write_plan((slice(16, 48),), np.ones(32, np.float32))  # [2, 6)
    assert [(lo, hi) for lo, hi, _e, _c in p2.leases] == [(2, 6)]
    p2.release_leases()                  # abandon the overlapping sibling
    # p1's lease survives: another writer still conflicts on [0, 4)
    sb = fdb.session("B")
    ab = TensorStore(None, BASE, session=sb).open()
    with pytest.raises(LeaseConflictError):
        ab.write_plan((slice(0, 8),), np.zeros(8, np.float32))
    sa.close()                           # flushes, then frees [0, 4)
    ab.write_plan((slice(0, 8),), np.zeros(8, np.float32)).execute()
    np.testing.assert_array_equal(arr[8:32], np.ones(24, np.float32))
    sb.close()
    fdb.close()


@pytest.mark.parametrize("backend", ["daos", "posix"])
def test_stale_writer_fenced_after_reacquisition(backend, tmp_path, make_fdb):
    """The acceptance scenario: a writer whose lease was broken and
    re-acquired cannot commit its planned write — and the new holder's
    data survives untouched."""
    fdb = make_fdb(backend)
    x = np.zeros((32, 32), np.float32)
    arr = TensorStore(fdb, BASE).save(x, chunks=(8, 8))
    sa, sb = fdb.session("A"), fdb.session("B")
    aa = TensorStore(None, BASE, session=sa).open()
    ab = TensorStore(None, BASE, session=sb).open()
    stale = aa.write_plan((slice(0, 16), slice(None)), x[:16] + 7)
    # coordinator presumes A dead and breaks its lease; B takes over
    fdb.release_lease(BASE, f"g{arr.meta.generation}", 0, 8, owner="A")
    pb = ab.write_plan((slice(0, 16), slice(None)), x[:16] + 9)
    pb.execute()
    with pytest.raises(StaleLeaseError, match="no longer current"):
        stale.execute()
    np.testing.assert_array_equal(arr[0:16], x[:16] + 9)  # B's data intact
    # A may re-acquire after B releases and then proceed at a fresh epoch
    sb.close()
    again = aa.write_plan((slice(0, 16), slice(None)), x[:16] + 7)
    assert again.leases[0][2] > stale.leases[0][2]        # epoch advanced
    again.execute()
    np.testing.assert_array_equal(arr[0:16], x[:16] + 7)
    sa.close()
    fdb.close()


def test_rmw_fetch_is_lease_fenced(tmp_path, make_fdb):
    """A stale writer aborts *before* its read-modify-write fetches — the
    lease gate guards the reads too, not only the archives."""
    fdb = make_fdb("posix")
    x = np.arange(64, dtype=np.float32)
    arr = TensorStore(fdb, BASE).save(x, chunks=(8,))
    sa = fdb.session("A")
    aa = TensorStore(None, BASE, session=sa).open()
    stale = aa.write_plan((slice(4, 12),), np.zeros(8, np.float32))
    assert stale.rmw_chunks == 2
    fdb.release_lease(BASE, "g0", 0, 2, owner="A")
    from repro.core.engine.meter import GLOBAL_METER
    before = len(GLOBAL_METER.snapshot())
    with pytest.raises(StaleLeaseError):
        stale.execute()
    reads = [op for op in GLOBAL_METER.snapshot()[before:]
             if op.kind == "read"]
    assert not reads                     # fenced before any fetch I/O
    np.testing.assert_array_equal(arr.read(), x)
    sa.close()
    fdb.close()


# ---------------------------------------------------------------------------
# per-session visibility (rule 3 barriers)
# ---------------------------------------------------------------------------

def test_per_session_dirty_and_flush(tmp_path, make_fdb):
    fdb = make_fdb("posix")
    arr = TensorStore(fdb, BASE).save(np.zeros(32, np.float32), chunks=(8,))
    sa, sb = fdb.session("A"), fdb.session("B")
    aa = TensorStore(None, BASE, session=sa).open()
    assert not sa.dirty and not sb.dirty
    aa.write_plan((slice(0, 8),), np.ones(8, np.float32)).execute(flush=False)
    assert sa.dirty and not sb.dirty     # dirty tracks per session
    assert fdb.dirty
    sb.flush()                           # ANY barrier publishes the client
    assert not sa.dirty and not fdb.dirty
    np.testing.assert_array_equal(arr[0:8], np.ones(8, np.float32))
    sa.close()
    sb.close()
    fdb.close()


def test_session_close_flushes_then_releases(tmp_path, make_fdb):
    """Leases must not be released over unflushed chunks: close flushes
    first, so the next holder can never RMW not-yet-visible bytes."""
    fdb = make_fdb("posix")
    arr = TensorStore(fdb, BASE).save(np.zeros(32, np.float32), chunks=(8,))
    sa = fdb.session("A")
    aa = TensorStore(None, BASE, session=sa).open()
    aa.write_plan((slice(0, 16),), np.ones(16, np.float32)).execute(
        flush=False)
    assert sa.dirty and len(sa.held_leases) == 1
    sa.close()
    assert not fdb.dirty                 # flushed on close
    assert fdb.lease_holders(BASE, "g0") == []
    np.testing.assert_array_equal(arr[0:16], np.ones(16, np.float32))
    with pytest.raises(RuntimeError, match="closed"):
        sa.archive({**BASE, "chunk": "c9"}, b"x")
    fdb.close()


def test_sessionless_store_unchanged(tmp_path, make_fdb):
    """No session, no leases: the single-writer path neither acquires nor
    checks anything (plans report empty lease lists)."""
    fdb = make_fdb("daos")
    arr = TensorStore(fdb, BASE).save(np.zeros(16, np.float32), chunks=(4,))
    plan = arr.write_plan((slice(None),), np.ones(16, np.float32))
    assert plan.session is None and plan.leases == []
    plan.execute()
    assert fdb.lease_holders(BASE, "g0") == []
    fdb.close()


def test_reshard_rejected_in_session(tmp_path, make_fdb):
    fdb = make_fdb("daos")
    TensorStore(fdb, BASE).save(np.zeros((8, 8), np.float32), chunks=(4, 4))
    with fdb.session("A") as sa:
        arr = TensorStore(None, BASE, session=sa).open()
        with pytest.raises(NotImplementedError, match="single-writer"):
            arr.reshard((2, 8))
    fdb.close()


# ---------------------------------------------------------------------------
# facades: ChunkedFieldStore.writer + FDBCheckpointer.save_sharded
# ---------------------------------------------------------------------------

def test_field_store_concurrent_writers():
    """Multi-producer write_window: two threads, disjoint windows, one
    coherent read after commit; overlap rejected; close releases."""
    from repro.data.pipeline import ChunkedFieldStore
    st = ChunkedFieldStore("nwp", FDBConfig(backend="daos"),
                           chunks=(16, 16))
    st.put_field("t2m", np.zeros((64, 64), np.float32))
    st.commit()
    wa, wb = st.writer("assimA"), st.writer("assimB")
    errs = []

    def job(w, sel, val):
        try:
            w.write_window("t2m", val, *sel)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    ta = threading.Thread(target=job,
                          args=(wa, (slice(0, 32), slice(None)), 1.0))
    tb = threading.Thread(target=job,
                          args=(wb, (slice(32, 64), slice(None)), 2.0))
    ta.start(); tb.start(); ta.join(); tb.join()
    assert not errs
    wa.commit()
    y = st.read_window("t2m", slice(None), slice(None))
    assert (y[:32] == 1.0).all() and (y[32:] == 2.0).all()
    # held windows block overlap until the writer closes
    with pytest.raises(LeaseConflictError):
        wb.write_window("t2m", 9.0, slice(16, 48), slice(None))
    wa.close()
    wb.close()
    with st.writer("late") as wl:
        wl.write_window("t2m", 9.0, slice(16, 48), slice(None))
        wl.commit()
    assert (st.read_window("t2m", slice(16, 48), slice(None)) == 9.0).all()
    st.close()


@pytest.mark.parametrize("codec", ["field16", "field8", "raw"])
def test_declared_field_filled_by_writer_clients(tmp_path, codec):
    """The I/O-server pattern: one client declares a field, three writer
    clients of the same deployment each fill their own levels and commit
    behind their own barrier.  The chunk objects match one ``put_field``
    of the same values byte for byte; a level whose writer has not
    committed reads as missing; a released writer holds nothing."""
    from repro.data.pipeline import ChunkedFieldStore
    x = (280 + 10 * np.random.default_rng(3).normal(size=(6, 1300))
         ).astype(np.float32)
    cfg = FDBConfig(backend="posix", root=str(tmp_path / "fdb"))
    coord, *io = [ChunkedFieldStore("nwp", cfg, chunks=(1, 512),
                                    codec=codec) for _ in range(4)]
    coord.declare_field("t_s0", x.shape, x.dtype)
    writers = [st.writer(f"io{i}") for i, st in enumerate(io)]
    for lvl in range(6):
        writers[lvl % 3].write_window("t_s0", x[lvl:lvl + 1],
                                      slice(lvl, lvl + 1))
    writers[0].commit()
    writers[1].commit()
    early = ChunkedFieldStore("nwp", cfg)
    np.testing.assert_array_equal(
        early.read_window("t_s0", slice(0, 2), fill_missing=False),
        early.read_window("t_s0", slice(0, 2)))
    with pytest.raises(KeyError):       # io2's levels: archived, unpublished
        early.read_window("t_s0", slice(2, 3), fill_missing=False)
    writers[2].commit()
    # io0 still holds its committed levels
    with pytest.raises(LeaseConflictError):
        writers[1].write_window("t_s0", x[3:4], slice(3, 4))

    coord.put_field("ref", x)
    coord.commit()
    arr, ref = coord.open_field("t_s0"), coord.open_field("ref")
    for idx in arr.grid.all_indices():
        assert coord.fdb.retrieve(arr.chunk_ident(idx)).read() == \
            coord.fdb.retrieve(ref.chunk_ident(idx)).read(), idx
    consumer = ChunkedFieldStore("nwp", cfg)
    np.testing.assert_array_equal(
        consumer.read_window("t_s0", fill_missing=False),
        consumer.read_window("ref", fill_missing=False))

    assert [w.release("t_s0") for w in writers] == [2, 2, 2]
    for w in writers:
        assert w.session.held_leases == [] and w._opened == {}
    assert coord.fdb.lease_holders(
        {"store": "nwp", "array": "t_s0", "writer": "prod0"}, "g0") == []
    releases = [s for s in GLOBAL_TRACER.spans() if s.name == "field.release"]
    assert [s.attrs["leases"] for s in releases] == [2, 2, 2]
    assert any(s.name == "field.declare" for s in GLOBAL_TRACER.spans())
    for st in (coord, *io, early, consumer):
        st.close()


@pytest.mark.parametrize("backend", ["daos", "posix"])
def test_save_sharded_matches_sequential_save(backend, tmp_path):
    """Each simulated rank leases + writes its own shard band; restore is
    byte-identical to a sequential save of the same state."""
    from repro.train.checkpoint import FDBCheckpointer
    params = {"w": np.arange(64 * 16, dtype=np.float32).reshape(64, 16),
              "b": np.arange(16, dtype=np.float32),
              "s": np.float32(3.5)}
    opt = {"mu": np.ones((64, 16), np.float32)}
    cfg = FDBConfig(backend=backend, root=str(tmp_path / "fdb"))
    ck = FDBCheckpointer("runA", cfg, n_shards=4)
    ck.save_sharded(10, params, opt, extra={"lr": np.float32(0.1)})
    got = ck.restore(10, params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(params[k]))
    np.testing.assert_array_equal(np.asarray(ck.restore(10, opt, "opt")["mu"]),
                                  opt["mu"])
    # banded tensor chunk objects match a sequential save's, byte for byte
    seq = FDBCheckpointer("runB", cfg, n_shards=4)
    seq.save(10, params, opt)
    a = ck.open_tensor(10, "w")
    b = seq.open_tensor(10, "w")
    assert a.meta.chunks == b.meta.chunks
    for idx in a.grid.all_indices():
        assert ck.fdb.retrieve(a.chunk_ident(idx)).read() == \
            seq.fdb.retrieve(b.chunk_ident(idx)).read()
    # all rank leases were released at the end of the save
    assert ck.fdb.lease_holders(
        {**ck._dataset("params", 10), "host": ck.host, "tensor": "w"},
        "g0") == []
    ck.close()
    seq.close()


def test_save_sharded_requires_chunked(tmp_path):
    from repro.train.checkpoint import FDBCheckpointer
    ck = FDBCheckpointer("runC", FDBConfig(backend="daos"), chunked=False)
    with pytest.raises(ValueError, match="chunked"):
        ck.save_sharded(0, {"w": np.ones(4, np.float32)})
    ck.close()


# ---------------------------------------------------------------------------
# threaded stress (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("backend", ["daos", "posix"])
def test_two_thread_stress_one_array(backend, tmp_path, make_fdb):
    """Two real threads hammer disjoint halves of one array through their
    own sessions — interleaved plans, partial (RMW) windows, per-write
    commits — and the final state is exactly what a serial replay gives."""
    fdb = make_fdb(backend, io_parallelism=4)
    n, chunk = 256, 8
    x = np.zeros(n, np.float32)
    arr = TensorStore(fdb, BASE).save(x, chunks=(chunk,))
    rng = np.random.default_rng(7)
    #: per-writer scripted updates inside its own half (some chunk-aligned,
    #: some partial -> RMW), replayed serially for the reference
    scripts = []
    for half in range(2):
        lo_half = half * (n // 2)
        script = []
        for _ in range(25):
            a = int(rng.integers(0, n // 2 - 1))
            b = int(rng.integers(a + 1, n // 2))
            val = float(rng.normal())
            script.append((lo_half + a, lo_half + b, val))
        scripts.append(script)
    errs = []

    def writer(w: int) -> None:
        try:
            with fdb.session(f"W{w}") as sess:
                aw = TensorStore(None, BASE, session=sess).open()
                for lo, hi, val in scripts[w]:
                    aw.write_plan((slice(lo, hi),),
                                  np.full(hi - lo, val, np.float32)
                                  ).execute(flush=True)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    ref = x.copy()
    for script in scripts:
        for lo, hi, val in script:
            ref[lo:hi] = val
    np.testing.assert_array_equal(arr.read(), ref)
    assert fdb.lease_holders(BASE, "g0") == []
    fdb.close()
