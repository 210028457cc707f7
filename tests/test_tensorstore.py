"""repro.tensorstore: chunked N-D arrays over the FDB.

Covers the acceptance criteria: roundtrip of non-chunk-aligned arrays on all
four backends, partial slice reads issuing I/O for only the intersecting
chunks (asserted via engine ``Meter`` op counts), chunk-boundary edge cases,
and codec on/off parity — plus the executor's bounded in-flight window and
the batched ``FDB.archive_many`` semantics.
"""
import itertools
import struct
import time

import numpy as np
import pytest

from repro.core import FDB, FDBConfig, FieldLocation
from repro.core.engine.meter import GLOBAL_METER
from repro.tensorstore import (ChunkExecutor, ChunkGrid, TensorStore,
                               auto_chunks, get_codec)


#: engine op kinds that move object payload bytes on a read path
DATA_READ_KINDS = {"array_read", "read", "http_get"}


def _data_reads(ops):
    return [op for op in ops if op.kind in DATA_READ_KINDS]


# ---------------------------------------------------------------------------
# roundtrip + partial reads (acceptance criteria)
# ---------------------------------------------------------------------------

def test_non_aligned_roundtrip(backend, tmp_path, make_store):
    """(37, 53) on a (16, 16) grid: every edge chunk is clipped."""
    fdb, ts = make_store(backend)
    x = np.random.default_rng(0).normal(size=(37, 53)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    assert arr.shape == (37, 53) and arr.dtype == np.float32
    assert arr.n_chunks == (3, 4)
    np.testing.assert_array_equal(arr.read(), x)
    fdb.close()


def test_partial_read_touches_only_intersecting_chunks(backend, tmp_path, make_store):
    fdb, ts = make_store(backend)
    x = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    ts.save(x, chunks=(16, 16))          # 4 x 4 chunk grid, 1 KiB chunks
    arr = ts.open()
    arr[0:1, 0:1]                        # warm catalogue/axis caches

    for sel, n_expected in [
        ((slice(0, 16), slice(0, 16)), 1),     # exactly one chunk
        ((slice(10, 40), slice(0, 10)), 3),    # rows 0-2 x col 0
        ((slice(0, 64), slice(20, 28)), 4),    # full column band
    ]:
        before = GLOBAL_METER.snapshot()
        np.testing.assert_array_equal(arr[sel], x[sel])
        new_ops = GLOBAL_METER.snapshot()[len(before):]
        reads = _data_reads(new_ops)
        if backend == "posix":
            # posix stripes one chunk read over several OSTs: assert on bytes
            assert sum(op.nbytes for op in reads) == n_expected * 16 * 16 * 4
        else:
            assert len(reads) == n_expected, (sel, reads)
        assert sum(op.nbytes for op in reads) < x.nbytes
    fdb.close()


def test_full_read_moves_all_bytes(backend, tmp_path, make_store):
    fdb, ts = make_store(backend)
    x = np.random.default_rng(2).normal(size=(40, 40)).astype(np.float32)
    ts.save(x, chunks=(32, 32))
    arr = ts.open()
    before = GLOBAL_METER.snapshot()
    np.testing.assert_array_equal(arr.read(), x)
    reads = _data_reads(GLOBAL_METER.snapshot()[len(before):])
    assert sum(op.nbytes for op in reads) == x.nbytes
    fdb.close()


def test_replace_semantics_same_layout(tmp_path, make_store):
    """Re-saving with an unchanged layout transactionally replaces every
    chunk (FDB rule 5)."""
    fdb, ts = make_store("daos")
    ts.save(np.zeros((8, 8), np.float32), chunks=(4, 4))
    y = np.random.default_rng(3).normal(size=(8, 8)).astype(np.float32)
    ts.save(y, chunks=(4, 4))
    np.testing.assert_array_equal(ts.open().read(), y)
    fdb.close()


def test_layout_change_rejected_without_wipe(tmp_path, make_store):
    """A re-create with a different grid would strand old-grid chunk objects
    (no per-object delete in the FDB API) — it must be rejected."""
    from repro.tensorstore import LayoutMismatchError
    fdb, ts = make_store("daos")
    ts.save(np.zeros((8, 8), np.float32), chunks=(2, 2))
    with pytest.raises(LayoutMismatchError):
        ts.create((8, 8), np.float32, chunks=(4, 4))
    with pytest.raises(LayoutMismatchError):
        ts.create((6, 6), np.float32, chunks=(2, 2))
    # after a wipe the new layout goes through
    fdb.wipe({"store": "s", "array": "a"})
    y = np.ones((6, 6), np.float32)
    ts.save(y, chunks=(4, 4))
    np.testing.assert_array_equal(ts.open().read(), y)
    fdb.close()


def test_field_store_regrid_wipes_stale_chunks():
    """ChunkedFieldStore.put_field transparently wipes + re-creates on a
    layout change, leaving no stale old-grid entries behind."""
    from repro.data import ChunkedFieldStore
    fs = ChunkedFieldStore("regrid", FDBConfig(backend="daos"))
    fs.put_field("f", np.zeros((8, 8), np.float32), chunks=(2, 2))
    fs.commit()
    y = np.random.default_rng(11).normal(size=(8, 8)).astype(np.float32)
    fs.put_field("f", y, chunks=(4, 4))
    fs.commit()
    np.testing.assert_array_equal(fs.read_window("f"), y)
    listed = list(fs.fdb.list({"store": "regrid", "array": "f"}))
    assert len(listed) == 4 + 1          # 4 new-grid chunks + meta, no stale
    fs.close()


def test_checkpoint_legacy_resave_shadows_chunked():
    """A legacy (chunked=False) re-save of a step previously saved chunked
    must win on restore — the chunked metadata is tombstoned."""
    from repro.train.checkpoint import FDBCheckpointer
    w = np.full((64, 32), 1.0, np.float32)
    ck1 = FDBCheckpointer("shadow", FDBConfig(backend="daos"))
    ck1.save(5, {"w": w})
    ck2 = FDBCheckpointer("shadow", FDBConfig(backend="daos"), chunked=False)
    ck2.save(5, {"w": w * 2})
    restored = ck2.restore(5, {"w": w})
    np.testing.assert_array_equal(np.asarray(restored["w"]), w * 2)
    ck1.close()
    ck2.close()


def test_open_missing_array_raises(tmp_path, make_store):
    fdb, ts = make_store("daos", array="nope")
    assert not ts.exists()
    with pytest.raises(FileNotFoundError):
        ts.open()
    fdb.close()


# ---------------------------------------------------------------------------
# chunk-aligned partial writes (arr[sel] = values)
# ---------------------------------------------------------------------------

def test_partial_write_roundtrip(backend, tmp_path, make_store):
    """In-place assignment round-trips on every backend, including
    partially-covered edge chunks (read-modify-write)."""
    fdb, ts = make_store(backend)
    x = np.random.default_rng(20).normal(size=(37, 53)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    v = np.random.default_rng(21).normal(size=(20, 30)).astype(np.float32)
    arr[10:30, 17:47] = v                # cuts through 6 chunks, all partial
    x[10:30, 17:47] = v
    np.testing.assert_array_equal(arr.read(), x)
    arr[16:32, 16:32] = 0.0              # exactly one full chunk + broadcast
    x[16:32, 16:32] = 0.0
    np.testing.assert_array_equal(arr.read(), x)
    fdb.close()


def test_partial_write_full_chunks_skip_rmw(tmp_path, make_store):
    """A chunk-aligned selection needs no read-modify-write: no data-read
    ops on the write path."""
    fdb, ts = make_store("daos")
    x = np.zeros((64, 64), np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    before = GLOBAL_METER.snapshot()
    arr[16:48, 0:32] = 1.0               # 2x2 whole chunks
    assert not _data_reads(GLOBAL_METER.snapshot()[len(before):])
    x[16:48, 0:32] = 1.0
    np.testing.assert_array_equal(arr.read(), x)
    fdb.close()


def test_partial_write_into_created_empty_array(tmp_path, make_store):
    """Chunks never written read as zeros (fill-value convention), so a
    created-but-unwritten array can be populated by partial writes."""
    fdb, ts = make_store("rados")
    arr = ts.create((10, 10), np.float32, chunks=(4, 4))
    arr[2:5, 2:5] = 9.0
    want = np.zeros((10, 10), np.float32)
    want[2:5, 2:5] = 9.0
    np.testing.assert_array_equal(arr.read(), want)
    np.testing.assert_array_equal(ts.open().read(), want)
    # strict mode: consumers of dense arrays can refuse the zeros fill and
    # surface never-written chunks as corruption instead
    with pytest.raises(KeyError, match="missing chunk"):
        arr.read_plan((slice(None), slice(None)), fill_missing=False)
    full = ts.save(np.ones((10, 10), np.float32), chunks=(4, 4))
    assert full.read_plan((slice(None), slice(None)),
                          fill_missing=False).n_chunks == 9
    fdb.close()


def test_partial_write_int_index_and_broadcast(tmp_path, make_store):
    fdb, ts = make_store("posix")
    x = np.zeros((9, 7, 5), np.float32)
    ts.save(x, chunks=(4, 3, 2))
    arr = ts.open()
    arr[3] = 7.0                          # int index + scalar broadcast
    x[3] = 7.0
    row = np.arange(5, dtype=np.float32)
    arr[-1, 2] = row                      # negative + squeezed-middle axes
    x[-1, 2] = row
    arr[2:4, 6, 1:3] = np.ones((2, 2), np.float32)
    x[2:4, 6, 1:3] = 1.0
    np.testing.assert_array_equal(arr.read(), x)
    # empty selection: no tasks, no I/O, no error
    assert arr.write_at((slice(5, 5),), np.zeros((0, 7, 5))) == []
    fdb.close()


def test_partial_write_sees_own_unflushed_chunks(tmp_path, make_store):
    """RMW fetches flush first (rule 3), so an archive-without-flush
    followed by a partial write must not lose the unflushed data."""
    fdb, ts = make_store("posix")
    x = np.full((8, 8), 3.0, np.float32)
    arr = ts.create(x.shape, x.dtype, chunks=(4, 4))
    arr.write(x, flush=False)             # archived, not yet committed
    arr[1:3, 1:3] = 5.0                   # partial: needs the 3.0 background
    x[1:3, 1:3] = 5.0
    np.testing.assert_array_equal(arr.read(), x)
    fdb.close()


def test_partial_write_lossy_codec_requantises_within_bound(tmp_path, make_store):
    fdb, ts = make_store("daos")
    rng = np.random.default_rng(22)
    x = rng.normal(size=(256, 128)).astype(np.float32)
    ts.save(x, chunks=(128, 128), codec="field8")
    arr = ts.open()
    v = rng.normal(size=(64, 128)).astype(np.float32)
    arr[32:96, :] = v                     # partial chunks: RMW requantises
    x[32:96, :] = v
    got = arr.read()
    bound = (x.max() - x.min()) / 255 * 0.51 + 1e-6
    assert np.abs(got - x).max() <= 2 * bound   # patch + re-encode: 2 passes
    fdb.close()


# ---------------------------------------------------------------------------
# read planning + posix coalescing
# ---------------------------------------------------------------------------

def test_posix_adjacent_chunks_coalesce(tmp_path, make_store):
    """Acceptance: a full read of a posix array with >= 4 adjacent chunks
    per file issues fewer I/O ops than chunks fetched — one writer's chunks
    land adjacent in one data file and merge into single ranged reads."""
    fdb, ts = make_store("posix")
    v = np.arange(64, dtype=np.float32)
    ts.save(v, chunks=(8,))               # 8 adjacent chunks, one file
    arr = ts.open()
    plan = arr.read_plan((slice(None),))
    assert plan.n_chunks == 8
    assert plan.read_ops() < plan.n_chunks
    assert plan.read_ops() == 1           # fully contiguous -> one read
    np.testing.assert_array_equal(plan.execute(), v)
    # the coalesced read really moves fewer ops through the engine meter
    before = GLOBAL_METER.snapshot()
    np.testing.assert_array_equal(arr.read(), v)
    reads = _data_reads(GLOBAL_METER.snapshot()[len(before):])
    assert sum(op.nbytes for op in reads) == v.nbytes
    fdb.close()


def test_object_store_reads_stay_object_granular(tmp_path, make_store):
    """No false coalescing on object backends: one op per chunk stays in
    flight (the object-store side of the paper's trade-off)."""
    for backend in ("daos", "rados", "s3"):
        fdb, ts = make_store(backend, array=f"og-{backend}")
        x = np.zeros((64,), np.float32)
        ts.save(x, chunks=(8,))
        plan = ts.open().read_plan((slice(None),))
        assert plan.read_ops() == plan.n_chunks == 8
        fdb.close()


def test_read_plan_partial_window(tmp_path, make_store):
    fdb, ts = make_store("posix")
    x = np.random.default_rng(23).normal(size=(64, 64)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    plan = arr.read_plan((slice(0, 32), slice(0, 32)))
    assert plan.n_chunks == 4
    assert plan.read_ops() <= 4
    np.testing.assert_array_equal(plan.execute(), x[:32, :32])
    # empty selection: a plan with nothing to do
    empty = arr.read_plan((slice(5, 5), slice(None)))
    assert empty.n_chunks == 0 and empty.read_ops() == 0
    assert empty.execute().shape == (0, 64)
    fdb.close()


# ---------------------------------------------------------------------------
# write planning + posix write coalescing (the WritePlan mirror)
# ---------------------------------------------------------------------------

def test_posix_write_plan_coalesces(tmp_path, make_store):
    """Acceptance: posix write_ops for a multi-chunk write is strictly
    lower than the chunk count — one writer's chunks append into one data
    file, so the whole plan lands as a single batched store write."""
    fdb, ts = make_store("posix")
    v = np.arange(64, dtype=np.float32)
    arr = ts.create(v.shape, v.dtype, chunks=(8,))    # 8 chunks, one file
    plan = arr.write_plan((slice(None),), v)
    assert plan.n_chunks == 8
    assert plan.write_ops() < plan.n_chunks
    assert plan.write_ops() == 1          # one data file -> one append
    locs = plan.execute()
    assert len(locs) == 8
    # locations are exact and adjacent: the read side coalesces them back
    # into one ranged read (write/read op symmetry)
    offs = [loc.offset for loc in locs]
    assert offs == sorted(offs)
    rplan = arr.read_plan((slice(None),))
    assert rplan.read_ops() == 1
    np.testing.assert_array_equal(rplan.execute(), v)
    fdb.close()


def test_object_store_writes_stay_object_granular(tmp_path, make_store):
    """No false write coalescing on object backends: one archive op per
    chunk stays in flight (the other side of the paper's trade-off)."""
    for backend in ("daos", "rados", "s3"):
        fdb, ts = make_store(backend, array=f"wog-{backend}")
        arr = ts.create((64,), np.float32, chunks=(8,))
        plan = arr.write_plan((slice(None),), np.zeros(64, np.float32))
        assert plan.write_ops() == plan.n_chunks == 8
        fdb.close()


def test_write_plan_read_plan_roundtrip(backend, tmp_path, make_store):
    """write_plan -> read_plan round-trips on every backend, including
    ragged edge chunks (batched encode falls back per shape group)."""
    fdb, ts = make_store(backend)
    x = np.random.default_rng(40).normal(size=(37, 53)).astype(np.float32)
    arr = ts.create(x.shape, x.dtype, chunks=(16, 16))
    plan = arr.write_plan((slice(None), slice(None)), x)
    assert plan.n_chunks == 12 and plan.rmw_chunks == 0
    plan.execute()
    np.testing.assert_array_equal(
        arr.read_plan((slice(None), slice(None)),
                      fill_missing=False).execute(), x)
    fdb.close()


def test_write_plan_partial_window_rmw_and_ops(tmp_path, make_store):
    """A window cutting through chunks: the plan reports its RMW split and
    still coalesces every re-archive into one posix write."""
    fdb, ts = make_store("posix")
    x = np.random.default_rng(41).normal(size=(64, 64)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    v = np.random.default_rng(42).normal(size=(30, 30)).astype(np.float32)
    plan = arr.write_plan((slice(10, 40), slice(10, 40)), v)
    assert plan.n_chunks == 9
    assert plan.rmw_chunks == 8           # only the (1,1) chunk is full
    assert plan.write_ops() == 1 < plan.n_chunks
    plan.execute()
    x[10:40, 10:40] = v
    np.testing.assert_array_equal(arr.read(), x)
    fdb.close()


def test_write_window_coalesces_store_writes(tmp_path):
    """The pipeline facade's write_window goes through the same coalesced
    plan: a multi-chunk assimilation window on posix lands as one batched
    store write (observed via the store's append offsets, not just the
    plan's claim)."""
    from repro.data import ChunkedFieldStore
    fs = ChunkedFieldStore("nwp-wco", FDBConfig(backend="posix",
                                                root=str(tmp_path / "fdb")),
                           chunks=(16, 16))
    field = np.zeros((64, 64), np.float32)
    fs.put_field("t2m", field)
    fs.commit()
    arr = fs.open_field("t2m")
    plan = arr.write_plan((slice(0, 32), slice(None)), np.ones((32, 64),
                                                               np.float32))
    assert plan.write_ops() == 1 and plan.n_chunks == 8
    fs.write_window("t2m", np.ones((32, 64), np.float32),
                    slice(0, 32), slice(None))
    fs.commit()
    field[0:32, :] = 1.0
    np.testing.assert_array_equal(fs.read_window("t2m"), field)
    fs.close()


def test_write_plan_flush_barrier_preserved(tmp_path, make_store):
    """FDB rule 3 under batching: a second client sees nothing until the
    writer flushes, then sees everything — and execute(flush=True) is that
    barrier."""
    root = str(tmp_path / "fdb")
    fdb, ts = make_store("posix")
    x = np.arange(64, dtype=np.float32)
    arr = ts.create(x.shape, x.dtype, chunks=(8,))
    arr.write_plan((slice(None),), x).execute(flush=False)
    reader = FDB(FDBConfig(backend="posix", schema="tensor", root=root))
    rts = TensorStore(reader, {"store": "s", "array": "a", "writer": "w0"})
    with pytest.raises(FileNotFoundError):
        rts.open()                        # not yet visible (rule 3)
    fdb.flush()
    reader.catalogue.refresh()
    np.testing.assert_array_equal(rts.open().read(), x)
    reader.close()
    fdb.close()


def test_archive_many_coalesces_on_posix(tmp_path, nwp_identifier):
    """archive_many groups items per destination data file: many fields of
    one (dataset, collocation) land as one batched append, and locations
    still resolve exactly."""
    fdb = FDB(FDBConfig(backend="posix", schema="nwp-posix",
                        root=str(tmp_path / "fdb")))
    items = [({**nwp_identifier, "step": str(i)}, bytes([i]) * 64)
             for i in range(10)]
    unit = fdb.archive_placement(items[0][0]).unit
    assert unit is not None
    assert all(fdb.archive_placement(i).unit == unit for i, _d in items)
    locs = fdb.archive_many(items)
    fdb.flush()
    assert len({loc.unit for loc in locs}) == 1       # one data file
    assert [loc.offset for loc in locs] == sorted(loc.offset for loc in locs)
    for i, (ident, data) in enumerate(items):
        assert fdb.retrieve(ident).read() == data
    fdb.close()


def test_archive_placement_object_backends_none(tmp_path, nwp_identifier):
    for backend in ("daos", "rados", "s3"):
        fdb = FDB(FDBConfig(backend=backend, schema="nwp-object",
                            root=str(tmp_path / "fdb")))
        p = fdb.archive_placement(nwp_identifier)
        assert p.unit is None and not p.mergeable_with(p)
        fdb.close()


def test_archive_batch_rejects_multi_value(nwp_identifier):
    fdb = FDB(FDBConfig(backend="daos"))
    with pytest.raises(ValueError, match="multi-value"):
        fdb.archive_batch([({**nwp_identifier, "step": [0, 6]}, b"x")])
    with pytest.raises(ValueError, match="multi-value"):
        fdb.archive_placement({**nwp_identifier, "step": "0/6"})
    fdb.close()


# ---------------------------------------------------------------------------
# batched codec paths (encode_batch / decode_batch)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec_name", ["raw", "field8", "field16"])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_codec_batch_byte_identical_to_loop(codec_name, dtype):
    """The single-launch batched encode must produce byte-identical
    containers to the per-chunk loop — equal-shape interior chunks, ragged
    tails, and ineligible (tiny) chunks alike — so the two paths
    interoperate on one array."""
    codec = get_codec(codec_name)
    rng = np.random.default_rng(50)
    arrs = [rng.normal(size=(16, 16)).astype(dtype) for _ in range(5)]
    arrs += [rng.normal(size=(5, 131)).astype(dtype)]   # ragged f32 tail
    arrs += [rng.normal(size=(3, 3)).astype(dtype)]     # ineligible -> raw
    batched = codec.encode_batch(arrs)
    looped = [codec.encode(a) for a in arrs]
    assert batched == looped
    shapes = [a.shape for a in arrs]
    dec_b = codec.decode_batch(batched, shapes, np.dtype(dtype))
    for got, data, shape in zip(dec_b, looped, shapes):
        np.testing.assert_array_equal(
            got, codec.decode(data, shape, np.dtype(dtype)))


@pytest.mark.parametrize("bits", [8, 16])
def test_codec_batch_roundtrip_bound(bits):
    codec = get_codec(f"field{bits}")
    rng = np.random.default_rng(51)
    arrs = [rng.normal(size=(32, 128)).astype(np.float32) for _ in range(4)]
    enc = codec.encode_batch(arrs)
    dec = codec.decode_batch(enc, [a.shape for a in arrs], np.float32)
    for a, d in zip(arrs, dec):
        bound = (a.max() - a.min()) / (2 ** bits - 1) * 0.51 + 1e-6
        assert np.abs(d - a).max() <= bound


def test_codec_batch_mixed_written_paths(tmp_path, make_store):
    """Chunks written per-chunk (old data) and batched (new data) decode
    together: the containers are identical, so a batched read of a
    mixed-provenance array just works."""
    fdb, ts = make_store("posix")
    x = np.random.default_rng(52).normal(size=(64, 64)).astype(np.float32)
    ts.save(x, chunks=(16, 16), codec="field16")      # batched write
    arr = ts.open()
    # overwrite two chunks through the per-chunk encode path
    codec = get_codec("field16")
    from repro.tensorstore import chunk_key
    for idx in ((0, 0), (1, 1)):
        tile = x[arr.grid.chunk_slices(idx)]
        fdb.archive(arr.store._ident(chunk_key(idx)), codec.encode(tile))
    fdb.flush()
    got = arr.read()
    bound = (x.max() - x.min()) / 65535 * 0.51 + 1e-6
    assert np.abs(got - x).max() <= bound
    fdb.close()


# ---------------------------------------------------------------------------
# per-FDB io executor (churn fix)
# ---------------------------------------------------------------------------

def test_fdb_io_executor_cached_and_rebuilt(tmp_path):
    fdb = FDB(FDBConfig(backend="daos", io_parallelism=4))
    ex = fdb.io_executor
    assert ex is fdb.io_executor                  # cached, not per-call
    assert ex.max_workers == 4
    fdb.config.io_parallelism = 2                 # config change -> rebuild
    ex2 = fdb.io_executor
    assert ex2 is not ex and ex2.max_workers == 2
    assert ex.is_shutdown                         # old one was shut down
    fdb.close()
    assert ex2.is_shutdown                        # close() shuts it down


def test_fdb_io_executor_not_shared_across_clients():
    a = FDB(FDBConfig(backend="daos"))
    b = FDB(FDBConfig(backend="daos"))
    assert a.io_executor is not b.io_executor
    a.close()
    assert not b.io_executor.is_shutdown          # b unaffected by a.close()
    b.close()


def test_tensorstore_uses_fdb_executor(tmp_path, make_store):
    fdb, ts = make_store("daos")
    assert ts.executor is fdb.io_executor
    fdb.close()


def test_tensorstore_survives_executor_rebuild(tmp_path, make_store):
    """A store must not cache the client's executor: after an
    io_parallelism change rebuilds it, the store's next I/O must ride the
    fresh pool, not a shut-down one."""
    fdb, ts = make_store("daos")
    x = np.arange(64, dtype=np.float32)
    arr = ts.create(x.shape, x.dtype, chunks=(8,))
    arr.write(x)
    fdb.config.io_parallelism = 2         # rebuilds on next access
    assert ts.executor.max_workers == 2
    arr.write(x * 2)                      # would raise on a dead pool
    np.testing.assert_array_equal(arr.read(), x * 2)
    fdb.close()


def test_fdb_io_executor_refuses_after_close():
    """A closed client must not silently mint a fresh pool nothing will
    ever shut down."""
    fdb = FDB(FDBConfig(backend="daos"))
    fdb.close()
    with pytest.raises(RuntimeError, match="closed"):
        fdb.io_executor


def test_posix_placement_is_side_effect_free(tmp_path, nwp_identifier):
    """Resolving a placement (planning a write) must not create files or
    charge the op meter — a plan that is never executed leaves no trace,
    and the data file only appears on the first real archive."""
    import os
    fdb = FDB(FDBConfig(backend="posix", schema="nwp-posix",
                        root=str(tmp_path / "fdb")))
    before = GLOBAL_METER.snapshot()
    p = fdb.archive_placement(nwp_identifier)
    assert p.unit is not None and not os.path.exists(p.unit)
    assert fdb.archive_placement(nwp_identifier).unit == p.unit   # stable
    assert GLOBAL_METER.snapshot()[len(before):] == []    # meter untouched
    fdb.flush()                           # reserved-only entries: no-op
    assert not os.path.exists(p.unit)
    loc = fdb.archive(nwp_identifier, b"x" * 32)
    assert loc.unit == p.unit             # archives land where planned
    fdb.flush()
    assert os.path.exists(p.unit)
    assert fdb.retrieve(nwp_identifier).read() == b"x" * 32
    fdb.close()


# ---------------------------------------------------------------------------
# chunk-grid edge cases
# ---------------------------------------------------------------------------

def test_grid_math_non_divisible():
    g = ChunkGrid((37, 53), (16, 16))
    assert g.n_chunks == (3, 4)
    assert g.chunk_shape((2, 3)) == (5, 5)          # clipped corner
    hits = list(g.intersecting((slice(30, 37), slice(48, 53))))
    assert {h[0] for h in hits} == {(1, 3), (2, 3)}


def test_grid_oversize_chunks_clip():
    g = ChunkGrid((10, 10), (64, 64))
    assert g.chunks == (10, 10) and g.n_chunks == (1, 1)


def test_grid_rejects_bad_args():
    with pytest.raises(ValueError):
        ChunkGrid((4, 4), (4,))
    with pytest.raises(ValueError):
        ChunkGrid((4,), (0,))


def test_grid_empty_selection_and_negative_indices():
    g = ChunkGrid((9, 7), (4, 3))
    sel, squeeze = g.normalize_key((slice(5, 5), slice(None)))
    assert g.selection_shape(sel) == (0, 7) and squeeze == ()
    assert list(g.intersecting(sel)) == []
    # negative integer indices resolve from the end and record squeezes
    sel, squeeze = g.normalize_key((-1, -7))
    assert sel == (slice(8, 9, 1), slice(0, 1, 1)) and squeeze == (0, 1)
    with pytest.raises(IndexError):
        g.normalize_key((-10, 0))
    # reversed slices clamp to empty rather than going negative
    sel, _ = g.normalize_key((slice(6, 2), slice(None)))
    assert g.selection_shape(sel) == (0, 7)


def test_grid_zero_length_dims():
    g = ChunkGrid((0, 4), (2, 2))
    assert g.n_chunks == (0, 2) and g.chunk_count == 0
    assert list(g.all_indices()) == []
    sel, _ = g.normalize_key((slice(None), slice(None)))
    assert g.selection_shape(sel) == (0, 4)
    assert list(g.intersecting(sel)) == []


def test_grid_write_plan_full_vs_partial():
    g = ChunkGrid((37, 53), (16, 16))
    # full-array selection covers every chunk, clipped edge chunks included
    sel, _ = g.normalize_key((slice(None), slice(None)))
    plan = list(g.write_plan(sel))
    assert len(plan) == 12 and all(full for *_x, full in plan)
    # a window ending mid-chunk: aligned chunks are full, the last partial
    sel, _ = g.normalize_key((slice(16, 32), slice(16, 50)))
    by_idx = {idx: full for idx, _c, _v, full in g.write_plan(sel)}
    assert by_idx == {(1, 1): True, (1, 2): True, (1, 3): False}
    # a clipped edge chunk covered to the array boundary counts as full
    sel, _ = g.normalize_key((slice(32, 37), slice(48, 53)))
    assert list(g.write_plan(sel)) == [
        ((2, 3), (slice(0, 5, 1), slice(0, 5, 1)),
         (slice(0, 5, 1), slice(0, 5, 1)), True)]


def test_store_zero_length_dim_roundtrip(tmp_path, make_store):
    fdb, ts = make_store("daos", array="empty")
    x = np.zeros((0, 4), np.float32)
    ts.save(x, chunks=(2, 2))
    arr = ts.open()
    assert arr.read().shape == (0, 4)
    assert arr.write_at((slice(None), slice(None)), x) == []
    fdb.close()


def test_indexing_edge_cases(tmp_path, make_store):
    fdb, ts = make_store("daos")
    x = np.random.default_rng(4).normal(size=(9, 7, 5)).astype(np.float32)
    ts.save(x, chunks=(4, 3, 2))
    arr = ts.open()
    np.testing.assert_array_equal(arr[3], x[3])              # int → squeeze
    np.testing.assert_array_equal(arr[-2, 1:], x[-2, 1:])    # negative index
    np.testing.assert_array_equal(arr[:, -3:, 4], x[:, -3:, 4])
    assert arr[2:2].size == 0                                # empty selection
    np.testing.assert_array_equal(arr[::2], x[::2])          # strided reads
    np.testing.assert_array_equal(arr[1::3, :, 4], x[1::3, :, 4])
    np.testing.assert_array_equal(arr[::-1], x[::-1])        # reversed reads
    np.testing.assert_array_equal(arr[8:2:-2, ::-1], x[8:2:-2, ::-1])
    arr[::-1] = x[::-1]                     # reversed writes: roundtrip
    np.testing.assert_array_equal(arr[:, :, :], x)
    with pytest.raises(IndexError):
        arr[0, 0, 0, 0]
    fdb.close()


def test_scalar_and_1d_arrays(tmp_path, make_store):
    fdb, ts = make_store("rados", array="scalar")
    ts.save(np.float32(3.25))
    assert ts.open().read() == np.float32(3.25)
    ts2 = TensorStore(fdb, {"store": "s", "array": "vec", "writer": "w0"})
    v = np.arange(1000, dtype=np.int64)
    ts2.save(v, chunks=(64,))
    np.testing.assert_array_equal(ts2.open()[128:700], v[128:700])
    fdb.close()


def test_auto_chunks_targets_size():
    chunks = auto_chunks((4096, 4096), np.float32, target_bytes=1 << 20)
    nbytes = chunks[0] * chunks[1] * 4
    assert nbytes <= 1 << 20
    assert auto_chunks((), np.float32) == ()
    assert auto_chunks((3,), np.float32) == (3,)


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["daos", "posix"])
def test_codec_parity_on_off(backend, tmp_path):
    """field8/field16 vs raw: lossy within the block-quantisation bound,
    identical shape/dtype, raw stays exact."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(300, 200)).astype(np.float32)
    fdb = FDB(FDBConfig(backend=backend, schema="tensor",
                        root=str(tmp_path / "fdb")))
    got = {}
    for codec in ("raw", "field8", "field16"):
        ts = TensorStore(fdb, {"store": "s", "array": f"a-{codec}",
                               "writer": "w0"})
        ts.save(x, chunks=(128, 128), codec=codec)
        got[codec] = ts.open().read()
        assert got[codec].shape == x.shape and got[codec].dtype == x.dtype
    np.testing.assert_array_equal(got["raw"], x)
    rng_x = x.max() - x.min()
    assert np.abs(got["field8"] - x).max() <= rng_x / 255 * 0.51 + 1e-6
    assert np.abs(got["field16"] - x).max() <= rng_x / 65535 * 0.51 + 1e-6
    assert np.abs(got["field16"] - x).max() < np.abs(got["field8"] - x).max()
    fdb.close()


def test_quant_codec_falls_back_to_raw_for_ints_and_tiny_chunks(tmp_path, make_store):
    fdb, ts = make_store("daos", array="ints")
    ints = np.arange(600, dtype=np.int32).reshape(30, 20)
    ts.save(ints, chunks=(16, 16), codec="field8")   # ineligible → raw marker
    np.testing.assert_array_equal(ts.open().read(), ints)
    fdb.close()


def test_codec_container_roundtrip_odd_tail():
    """Sizes that are not multiples of 128 carry an exact float tail."""
    codec = get_codec("field8")
    x = np.random.default_rng(6).normal(size=(5, 131)).astype(np.float32)
    y = codec.decode(codec.encode(x), x.shape, x.dtype)
    assert y.shape == x.shape
    # head quantised, tail exact
    tail = x.reshape(-1)[(x.size // 128) * 128:]
    np.testing.assert_array_equal(y.reshape(-1)[(x.size // 128) * 128:], tail)


def test_unknown_codec_rejected(tmp_path, make_store):
    fdb, ts = make_store("daos")
    with pytest.raises(ValueError):
        ts.create((4, 4), np.float32, codec="zstd")
    fdb.close()


# ---------------------------------------------------------------------------
# executor + archive_many
# ---------------------------------------------------------------------------

def test_executor_bounded_in_flight():
    ex = ChunkExecutor(max_workers=4, max_in_flight=2)

    def task(i):
        time.sleep(0.01)
        return i * i

    results = ex.map_ordered(task, range(12))
    assert results == [i * i for i in range(12)]
    assert ex.peak_in_flight <= 2
    ex.shutdown()


def test_executor_propagates_errors_in_order():
    ex = ChunkExecutor(max_workers=2)

    def task(i):
        if i == 3:
            raise RuntimeError("chunk 3 failed")
        return i

    with pytest.raises(RuntimeError, match="chunk 3"):
        ex.map_ordered(task, range(6))
    ex.shutdown()


def test_executor_propagates_client_context():
    from repro.core import client_context
    from repro.core.engine.meter import current_client
    ex = ChunkExecutor(max_workers=2)
    with client_context("proc7@node3"):
        seen = ex.map_ordered(lambda _i: current_client(), range(4))
    assert seen == ["proc7@node3"] * 4
    ex.shutdown()


def test_archive_many_returns_locations(backend, tmp_path, nwp_identifier):
    schema = "nwp-posix" if backend == "posix" else "nwp-object"
    fdb = FDB(FDBConfig(backend=backend, schema=schema,
                        root=str(tmp_path / "fdb")))
    items = [({**nwp_identifier, "step": str(i)}, bytes([i]) * 256)
             for i in range(12)]
    locs = fdb.archive_many(items)
    fdb.flush()
    assert len(locs) == 12
    assert all(isinstance(loc, FieldLocation) for loc in locs)
    # locations come back in input order and resolve to the right payloads
    for i, loc in enumerate(locs):
        assert fdb.store.retrieve(loc).read() == bytes([i]) * 256
    for i in range(12):
        assert fdb.retrieve({**nwp_identifier, "step": str(i)}).read() \
            == bytes([i]) * 256
    fdb.close()


@pytest.mark.parametrize("persistence", ["immediate", "on_flush"])
def test_parallel_archive_rados_span_mode_consistent(tmp_path, persistence,
                                                     nwp_identifier):
    """Span mode appends into shared objects: under parallel archive the
    physical append order must match the reserved offsets, or locations
    would point at other items' bytes."""
    fdb = FDB(FDBConfig(backend="rados", schema="nwp-object",
                        rados_object_mode="span",
                        rados_persistence=persistence,
                        rados_max_object_size=4096))
    items = [({**nwp_identifier, "step": str(i)},
              bytes([i % 251]) * (100 + (i % 7) * 13))
             for i in range(200)]
    locs = fdb.archive_many(items, parallelism=16)
    fdb.flush()
    for (ident, data), loc in zip(items, locs):
        assert fdb.retrieve(ident).read() == data, ident
        assert fdb.store.retrieve(loc).read() == data
    fdb.close()


def test_archive_many_serial_path_equivalent(tmp_path, nwp_identifier):
    fdb = FDB(FDBConfig(backend="daos", io_parallelism=0))
    items = [({**nwp_identifier, "step": str(i)}, b"z" * 64) for i in range(3)]
    locs = fdb.archive_many(items)
    assert len(locs) == 3
    fdb.close()


# ---------------------------------------------------------------------------
# integrations: checkpoint + data pipeline
# ---------------------------------------------------------------------------

def test_checkpoint_partial_tensor_read():
    from repro.train.checkpoint import FDBCheckpointer
    ck = FDBCheckpointer("ts-part", FDBConfig(backend="daos"), n_shards=4)
    w = np.random.default_rng(7).normal(size=(256, 64)).astype(np.float32)
    ck.save(3, {"w": w})
    arr = ck.open_tensor(3, "w")
    assert arr.n_chunks[0] == 4                   # n_shards → axis-0 bands
    np.testing.assert_array_equal(arr[100:200], w[100:200])
    ck.close()


def test_chunked_field_store_window_read(tmp_path):
    from repro.data import ChunkedFieldStore
    fs = ChunkedFieldStore("nwp", FDBConfig(backend="rados"),
                           chunks=(32, 32))
    field = np.random.default_rng(8).normal(size=(100, 90)).astype(np.float32)
    fs.put_field("t2m", field)
    fs.commit()
    np.testing.assert_array_equal(
        fs.read_window("t2m", slice(10, 60), slice(40, 80)),
        field[10:60, 40:80])
    np.testing.assert_array_equal(fs.read_window("t2m"), field)
    fs.wipe_field("t2m")
    with pytest.raises(FileNotFoundError):
        fs.open_field("t2m")
    fs.close()


def test_chunked_field_store_window_write(tmp_path):
    """The assimilation pattern: patch a window of an archived field, commit
    once, and consumers see the increment."""
    from repro.data import ChunkedFieldStore
    fs = ChunkedFieldStore("nwp-asml", FDBConfig(backend="posix",
                                                 root=str(tmp_path / "fdb")),
                           chunks=(32, 32))
    field = np.random.default_rng(30).normal(size=(100, 90)
                                             ).astype(np.float32)
    fs.put_field("t2m", field)
    fs.commit()
    inc = np.random.default_rng(31).normal(size=(50, 40)).astype(np.float32)
    fs.write_window("t2m", field[10:60, 40:80] + inc,
                    slice(10, 60), slice(40, 80))
    fs.commit()
    field[10:60, 40:80] += inc
    np.testing.assert_array_equal(fs.read_window("t2m"), field)
    fs.close()


def test_checkpoint_update_tensor_in_place():
    """Optimizer-state touch-up: patch rows of a saved tensor; only the
    intersecting chunks are re-archived and restore sees the update."""
    from repro.train.checkpoint import FDBCheckpointer
    ck = FDBCheckpointer("ts-upd", FDBConfig(backend="daos"), n_shards=4)
    mu = np.random.default_rng(32).normal(size=(256, 64)).astype(np.float32)
    ck.save(7, {"w": np.zeros((8, 8), np.float32)}, opt_state={"mu": mu})
    new_rows = np.random.default_rng(33).normal(size=(50, 64)
                                                ).astype(np.float32)
    ck.update_tensor(7, "mu", slice(100, 150), new_rows, kind="opt")
    mu[100:150] = new_rows
    got = ck.restore(7, {"mu": mu}, kind="opt")
    np.testing.assert_array_equal(np.asarray(got["mu"]), mu)
    ck.close()


def test_checkpoint_restore_refuses_partial_chunked_tensor():
    """Restore reads strictly: a chunked checkpoint tensor with a missing
    chunk (lost data) raises instead of silently zero-filling."""
    from repro.train.checkpoint import FDBCheckpointer
    ck = FDBCheckpointer("ts-strict", FDBConfig(backend="daos"))
    w = np.ones((64, 32), np.float32)
    ck.save(1, {"w": w})
    # simulate lost chunks: wipe the step, then re-create metadata only
    ck.fdb.wipe({"run": "ts-strict", "kind": "params", "step": "1"})
    ck._tensor_store("params", 1, "w").create(w.shape, w.dtype,
                                              chunks=(16, 32))
    ck.fdb.flush()
    with pytest.raises(KeyError, match="missing chunk"):
        ck.restore(1, {"w": w})
    ck.close()


# ---------------------------------------------------------------------------
# FDB facade regressions (bugfix sweep)
# ---------------------------------------------------------------------------

def test_fdb_non_string_identifier_values(nwp_identifier):
    """Identifier values may be ints/floats everywhere, and sequence values
    are multi-value request expressions — normalised in one shared place."""
    fdb = FDB(FDBConfig(backend="daos"))
    base = {**nwp_identifier}
    del base["step"]
    for step in (0, 6, 12):
        fdb.archive({**base, "step": step}, bytes([step]) * 16)
    fdb.flush()
    assert fdb.retrieve({**base, "step": 0}).read() == bytes(16)
    # a sequence value expands like the "0/12" request expression
    assert fdb.retrieve({**base, "step": [0, 12]}).length() == 32
    assert fdb.retrieve({**base, "step": "0/12"}).length() == 32
    # unordered sets sort, so the concatenated payload order is stable
    assert fdb.retrieve({**base, "step": {12, 0}}).read() \
        == bytes(16) + bytes([12]) * 16
    assert fdb.axes({**base, "step": 0}, "step") == {"0", "6", "12"}
    # archive must be fully specified: an expression value would catalogue
    # the object under a key no retrieve can expand back to
    with pytest.raises(ValueError, match="multi-value"):
        fdb.archive({**base, "step": [0, 6]}, b"x")
    with pytest.raises(ValueError, match="multi-value"):
        fdb.archive({**base, "step": "0/6"}, b"x")
    fdb.close()


def test_lustre_sim_keyed_on_stripe_geometry(tmp_path):
    """Two FDBs sharing a root but differing in OST/stripe geometry must not
    share a LustreSim, or geometry sweeps measure the first config forever."""
    root = str(tmp_path / "fdb")
    a = FDB(FDBConfig(backend="posix", schema="tensor", root=root,
                      lustre_stripe_count=1))
    b = FDB(FDBConfig(backend="posix", schema="tensor", root=root,
                      lustre_stripe_count=8))
    c = FDB(FDBConfig(backend="posix", schema="tensor", root=root,
                      lustre_stripe_count=1))
    assert a.store.sim is not b.store.sim
    assert a.store.sim is c.store.sim     # same geometry still shares
    assert a.store.sim.stripe_count == 1 and b.store.sim.stripe_count == 8
    a.close(), b.close(), c.close()


# ---------------------------------------------------------------------------
# strided selections (read + write paths)
# ---------------------------------------------------------------------------

def test_strided_read_roundtrip(backend, tmp_path, make_store):
    """Positive-step selections match numpy on every backend, including
    steps larger than the chunk and offset starts."""
    fdb, ts = make_store(backend)
    x = np.random.default_rng(60).normal(size=(37, 53)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    for sel in [(slice(None, None, 2),),
                (slice(1, 30, 3), slice(0, None, 4)),
                (slice(None, None, 17), slice(5, None, 23)),
                (slice(0, 37, 16), slice(52, 53, 7)),
                (2, slice(1, None, 5))]:
        np.testing.assert_array_equal(arr[sel], x[sel], err_msg=str(sel))
    fdb.close()


def test_strided_write_roundtrip(backend, tmp_path, make_store):
    """Strided assignment preserves the stride gaps (RMW) on every
    backend."""
    fdb, ts = make_store(backend)
    x = np.random.default_rng(61).normal(size=(37, 53)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    v = np.random.default_rng(62).normal(
        size=x[2::5, 1::7].shape).astype(np.float32)
    arr[2::5, 1::7] = v
    x[2::5, 1::7] = v
    np.testing.assert_array_equal(arr.read(), x)
    arr[::2] = 0.0                       # broadcast over a strided selection
    x[::2] = 0.0
    np.testing.assert_array_equal(arr.read(), x)
    fdb.close()


def test_strided_read_skips_strided_over_chunks(tmp_path, make_store):
    """A step larger than the chunk touches only the chunks holding a
    selected point — observed via planned chunk count AND the meter."""
    fdb, ts = make_store("daos")
    x = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    ts.save(x, chunks=(16, 16))          # 4 x 4 chunk grid
    arr = ts.open()
    plan = arr.read_plan((slice(None, None, 32), slice(None, None, 32)))
    assert plan.n_chunks == 4            # rows 0/32 x cols 0/32 -> 4 chunks
    before = GLOBAL_METER.snapshot()
    np.testing.assert_array_equal(plan.execute(), x[::32, ::32])
    reads = _data_reads(GLOBAL_METER.snapshot()[len(before):])
    assert len(reads) == 4
    # strided writes classify as RMW (stride gaps must be preserved)
    wplan = arr.write_plan((slice(None, None, 2), slice(None)),
                           np.zeros((32, 64), np.float32))
    assert wplan.n_chunks == 16 and wplan.rmw_chunks == 16
    fdb.close()


def test_negative_step_read_roundtrip(backend, tmp_path, make_store):
    """Reversed reads on every backend: normalised to a positive-step plan
    plus one client-side flip, so results match numpy exactly."""
    fdb, ts = make_store(backend)
    x = np.random.default_rng(11).normal(size=(37, 53)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    for sel in [
        (slice(None, None, -1), slice(None)),
        (slice(None, None, -1), slice(None, None, -1)),
        (slice(30, 4, -3), slice(50, None, -7)),
        (slice(None, None, -16),),           # step larger than the chunk
        (5, slice(None, None, -2)),          # int squeeze + reversed
        (slice(2, 2, -1), slice(None)),      # empty reversed slice
    ]:
        np.testing.assert_array_equal(arr[sel], x[sel], err_msg=str(sel))
    # the plan only touches chunks holding selected points, same as the
    # forward equivalent
    plan = arr.read_plan((slice(None, None, -16), slice(None, None, -16)))
    fwd = arr.read_plan((slice(36, None, -16), slice(52, None, -16)))
    assert plan.n_chunks == fwd.n_chunks
    # only reshards keep rejecting reversed selections (a re-layout has no
    # meaning for a descending source order)
    with pytest.raises(NotImplementedError, match="positive step"):
        arr.reshard_plan((8, 53), sel=(slice(None, None, -1), slice(None)))
    fdb.close()


def test_negative_step_write_roundtrip(backend, tmp_path, make_store):
    """Reversed assignment on every backend: the values flip client-side
    against the positive-step mirror plan, so results match numpy's
    reversed-assignment semantics exactly."""
    fdb, ts = make_store(backend)
    rng = np.random.default_rng(63)
    x = rng.normal(size=(37, 53)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    for sel in [
        (slice(None, None, -1), slice(None)),        # full reverse
        (slice(30, 4, -3), slice(None)),             # strided reverse
        (slice(None, None, -2), slice(50, 3, -7)),   # both axes reversed
        (slice(None, None, -16),),                   # step > chunk
        (5, slice(None, None, -2)),                  # int squeeze + reverse
    ]:
        v = rng.normal(size=x[sel].shape).astype(np.float32)
        arr[sel] = v
        x[sel] = v
        np.testing.assert_array_equal(arr.read(), x, err_msg=str(sel))
    # broadcast onto a reversed selection (scalar and row)
    arr[::-1, ::2] = 3.5
    x[::-1, ::2] = 3.5
    np.testing.assert_array_equal(arr.read(), x)
    row = rng.normal(size=(53,)).astype(np.float32)
    arr[10:2:-4] = row
    x[10:2:-4] = row
    np.testing.assert_array_equal(arr.read(), x)
    # empty reversed selection: clean no-op
    arr[2:2:-1] = 99.0
    np.testing.assert_array_equal(arr.read(), x)
    fdb.close()


@pytest.mark.parametrize("backend", ["daos", "posix"])
def test_zero_length_selections(backend, tmp_path, make_store):
    """Empty selections are clean no-ops on read, write and reshard:
    empty arrays out, empty values in, zero planned I/O ops."""
    fdb, ts = make_store(backend)
    x = np.arange(36, dtype=np.float32).reshape(6, 6)
    ts.save(x, chunks=(2, 2))
    arr = ts.open()
    # reads
    assert arr[3:3].shape == (0, 6)
    assert arr[5:5:3, 1:4].shape == (0, 3)      # empty strided window
    assert arr[2:2, 4:4].size == 0
    rp = arr.read_plan((slice(3, 3), slice(None)))
    assert rp.n_chunks == 0 and rp.read_ops() == 0
    # writes: empty value arrays are accepted, nothing is archived
    wp = arr.write_plan((slice(3, 3), slice(None)),
                        np.zeros((0, 6), np.float32))
    assert wp.n_chunks == 0 and wp.write_ops() == 0 and wp.leases == []
    assert wp.execute() == []
    arr[4:4] = 7.0                               # broadcast onto empty: noop
    arr[0:0, 0:0] = np.zeros((0, 0), np.float32)
    np.testing.assert_array_equal(arr.read(), x)
    # reshard of an empty sub-selection: a valid empty array, no data I/O
    arr.reshard((2, 2), sel=(slice(3, 3), slice(None)))
    assert arr.shape == (0, 6)
    assert arr.read().shape == (0, 6)
    fdb.close()


@pytest.mark.parametrize("backend", ["daos", "posix"])
def test_garbage_report_after_reshard_and_recreate(backend, tmp_path, make_store):
    """garbage_report counts retained old-generation chunk bytes — the
    versioned-retain cost of reshards and on_mismatch='retain' re-creates
    (and only that: a fresh array reports zero garbage)."""
    from repro.tensorstore import GarbageReport
    fdb, ts = make_store(backend)
    x = np.random.default_rng(5).normal(size=(32, 32)).astype(np.float32)
    arr = ts.save(x, chunks=(8, 8))              # 16 chunks x 256 B
    rep = ts.garbage_report()
    assert isinstance(rep, GarbageReport)
    assert rep.live_generation == 0 and rep.live_chunks == 16
    assert rep.live_bytes == x.nbytes and rep.garbage_bytes == 0
    arr.reshard((16, 32))                        # gen 0 -> versioned garbage
    rep = ts.garbage_report()
    assert rep.live_generation == 1 and rep.live_chunks == 2
    assert rep.garbage_chunks == 16 and rep.garbage_bytes == x.nbytes
    assert rep.garbage_generations == (0,)
    # a retain re-create strands generation 1's chunks as well
    ts.create((32, 32), np.float32, chunks=(4, 4), on_mismatch="retain")
    fdb.flush()
    rep = ts.garbage_report()
    assert rep.live_generation == 2 and rep.live_chunks == 0
    assert rep.garbage_chunks == 18 and rep.garbage_generations == (0, 1)
    assert rep.garbage_bytes == 2 * x.nbytes
    fdb.close()


def test_grid_linear_id_and_merge_ranges():
    from repro.tensorstore import merge_id_ranges
    g = ChunkGrid((37, 53), (16, 16))            # (3, 4) chunk grid
    ids = [g.linear_id(idx) for idx in g.all_indices()]
    assert ids == list(range(12))                # row-major, dense
    assert g.linear_id((2, 3)) == 11
    with pytest.raises(IndexError):
        g.linear_id((3, 0))
    assert merge_id_ranges([0, 1, 2, 7, 8]) == [(0, 3), (7, 9)]
    assert merge_id_ranges([3, 1, 1, 2]) == [(1, 4)]     # dups + unsorted
    assert merge_id_ranges([]) == []
    # a row band of chunks leases as ONE contiguous range; a column band
    # fragments into one range per chunk row
    row_band = [g.linear_id(idx) for idx, _c, _o in g.intersecting(
        g.normalize_key((slice(0, 16), slice(None)))[0])]
    assert merge_id_ranges(row_band) == [(0, 4)]
    col_band = [g.linear_id(idx) for idx, _c, _o in g.intersecting(
        g.normalize_key((slice(None), slice(0, 16)))[0])]
    assert merge_id_ranges(col_band) == [(0, 1), (4, 5), (8, 9)]


def test_grid_strided_math():
    g = ChunkGrid((37, 53), (16, 16))
    sel, squeeze = g.normalize_key((slice(None, None, 5), slice(1, 50, 9)))
    assert squeeze == ()
    assert sel[0] == slice(0, 36, 5)     # stop normalised to last + 1
    assert sel[1] == slice(1, 47, 9)
    assert g.selection_shape(sel) == (8, 6)
    hits = list(g.intersecting(sel))
    # every selected point lands in exactly one (chunk, out) slot
    seen = np.zeros((8, 6), bool)
    for idx, chunk_sel, out_sel in hits:
        block = np.zeros(g.chunk_shape(idx), bool)
        block[chunk_sel] = True
        assert block.sum() == (out_sel[0].stop - out_sel[0].start) * \
            (out_sel[1].stop - out_sel[1].start)
        assert not seen[out_sel].any()
        seen[out_sel] = True
    assert seen.all()
    # a chunk the stride steps over entirely is not visited
    g2 = ChunkGrid((64,), (8,))
    idxs = [idx for idx, _c, _o in g2.intersecting(
        g2.normalize_key((slice(0, None, 24),))[0])]
    assert idxs == [(0,), (3,), (6,)]    # points 0, 24, 48
    # full coverage requires step 1 unless the chunk dim is size 1
    sel, _ = g2.normalize_key((slice(None, None, 2),))
    assert all(not full for *_x, full in g2.write_plan(sel))
    g3 = ChunkGrid((4, 1), (2, 1))
    sel, _ = g3.normalize_key((slice(None), slice(None, None, 3)))
    assert all(full for *_x, full in g3.write_plan(sel))
    # write/reshard normalisation still rejects negative steps; the read
    # path serves them via normalize_read_key (positive plan + flip)
    with pytest.raises(NotImplementedError, match="positive step"):
        g.normalize_key((slice(None, None, -1),))
    sel, squeeze, flips = g.normalize_read_key(
        (slice(None, None, -5), slice(49, None, -9)))
    assert squeeze == () and flips == (0, 1)
    assert sel[0] == slice(1, 37, 5)     # 36, 31, ... 1 ascending
    assert sel[1] == slice(4, 50, 9)     # 49, 40, ... 4 ascending
    sel, _sq, flips = g.normalize_read_key((slice(2, 2, -1), slice(None)))
    assert g.selection_shape(sel) == (0, 53)    # empty reversed slice
    assert flips == ()


# ---------------------------------------------------------------------------
# RMW fetch coalescing + window-bounded write staging
# ---------------------------------------------------------------------------

def test_rmw_fetches_coalesce_on_posix(tmp_path, make_store):
    """Partial-write RMW fetches route through a whole-chunk ReadPlan:
    adjacent posix chunks fetch as ONE ranged read, not one per chunk."""
    from repro.tensorstore import ReadPlan
    fdb, ts = make_store("posix")
    v = np.arange(64, dtype=np.float32)
    ts.save(v, chunks=(8,))              # 8 adjacent chunks, one file
    arr = ts.open()
    fetch = ReadPlan.for_chunks(arr, [(i,) for i in range(8)])
    assert fetch.read_ops() == 1         # all eight coalesce
    chunks = fetch.read_chunks()
    np.testing.assert_array_equal(np.concatenate(chunks), v)
    assert all(c.flags.writeable for c in chunks)
    # end to end: a strided write (all chunks partial) moves the fetch
    # bytes through the meter as coalesced reads
    before = GLOBAL_METER.snapshot()
    arr[::2] = -1.0
    reads = _data_reads(GLOBAL_METER.snapshot()[len(before):])
    assert sum(op.nbytes for op in reads) == v.nbytes   # fetched once
    v[::2] = -1.0
    np.testing.assert_array_equal(arr.read(), v)
    fdb.close()


def test_read_plan_for_chunks_missing_fill(tmp_path, make_store):
    from repro.tensorstore import ReadPlan
    fdb, ts = make_store("daos")
    arr = ts.create((16,), np.float32, chunks=(4,))
    arr[0:4] = 7.0                       # only chunk 0 exists
    chunks = ReadPlan.for_chunks(arr, [(0,), (2,)]).read_chunks()
    np.testing.assert_array_equal(chunks[0], np.full(4, 7.0, np.float32))
    np.testing.assert_array_equal(chunks[1], np.zeros(4, np.float32))
    with pytest.raises(KeyError, match="missing chunk"):
        ReadPlan.for_chunks(arr, [(2,)], fill_missing=False)
    with pytest.raises(TypeError, match="read_chunks"):
        ReadPlan.for_chunks(arr, [(0,)]).execute()
    fdb.close()


def test_write_plan_staged_by_executor_window(tmp_path):
    """A plan larger than the executor window stages its encodes: one
    batched posix write per stage (write_ops = ceil(chunks/window)), never
    the whole plan's tiles at once."""
    from repro.tensorstore import ChunkExecutor
    fdb = FDB(FDBConfig(backend="posix", schema="tensor",
                        root=str(tmp_path / "fdb")))
    ex = ChunkExecutor(max_workers=2, max_in_flight=2)
    ts = TensorStore(fdb, {"store": "s", "array": "a", "writer": "w0"},
                     executor=ex)
    v = np.arange(64, dtype=np.float32)
    arr = ts.create(v.shape, v.dtype, chunks=(8,))    # 8 chunks, window 2
    plan = arr.write_plan((slice(None),), v)
    assert plan.window == 2
    assert [len(s) for s in plan.stages] == [2, 2, 2, 2]
    assert plan.write_ops() == 4 < plan.n_chunks
    locs = plan.execute()
    offs = [loc.offset for loc in locs]
    assert offs == sorted(offs)          # stages append in plan order
    np.testing.assert_array_equal(arr.read(), v)
    assert arr.read_plan((slice(None),)).read_ops() == 1
    ex.shutdown()
    fdb.close()


# ---------------------------------------------------------------------------
# staged reads: a stage of missed chunks decodes in one batched call
# ---------------------------------------------------------------------------

def _staged_store(backend, tmp_path, window=32, cache_bytes=0):
    """(fdb, store, executor) with a known executor window, traced so the
    codec's ambient counters count."""
    from repro.obs import Tracer
    fdb = FDB(FDBConfig(backend=backend, schema="tensor",
                        root=str(tmp_path / "fdb"),
                        chunk_cache_bytes=cache_bytes),
              tracer=Tracer(enabled=True))
    ex = ChunkExecutor(max_workers=4, max_in_flight=window)
    ts = TensorStore(fdb, {"store": "s", "array": "a", "writer": "w0"},
                     executor=ex)
    return fdb, ts, ex


def _per_chunk(arr):
    """The whole array as per-chunk ``Codec.decode`` gives it (chunks
    never written as zeros)."""
    out = np.zeros(arr.shape, arr.dtype)
    for idx in itertools.product(*(range(n) for n in arr.n_chunks)):
        data = arr.store.fdb.retrieve(arr.chunk_ident(idx)).read()
        if data:
            out[arr.grid.chunk_slices(idx)] = arr._codec.decode(
                data, arr.grid.chunk_shape(idx), arr.dtype)
    return out


def _warm(arr):
    """Run every launch shape of the array's chunk geometries once, so the
    launches a test sees next are the read's own."""
    first = {}
    for idx in itertools.product(*(range(n) for n in arr.n_chunks)):
        data = arr.store.fdb.retrieve(arr.chunk_ident(idx)).read()
        if data:
            first.setdefault(arr.grid.chunk_shape(idx), data)
    for shape, data in first.items():
        arr._codec.decode_batch([data] * 3, [shape] * 3, arr.dtype)


@pytest.fixture
def launches(monkeypatch):
    """Leading batch dimension of every field_decode launch, in order."""
    from repro.kernels import ops
    seen = []
    real = ops.field_decode

    def field_decode(q, *args, **kw):
        seen.append(q.shape[0])
        return real(q, *args, **kw)
    monkeypatch.setattr(ops, "field_decode", field_decode)
    return seen


def _launch_count(fdb):
    return fdb.metrics().get("codec.decode_launches", {}).get("value", 0)


@pytest.mark.parametrize("backend", ["daos", "posix"])
@pytest.mark.parametrize("key", [
    (slice(None), slice(None)),                    # the whole array
    (slice(3, 61), slice(100, 900)),               # contiguous window
    (slice(None, None, 3), slice(7, None, 97)),    # strided
    (slice(None, None, -2), slice(900, 50, -33)),  # negative steps
    (5, slice(None)),                              # scalar index
    (-1, 1000),                                    # scalars only
])
def test_staged_read_matches_per_chunk_decode(backend, key, tmp_path):
    """70 rows of two (1, 400) chunks and a ragged (1, 300) edge chunk:
    every selection reads back byte-identical to per-chunk decodes."""
    fdb, ts, ex = _staged_store(backend, tmp_path)
    x = np.random.default_rng(60).normal(size=(70, 1100)).astype(np.float32)
    arr = ts.save(x, chunks=(1, 400), codec="field16")
    want = _per_chunk(arr)[key]
    got = arr.read_plan(key).execute()
    assert got.shape == np.shape(want) and got.dtype == want.dtype
    assert got.tobytes() == np.ascontiguousarray(want).tobytes()
    ex.shutdown()
    fdb.close()


@pytest.mark.parametrize("backend,stages", [
    ("daos", [32, 32, 6]),          # one op per chunk, staged
    ("posix", [70]),                # one coalesced read, one stage
])
def test_staged_read_launches(backend, stages, tmp_path, launches):
    """70 same-shape misses under a window of 32: launches of at most 32
    chunks, each at a power-of-two batch, the last padded; the counter
    counts exactly those launches."""
    fdb, ts, ex = _staged_store(backend, tmp_path)
    x = np.random.default_rng(61).normal(size=(70, 384)).astype(np.float32)
    arr = ts.save(x, chunks=(1, 384), codec="field8")
    want = _per_chunk(arr)
    _warm(arr)
    launches.clear()
    plan = arr.read_plan((slice(None), slice(None)))
    assert plan.window == 32
    assert [sum(len(plan.batches[b][0]) for b in st)
            for st in plan.stages] == stages
    before = _launch_count(fdb)
    got = plan.execute()
    assert sorted(launches) == [8, 32, 32]
    assert _launch_count(fdb) - before == 3
    assert got.tobytes() == want.tobytes()
    ex.shutdown()
    fdb.close()


@pytest.mark.parametrize("backend", ["daos", "posix"])
@pytest.mark.parametrize("key,batch", [
    ((0, slice(0, 400)), [1]),              # one chunk: B = 1, as before
    ((0, slice(None)), [2, 1]),             # the ragged edge: its own group
])
def test_small_plans_launch_per_geometry(backend, key, batch, tmp_path,
                                         launches):
    fdb, ts, ex = _staged_store(backend, tmp_path)
    x = np.random.default_rng(62).normal(size=(2, 1100)).astype(np.float32)
    arr = ts.save(x, chunks=(1, 400), codec="field16")
    want = _per_chunk(arr)[key]
    _warm(arr)
    launches.clear()
    before = _launch_count(fdb)
    got = arr[key]
    assert launches == batch
    assert _launch_count(fdb) - before == len(batch)
    assert got.tobytes() == want.tobytes()
    ex.shutdown()
    fdb.close()


@pytest.mark.parametrize("backend", ["daos", "posix"])
def test_staged_read_with_cached_and_missing_chunks(backend, tmp_path,
                                                    launches):
    """Cached and never-written chunks take no stage; the misses between
    them are staged and decoded in their batches."""
    fdb, ts, ex = _staged_store(backend, tmp_path, window=8,
                                cache_bytes=1 << 20)
    x = np.random.default_rng(63).normal(size=(40, 384)).astype(np.float32)
    arr = ts.create(x.shape, x.dtype, chunks=(1, 384), codec="field16")
    arr.write_at((slice(0, 30), slice(None)), x[:30])   # rows 30.. missing
    arr[4:12:3, :]                  # rows 4, 7 and 10 now cached
    want = _per_chunk(arr)
    _warm(arr)
    launches.clear()
    before = _launch_count(fdb)
    plan = arr.read_plan((slice(None), slice(None)))
    assert plan.cache_hits == 3 and len(plan.missing) == 10
    staged = sum(len(plan.batches[b][0]) for st in plan.stages for b in st)
    assert staged == 27
    assert plan.execute().tobytes() == want.tobytes()
    # daos: stages of 8, 8, 8 and 3 misses, in any order across the
    # executor's workers; posix: one coalesced batch
    assert sorted(launches) == {"daos": [4, 8, 8, 8], "posix": [32]}[backend]
    assert _launch_count(fdb) - before == len(launches)
    ex.shutdown()
    fdb.close()


@pytest.mark.parametrize("backend", ["daos", "posix"])
def test_rmw_read_chunks_staged(backend, tmp_path, launches):
    """The write path's whole-chunk fetch takes the same stages, and its
    chunks are byte-identical to per-chunk decodes and writable."""
    from repro.tensorstore import ReadPlan
    fdb, ts, ex = _staged_store(backend, tmp_path, window=4)
    x = np.random.default_rng(64).normal(size=(10, 384)).astype(np.float32)
    arr = ts.save(x, chunks=(1, 384), codec="field8")
    want = _per_chunk(arr)
    _warm(arr)
    launches.clear()
    before = _launch_count(fdb)
    plan = ReadPlan.for_chunks(arr, [(i, 0) for i in range(10)])
    chunks = plan.read_chunks()
    for i, c in enumerate(chunks):
        assert c.flags.writeable
        assert c.tobytes() == want[i:i + 1].tobytes()
    assert sorted(launches) == {"daos": [2, 4, 4], "posix": [16]}[backend]
    assert _launch_count(fdb) - before == len(launches)
    # a strided write patches every row through those fetches
    arr[:, ::2] = 0.0
    want[:, ::2] = 0.0
    got = arr.read()
    assert np.abs(got - want).max() <= (x.max() - x.min()) / 255
    ex.shutdown()
    fdb.close()


def test_failing_fetch_in_a_stage_is_annotated(tmp_path, monkeypatch):
    """A fetch that fails inside a stage raises through the executor's
    annotation, naming the failed stage and its chunks."""
    from repro.tensorstore import ReadPlan
    fdb, ts, ex = _staged_store("daos", tmp_path, window=4)
    x = np.zeros((10, 384), np.float32)
    arr = ts.save(x, chunks=(1, 384), codec="field16")
    real = ReadPlan._fetch

    def fetch(self, mh, n_chunks):
        if self.tasks[self.batches[5][0][0]][0] == (5, 0) and \
                mh is self.batches[5][1]:
            raise OSError("object 5 unreachable")
        return real(self, mh, n_chunks)
    monkeypatch.setattr(ReadPlan, "_fetch", fetch)
    with pytest.raises(OSError, match="unreachable") as err:
        arr.read()
    notes = " ".join(getattr(err.value, "__notes__", [])) + str(err.value)
    assert "first failure of 1/3" in notes
    assert ("op=io.fetch backend=daos chunks=[(4, 0), (5, 0), (6, 0), "
            "(7, 0)]") in notes
    ex.shutdown()
    fdb.close()


def test_concurrent_staged_reads(tmp_path):
    """Readers on more threads than cores, with a short switch interval,
    stage, warm and decode one new geometry concurrently: every answer
    is byte-identical to per-chunk decodes."""
    import sys
    import threading
    fdb, ts, ex = _staged_store("daos", tmp_path, window=8)
    x = np.random.default_rng(66).normal(size=(24, 1408)).astype(np.float32)
    arr = ts.save(x, chunks=(1, 704), codec="field8")     # 5-row chunks
    want = _per_chunk(arr)
    keys = [(slice(i, None, 3), slice(None)) for i in range(3)] + \
        [(slice(None), slice(i * 100, None, 7)) for i in range(9)]
    errors, done = [], []

    def reader(key):
        try:
            for _ in range(3):
                got = arr.read_plan(key).execute()
                assert got.tobytes() == np.ascontiguousarray(
                    want[key]).tobytes()
            done.append(key)
        except BaseException as e:   # noqa: BLE001 - reported below
            errors.append(e)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader, args=(k,)) for k in keys]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert errors == [] and len(done) == len(keys)
    ex.shutdown()
    fdb.close()


@pytest.fixture
def compiles():
    """Every XLA compilation while the test runs."""
    import jax
    seen = []

    def on_event(event, _duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    yield seen
    jax.monitoring.unregister_event_duration_listener(on_event)


def _decode_counted(codec, datas, shapes):
    """``decode_batch`` inside a traced span: (chunks, launches counted)."""
    from repro.obs import Tracer
    tracer = Tracer(enabled=True)
    with tracer.span("codec.decode"):
        got = codec.decode_batch(datas, shapes, np.float32)
    return got, tracer.metrics.snapshot().get(
        "codec.decode_launches", {}).get("value", 0)


@pytest.mark.parametrize("n,batches", [
    (1, [1]), (3, [4]), (12, [16]), (32, [32]), (33, [32, 1]),
    (70, [32, 32, 8]),
])
def test_decode_batch_launch_shapes(n, batches, launches, compiles):
    """A group of any size decodes in slices of at most 32 chunks, each at
    a power-of-two batch, byte-identical to per-chunk decodes; once a
    geometry met a group of several, no size compiles again."""
    from repro.tensorstore.codec import MAX_DECODE_BATCH, launch_batch
    assert MAX_DECODE_BATCH == 32
    assert [launch_batch(k) for k in (1, 2, 3, 5, 8, 9, 32)] == \
        [1, 2, 4, 8, 8, 16, 32]
    codec = get_codec("field8")
    rng = np.random.default_rng(65)
    arrs = [rng.normal(size=(5, 128)).astype(np.float32) for _ in range(n)]
    datas = codec.encode_batch(arrs)
    shapes = [a.shape for a in arrs]
    want = [codec.decode(d, s, np.float32).tobytes()
            for d, s in zip(datas, shapes)]
    codec.decode_batch([datas[0]] * 3, [shapes[0]] * 3, np.float32)
    launches.clear()
    compiles.clear()
    got, counted = _decode_counted(codec, datas, shapes)
    assert [g.tobytes() for g in got] == want
    assert launches == batches and counted == len(batches)
    assert compiles == []


def test_first_decode_warms_the_geometry_ladder(launches, monkeypatch):
    """A geometry's first decode runs every launch size up to its cap on
    zeros, once; the cap holds a launch within ``MAX_LAUNCH_BYTES``, so a
    whole ERA5 field decodes one chunk a launch."""
    from repro.tensorstore import codec as codec_mod
    assert [codec_mod.launch_cap(r) for r in (1304, 2048, 360, 105440)] \
        == [32, 32, 32, 1]
    codec = get_codec("field16")
    arr = np.random.default_rng(67).normal(size=(3, 1920)).astype(
        np.float32)                         # a geometry no other test uses
    data = codec.encode(arr)
    rows, block = struct.unpack_from("<II", data, 1)
    assert (rows, block) not in codec._warm
    monkeypatch.setattr(codec_mod, "MAX_LAUNCH_BYTES", 5 * rows * 128 * 4)
    assert codec_mod.launch_cap(rows) == 4
    codec.decode_batch([data], [arr.shape], np.float32)
    assert launches == [1, 2, 4, 1]
    launches.clear()
    got = codec.decode_batch([data] * 6, [arr.shape] * 6, np.float32)
    assert launches == [4, 2]
    want = codec.decode(data, arr.shape, np.float32).tobytes()
    assert all(g.tobytes() == want for g in got)


def test_fair_map_ordered_takes_its_share_of_the_workers():
    """A lone fair call runs on every worker; with another fair call
    running, each keeps at most half of them busy."""
    import threading
    ex = ChunkExecutor(max_workers=4)
    lock, busy, peak = threading.Lock(), [0], [0]

    def work(i):
        with lock:
            busy[0] += 1
            peak[0] = max(peak[0], busy[0])
        time.sleep(0.02)
        with lock:
            busy[0] -= 1
        return i
    assert ex.map_ordered(work, range(12), fair=True) == list(range(12))
    assert peak[0] == 4
    started, release = threading.Event(), threading.Event()

    def hold(_):
        started.set()
        release.wait(10)
    other = threading.Thread(
        target=lambda: ex.map_ordered(hold, [0], fair=True))
    other.start()
    started.wait(10)
    peak[0] = 0
    try:
        assert ex.map_ordered(work, range(12), fair=True) == list(range(12))
        assert peak[0] == 2
        # an unfair call still fills the window
        peak[0] = 0
        ex.map_ordered(work, range(12))
        assert peak[0] == 3             # every worker the held task leaves
    finally:
        release.set()
        other.join()
    ex.shutdown()


# ---------------------------------------------------------------------------
# resharding (ReshardPlan: plan-composed re-layout)
# ---------------------------------------------------------------------------

def test_reshard_byte_equality_roundtrip(backend, tmp_path, make_store):
    """Reshard must produce byte-identical data on the new grid vs a
    client-side reference rewrite — per chunk object, not just per read."""
    from repro.tensorstore import chunk_key, get_codec
    fdb, ts = make_store(backend)
    x = np.random.default_rng(70).normal(size=(37, 53)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    arr.reshard((8, 32))
    assert arr.chunks == (8, 32) and arr.meta.generation == 1
    np.testing.assert_array_equal(arr.read(fill_missing=False), x)
    # a fresh open sees the new layout and identical data
    arr2 = ts.open()
    assert arr2.chunks == (8, 32) and arr2.meta.generation == 1
    np.testing.assert_array_equal(arr2.read(), x)
    # chunk-object bytes == the reference client-side rewrite's encodes
    codec = get_codec("raw")
    for idx in arr2.grid.all_indices():
        got = fdb.retrieve(arr2.chunk_ident(idx)).read()
        assert got == codec.encode(x[arr2.grid.chunk_slices(idx)]), idx
    fdb.close()


def test_reshard_posix_ops_below_naive(tmp_path, make_store):
    """Acceptance: reshard read/write op counts on posix stay strictly
    below the naive one-op-per-chunk rewrite, on the plan AND the meter."""
    fdb, ts = make_store("posix")
    x = np.random.default_rng(71).normal(size=(64, 64)).astype(np.float32)
    ts.save(x, chunks=(16, 16))          # 16 source chunks
    arr = ts.open()
    plan = arr.reshard_plan((8, 64))     # 8 dest chunks
    assert plan.read_ops() < plan.src_chunk_fetches()
    assert plan.write_ops() < plan.n_dest_chunks
    plan.execute()
    assert plan.read_ops_executed == plan.read_ops()
    assert plan.write_ops_executed == plan.write_ops()
    np.testing.assert_array_equal(arr.read(), x)
    fdb.close()


def test_reshard_object_backends_stay_object_granular(tmp_path, make_store):
    fdb, ts = make_store("daos")
    x = np.zeros((64,), np.float32)
    ts.save(x, chunks=(8,))
    plan = ts.open().reshard_plan((16,))
    assert plan.write_ops() == plan.n_dest_chunks == 4
    assert plan.read_ops() == plan.src_chunk_fetches() == 8
    fdb.close()


@pytest.mark.parametrize("backend", ["posix", "rados"])
def test_reshard_strided_subsample(backend, tmp_path, make_store):
    """sel= reshards a strided sub-selection — the consumer-subsampled-grid
    pattern: shape becomes the selection's shape."""
    fdb, ts = make_store(backend)
    x = np.random.default_rng(72).normal(size=(40, 60)).astype(np.float32)
    ts.save(x, chunks=(16, 16))
    arr = ts.open()
    arr.reshard((10, 10), sel=(slice(0, None, 2), slice(1, None, 3)))
    ref = x[::2, 1::3]
    assert arr.shape == ref.shape
    np.testing.assert_array_equal(arr.read(fill_missing=False), ref)
    np.testing.assert_array_equal(ts.open().read(), ref)
    with pytest.raises(ValueError, match="slices"):
        arr.reshard_plan((5, 5), sel=(0, slice(None)))
    fdb.close()


def test_reshard_bounded_staging(tmp_path, make_store):
    """The streaming property: a small window splits the reshard into many
    batches and peak staged bytes stay within one window of dest chunks."""
    from repro.tensorstore import chunk_rectangles
    fdb, ts = make_store("posix")
    x = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    ts.save(x, chunks=(8, 8))
    arr = ts.open()
    plan = arr.reshard_plan((16, 16), window=2)
    assert plan.n_batches == 8           # 16 dest chunks / window 2
    plan.execute()
    assert plan.peak_staged_bytes <= 2 * 16 * 16 * 4
    np.testing.assert_array_equal(arr.read(), x)
    # rectangle splitting covers every chunk exactly once
    rects = list(chunk_rectangles((3, 4, 5), 7))
    cover = np.zeros((3, 4, 5), int)
    for rect in rects:
        assert np.prod([hi - lo for lo, hi in rect]) <= 7
        cover[tuple(slice(lo, hi) for lo, hi in rect)] += 1
    assert (cover == 1).all()
    assert list(chunk_rectangles((), 4)) == [()]
    fdb.close()


def test_reshard_flush_barrier_and_crash_safety(tmp_path, make_store):
    """Rule 3 through composition: a second client sees the OLD layout
    until the resharding writer flushes — a reshard interrupted before its
    commit barrier leaves the old layout fully intact."""
    root = str(tmp_path / "fdb")
    fdb, ts = make_store("posix")
    x = np.arange(64, dtype=np.float32)
    ts.save(x, chunks=(8,))
    arr = ts.open()
    arr.reshard((16,), flush=False)      # archived, not yet committed
    reader = FDB(FDBConfig(backend="posix", schema="tensor", root=root))
    rts = TensorStore(reader, {"store": "s", "array": "a", "writer": "w0"})
    reader.catalogue.refresh()
    old = rts.open()
    assert old.chunks == (8,) and old.meta.generation == 0
    np.testing.assert_array_equal(old.read(), x)
    fdb.flush()                          # the commit barrier
    reader.catalogue.refresh()
    new = rts.open()
    assert new.chunks == (16,) and new.meta.generation == 1
    np.testing.assert_array_equal(new.read(), x)
    reader.close()
    fdb.close()


def test_reshard_noop_and_codec_change(tmp_path, make_store):
    fdb, ts = make_store("daos")
    x = np.random.default_rng(73).normal(size=(256, 128)).astype(np.float32)
    ts.save(x, chunks=(128, 128))
    arr = ts.open()
    plan = arr.reshard_plan((128, 128))  # identical layout: nothing to move
    assert plan.noop and plan.n_batches == 0
    plan.execute()
    assert arr.meta.generation == 0
    # codec change forces a real rewrite even on the same grid
    arr.reshard((128, 128), codec="field16")
    assert arr.meta.codec == "field16" and arr.meta.generation == 1
    bound = (x.max() - x.min()) / 65535 * 0.51 + 1e-6
    assert np.abs(arr.read() - x).max() <= bound
    fdb.close()


def test_create_on_mismatch_retain_bumps_generation(tmp_path, make_store):
    """The versioned-retain policy: a layout change under
    on_mismatch='retain' forks a fresh generation instead of raising, and
    old-generation chunks can never shadow the new grid."""
    from repro.tensorstore import LayoutMismatchError
    fdb, ts = make_store("daos")
    ts.save(np.full((8, 8), 3.0, np.float32), chunks=(2, 2))
    with pytest.raises(LayoutMismatchError):
        ts.create((8, 8), np.float32, chunks=(4, 4))
    arr = ts.create((8, 8), np.float32, chunks=(4, 4), on_mismatch="retain")
    assert arr.meta.generation == 1
    # the new generation starts empty — the old grid's (2,2) chunks (which
    # share unprefixed indices like c0.0) must not leak through
    np.testing.assert_array_equal(arr.read(), np.zeros((8, 8), np.float32))
    arr.write(np.ones((8, 8), np.float32))
    np.testing.assert_array_equal(ts.open().read(),
                                  np.ones((8, 8), np.float32))
    assert ts.open().meta.generation == 1
    # unchanged layout keeps the live generation (replace semantics)
    again = ts.create((8, 8), np.float32, chunks=(4, 4))
    assert again.meta.generation == 1
    with pytest.raises(ValueError, match="on_mismatch"):
        ts.create((8, 8), np.float32, chunks=(4, 4), on_mismatch="wipe")
    fdb.close()


def test_meta_generation_format_versioning():
    """Generation-0 metadata stays format v1 (readable by pre-generation
    code); resharded layouts serialise as v2."""
    import json
    from repro.tensorstore import ArrayMeta
    m0 = ArrayMeta(shape=(8,), dtype="float32", chunks=(4,))
    d0 = json.loads(m0.to_bytes().decode())
    assert d0["version"] == 1 and "generation" not in d0
    assert ArrayMeta.from_bytes(m0.to_bytes()) == m0
    m2 = ArrayMeta(shape=(8,), dtype="float32", chunks=(4,), generation=2)
    d2 = json.loads(m2.to_bytes().decode())
    assert d2["version"] == 2 and d2["generation"] == 2
    assert ArrayMeta.from_bytes(m2.to_bytes()) == m2
    assert m0.layout_matches(m2)
    with pytest.raises(ValueError, match="newer"):
        ArrayMeta.from_bytes(json.dumps({
            "shape": [8], "dtype": "float32", "chunks": [4],
            "version": 3}).encode())


# ---------------------------------------------------------------------------
# reshard through the facades (pipeline + checkpoint)
# ---------------------------------------------------------------------------

def test_field_store_reshard(tmp_path):
    """Producer grid -> consumer grid through the pipeline facade, with
    coalesced ops and immediate consumer visibility."""
    from repro.data import ChunkedFieldStore
    fs = ChunkedFieldStore("nwp-rs", FDBConfig(backend="posix",
                                               root=str(tmp_path / "fdb")),
                           chunks=(32, 32))
    field = np.random.default_rng(80).normal(size=(96, 96)
                                             ).astype(np.float32)
    fs.put_field("t2m", field)
    fs.commit()
    arr = fs.reshard("t2m", (96, 16))    # row-major -> column bands
    assert arr.chunks == (96, 16)
    np.testing.assert_array_equal(fs.read_window("t2m"), field)
    # strided subsample on the way through (every other row)
    fs.reshard("t2m", (48, 48), slice(0, None, 2))
    np.testing.assert_array_equal(fs.read_window("t2m"), field[::2])
    # strided window reads/writes through the facade
    np.testing.assert_array_equal(
        fs.read_window("t2m", slice(0, None, 3), slice(1, 90, 5)),
        field[::2][::3, 1:90:5])
    fs.write_window("t2m", 0.0, slice(0, None, 2))
    fs.commit()                          # rule 3: visibility needs the flush
    want = field[::2].copy()
    want[::2] = 0.0
    np.testing.assert_array_equal(fs.read_window("t2m"), want)
    fs.close()


def test_field_store_consumer_refresh_after_reshard(tmp_path):
    """A consumer store that cached its open keeps the old generation
    (versioned retain keeps it readable) until open_field(refresh=True)
    picks up the producer's re-layout."""
    from repro.data import ChunkedFieldStore
    cfg = FDBConfig(backend="posix", root=str(tmp_path / "fdb"))
    prod = ChunkedFieldStore("nwp-rf", cfg, chunks=(32, 32))
    field = np.random.default_rng(84).normal(size=(64, 64)).astype(np.float32)
    prod.put_field("t2m", field)
    prod.commit()
    cons = ChunkedFieldStore("nwp-rf", cfg, chunks=(32, 32))
    assert cons.open_field("t2m").chunks == (32, 32)   # cached open
    prod.reshard("t2m", (32, 16), slice(0, None, 2))   # shape halves
    cons.fdb.catalogue.refresh()
    stale = cons.open_field("t2m")
    assert stale.chunks == (32, 32)                    # still the old open
    np.testing.assert_array_equal(stale.read(), field)
    fresh = cons.open_field("t2m", refresh=True)
    assert fresh.chunks == (32, 16) and fresh.meta.generation == 1
    np.testing.assert_array_equal(cons.read_window("t2m"), field[::2])
    prod.close()
    cons.close()


def test_checkpoint_topology_change_restore():
    """Restore onto a different chunking than the checkpoint was saved
    with: a new-topology checkpointer reshards the saved tensors onto its
    own banding, then sharded partial reads line up."""
    from repro.train.checkpoint import FDBCheckpointer
    w = np.random.default_rng(81).normal(size=(256, 64)).astype(np.float32)
    mu = np.random.default_rng(82).normal(size=(128, 32)).astype(np.float32)
    ck4 = FDBCheckpointer("topo", FDBConfig(backend="daos"), n_shards=4)
    ck4.save(3, {"w": w}, opt_state={"mu": mu})
    # a 2-shard run opens the 4-band checkpoint as-is...
    ck2 = FDBCheckpointer("topo", FDBConfig(backend="daos"), n_shards=2)
    assert ck2.open_tensor(3, "w").n_chunks[0] == 4
    got = ck2.restore(3, {"w": w})       # whole-tensor restore still works
    np.testing.assert_array_equal(np.asarray(got["w"]), w)
    # ...then reshards it onto its own banding
    ck2.reshard_step(3, {"w": w})
    ck2.reshard_tensor(3, "mu", kind="opt")
    assert ck2.open_tensor(3, "w").n_chunks[0] == 2
    assert ck2.open_tensor(3, "mu", kind="opt").n_chunks[0] == 2
    np.testing.assert_array_equal(
        np.asarray(ck2.restore(3, {"w": w})["w"]), w)
    np.testing.assert_array_equal(
        np.asarray(ck2.restore(3, {"mu": mu}, kind="opt")["mu"]), mu)
    # band-aligned partial read on the new topology
    np.testing.assert_array_equal(ck2.open_tensor(3, "w")[128:256], w[128:])
    ck4.close()
    ck2.close()


def test_checkpoint_resave_new_banding_bumps_generation():
    """A re-save of a step under a different n_shards must not fail and
    must win on restore (create on_mismatch='retain')."""
    from repro.train.checkpoint import FDBCheckpointer
    w = np.random.default_rng(83).normal(size=(64, 16)).astype(np.float32)
    ck4 = FDBCheckpointer("reband", FDBConfig(backend="daos"), n_shards=4)
    ck4.save(1, {"w": w})
    ck8 = FDBCheckpointer("reband", FDBConfig(backend="daos"), n_shards=8)
    ck8.save(1, {"w": w * 2})
    arr = ck8.open_tensor(1, "w")
    assert arr.meta.generation == 1 and arr.n_chunks[0] == 8
    np.testing.assert_array_equal(
        np.asarray(ck8.restore(1, {"w": w})["w"]), w * 2)
    ck4.close()
    ck8.close()


# ---------------------------------------------------------------------------
# heavy sweep (excluded from tier-1 via the slow marker)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sweep_chunk_sizes_roundtrip(backend, tmp_path, make_store):
    rng = np.random.default_rng(9)
    x = rng.normal(size=(257, 129)).astype(np.float32)
    for cs in (8, 32, 64, 128, 512):
        fdb, ts = make_store(backend, array=f"sweep{cs}")
        ts.save(x, chunks=(cs, cs))
        np.testing.assert_array_equal(ts.open().read(), x)
        fdb.close()
