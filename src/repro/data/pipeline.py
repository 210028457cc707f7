"""FDB-backed training-data pipeline (the paper's producer/consumer pattern).

Producers (tokenizer jobs / NWP field generators) archive sample shards;
training readers retrieve per-step batches while producers may still be
writing — the thesis's operational write+read contention pattern, running on
whichever FDB backend is configured.  A background prefetch thread overlaps
retrieval with compute (I/O-forwarding analogue).
"""
from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, Optional

import numpy as np

from repro.core import FDB, FDBConfig, Identifier, WriterSession
from repro.core.schema import DATA_SCHEMA, TENSOR_SCHEMA
from repro.tensorstore import (ChunkedArray, LayoutMismatchError,
                               TensorStore, TreeCatalogue)


class SyntheticTokens:
    """Deterministic synthetic LM data (no external corpora in-container)."""

    def __init__(self, vocab_size: int, seq_len: int, seed: int = 0):
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.seed = seed

    def batch(self, step: int, batch_size: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(self.seed + step)
        toks = rng.integers(0, self.vocab_size,
                            (batch_size, self.seq_len + 1), dtype=np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


class ChunkedFieldStore:
    """Chunked N-D weather-field access over ``repro.tensorstore``.

    Producers archive whole fields (lat × lon × level grids) as chunked
    arrays; consumers slice windows — ``read_window("t2m", slice(0, 120),
    slice(300, 420))`` retrieves only the intersecting chunks, the partial-
    read NWP workload (regional post-processing / PGEN extraction) the
    whole-blob archive path cannot serve.
    """

    def __init__(self, store: str = "nwp",
                 fdb_config: Optional[FDBConfig] = None,
                 writer: str = "prod0", codec: str = "raw",
                 chunks: Optional[tuple] = None,
                 tracer=None, faults=None, retry=None, meter=None,
                 cache_bytes: int = 64 * 2 ** 20):
        cfg = fdb_config or FDBConfig(backend="daos")
        import dataclasses
        if cfg.resolved_schema().name != "tensor":
            cfg = dataclasses.replace(cfg, schema=TENSOR_SCHEMA)
        # the serving facade defaults the decoded-chunk cache ON (the raw
        # FDB/TensorStore layers leave it off so op accounting stays
        # exact); cache_bytes=0 opts out, and an explicit
        # FDBConfig.chunk_cache_bytes wins
        if cfg.chunk_cache_bytes == 0 and cache_bytes > 0:
            cfg = dataclasses.replace(cfg, chunk_cache_bytes=cache_bytes)
        # tracer/faults/retry/meter pass straight through to the FDB
        # client, so workflow drivers can observe, chaos-test and
        # cost-model the field path without reaching around the facade
        self.fdb = FDB(cfg, meter=meter, tracer=tracer, faults=faults,
                       retry=retry)
        self.store = store
        #: collocation key all producers share (the schema "writer" dim) —
        #: named writer_key so the :meth:`writer` session factory can keep
        #: the ISSUE-facing name
        self.writer_key = writer
        self.codec = codec
        self.chunks = chunks
        # metadata only changes on wipe/re-put/reshard, so opened arrays
        # cache; those mutators update or drop this store's own cache, but
        # a *different* consumer store must re-open after a producer
        # reshard (open_field(refresh=True)) — see reshard()
        self._opened: Dict[str, ChunkedArray] = {}
        #: consolidated metadata for this store's dataset tree (the Zarr
        #: ``.zmetadata`` idiom): creates/reshards through this facade keep
        #: it fresh, and :meth:`open_tree` opens every field with ONE fetch
        self.tree = TreeCatalogue(
            self.fdb, {"store": store, "writer": writer},
            member_dim="array")

    def _ts(self, name: str) -> TensorStore:
        return TensorStore(self.fdb, {"store": self.store, "array": name,
                                      "writer": self.writer_key},
                           tree=self.tree)

    # -- producer side -----------------------------------------------------
    def _create(self, name: str, shape, dtype, chunks: Optional[tuple],
                codec: Optional[str]) -> ChunkedArray:
        ts = self._ts(name)
        kw = dict(chunks=chunks or self.chunks, codec=codec or self.codec)
        try:
            arr = ts.create(shape, dtype, **kw)
        except LayoutMismatchError:
            # layout changed: the array's dataset is exactly (store,
            # array), so a wipe removes every stale chunk before
            # re-creating
            self.wipe_field(name)
            arr = ts.create(shape, dtype, **kw)
        self._opened[name] = arr
        return arr

    def put_field(self, name: str, values: np.ndarray,
                  chunks: Optional[tuple] = None,
                  codec: Optional[str] = None) -> ChunkedArray:
        values = np.asarray(values)
        with self.fdb.tracer.span("field.put", field=name,
                                  nbytes=values.nbytes):
            arr = self._create(name, values.shape, values.dtype, chunks,
                               codec)
            # commit() is the visibility barrier — don't flush per field
            arr.write(values, flush=False)
        return arr

    def declare_field(self, name: str, shape, dtype,
                      chunks: Optional[tuple] = None,
                      codec: Optional[str] = None) -> ChunkedArray:
        """Archive a field's metadata with no chunks, record it in the
        consolidated tree and flush, so that other clients' writers can
        open the field and fill it window by window
        (:meth:`FieldWriter.write_window`) before any value exists.
        Unwritten chunks read as missing (``fill_missing``).  A layout that
        differs from a stored one wipes the field first, as
        :meth:`put_field` does.  The flush publishes everything else this
        client archived too."""
        with self.fdb.tracer.span("field.declare", field=name):
            arr = self._create(name, shape, dtype, chunks, codec)
            self.commit()
        return arr

    def commit(self) -> None:
        self.fdb.flush()

    # -- consumer side -----------------------------------------------------
    def open_field(self, name: str, refresh: bool = False) -> ChunkedArray:
        """Open (and cache) a field's chunked array.  The first open on a
        fresh consumer loads the **consolidated metadata** once (one
        fetch) and serves every subsequent field open from it — per-array
        metadata fetches happen only for fields the consolidated object
        does not know (written by code that does not maintain it, or by a
        concurrent producer since the load).

        ``refresh=True`` drops the cached open and re-reads the
        authoritative per-array metadata — required for a consumer to pick
        up another client's re-layout (``reshard``), since versioned
        retain keeps the old generation's chunks readable and a stale
        cached open would keep returning them; the consolidated mirror is
        reloaded too."""
        if refresh:
            self._opened.pop(name, None)
            arr = self._opened[name] = self._ts(name).open()
            self.tree.load()    # resync the consolidated mirror as well
            return arr
        arr = self._opened.get(name)
        if arr is None:
            if not self.tree.loaded:
                self.tree.load()
            meta = self.tree.get(name)
            if meta is not None:        # consolidated hit: no fetch
                arr = ChunkedArray(self._ts(name), meta)
            else:                       # fall back to per-array metadata
                arr = self._ts(name).open()
            self._opened[name] = arr
        return arr

    def open_tree(self, refresh: bool = False) -> Dict[str, ChunkedArray]:
        """Open every field of this store's dataset tree with a **single**
        consolidated-metadata fetch (the Zarr consolidated-open idiom) —
        the serving cold-start path: N arrays, one round-trip.  Returns
        ``{name: ChunkedArray}`` and primes the per-field open cache.
        Fields written by clients that do not maintain the consolidated
        object are absent — open them via :meth:`open_field`, which falls
        back to the authoritative per-array metadata."""
        if refresh or not self.tree.loaded:
            self.tree.load()
        out: Dict[str, ChunkedArray] = {}
        for name in self.tree.names():
            if name.startswith("."):
                continue
            meta = self.tree.get(name)
            arr = self._opened.get(name)
            if arr is None or arr.meta != meta:
                arr = self._opened[name] = ChunkedArray(self._ts(name),
                                                        meta)
            out[name] = arr
        return out

    def read_window(self, name: str, *selection,
                    fill_missing: bool = True) -> np.ndarray:
        """Read a window of a field; I/O is issued for only the chunks the
        window intersects — in parallel, and coalesced into single ranged
        reads where chunks are adjacent in one file (posix backend).
        Windows may be strided (``slice(0, 720, 4)`` — every 4th latitude):
        chunks the stride steps over are not touched at all.

        ``fill_missing=False`` raises ``KeyError`` on never-written chunks
        instead of zero-filling — for consumers of dense fields where a
        missing chunk means lost or not-yet-committed data.
        """
        arr = self.open_field(name)
        with self.fdb.tracer.span("field.read_window", field=name):
            return arr.read_plan(tuple(selection),
                                 fill_missing=fill_missing).execute()

    def write_window(self, name: str, values, *selection) -> ChunkedArray:
        """Chunk-aligned in-place update of a field window — the
        assimilation pattern: ``write_window("t2m", increment, slice(0,
        120), slice(300, 420))`` re-archives only the chunks the window
        touches (partially covered edge chunks read-modify-write), through
        a coalesced :class:`~repro.tensorstore.WritePlan` — chunks landing
        in one posix data file archive as a single batched store write, and
        same-shape chunks encode in one codec kernel launch.

        Visibility of the *new* chunk versions waits for :meth:`commit`.
        Windows may be strided (a subsampled analysis grid writing every
        k-th row): stride gaps are preserved via read-modify-write of the
        touched chunks.  Caveat for chunk-*aligned* batching only: a window
        that partially covers a chunk needs read-modify-write, and the RMW
        pre-flush (FDB rule 3, see :meth:`ChunkedArray.write_at`) publishes
        whatever this producer archived earlier in the batch.  Producers
        that need a strict single commit barrier must keep their windows
        chunk-aligned.
        """
        arr = self.open_field(name)
        # normalize_key pads a short/empty key with full slices
        with self.fdb.tracer.span("field.write_window", field=name):
            arr.write_plan(tuple(selection), values).execute(flush=False)
        return arr

    def reshard(self, name: str, new_chunks, *selection,
                codec: Optional[str] = None) -> ChunkedArray:
        """Re-lay-out a field onto a new chunk grid — the producer-grid vs
        consumer-grid mismatch the paper's workflows revolve around: a
        model archives level-major chunks, regional post-processing wants
        lat/lon tiles, so the pipeline reshards between the stages instead
        of punishing every consumer read.

        Streams through bounded batches (one coalesced read plan + one
        coalesced write plan each — see
        :class:`repro.tensorstore.ReshardPlan`); the whole field is never
        materialised client-side, and the re-layout is committed (flushed)
        before returning: this store's cached open is updated in place and
        consumers *opening* the field afterwards see the new grid.  A
        consumer store that already cached its open keeps reading the
        retained old generation until it re-opens —
        ``open_field(name, refresh=True)`` — because versioned retain
        deliberately keeps the old chunks readable.  A trailing
        ``*selection`` of slices (possibly strided) subsamples on the way
        through — e.g. every other level for a coarse consumer.

        Old-grid chunks are retained versioned (unreachable, never read as
        wrong data) because the FDB has no per-object delete; to *reclaim*
        their space instead, use :meth:`wipe_field` + :meth:`put_field`,
        which costs a full client-side roundtrip.
        """
        arr = self.open_field(name)
        sel = tuple(selection) if selection else None
        arr.reshard(new_chunks, codec=codec, sel=sel, flush=True)
        return arr

    def wipe_field(self, name: str) -> None:
        self._opened.pop(name, None)
        self.fdb.wipe({"store": self.store, "array": name})
        # the tree index lives in its own (store, array=".tree") dataset,
        # so the wipe above never touches it — drop the member explicitly
        # (loading first so an unloaded mirror can't leave a stale entry)
        if not self.tree.loaded:
            self.tree.load()
        self.tree.forget(name)

    # -- multi-producer side ------------------------------------------------
    def writer(self, writer_id: str, lease_ttl: Optional[float] = None,
               heartbeat_interval: Optional[float] = None,
               lease_block: bool = False,
               lease_timeout: Optional[float] = None) -> "FieldWriter":
        """Open a :class:`FieldWriter` — one producer task's session on
        this store, the multi-writer counterpart of :meth:`write_window`.

        Several writers (e.g. parallel assimilation tasks, ensemble
        members) may update *one* field concurrently: each writer's window
        acquires the covering chunk-range leases at plan time, so disjoint
        windows proceed in parallel while overlapping windows fail fast
        with ``LeaseConflictError`` instead of racing to a silent
        last-flush merge.  All writers share this store's collocation key
        (the ``writer`` schema dim), so consumers read one coherent array;
        the *session* identity exists for leases and per-session dirty
        tracking, not for placement.

        Writers opened here share this store's FDB client and executor, and
        so its flush: any one writer's :meth:`FieldWriter.commit` publishes
        every writer's archives.  Writers that each need their own barrier
        (I/O servers that publish their share of a step independently) take
        one ``ChunkedFieldStore`` each, built with the same ``store``,
        ``writer`` and ``FDBConfig``: separate clients of one deployment,
        which share its lease table.  One of them declares the fields they
        fill (:meth:`declare_field`).

        ``lease_block=True`` flips the overlap posture from fail-fast to
        wait: plan-time acquires queue (up to ``lease_timeout`` seconds)
        on conflicting windows until their holder releases or its
        ``lease_ttl`` lapses — how workflow assimilation stages serialise
        overlapping analysis windows instead of erroring
        (``docs/workflows.md``).

        Use as a context manager; :meth:`FieldWriter.commit` is the
        visibility barrier, :meth:`FieldWriter.release` gives up a
        committed field, and closing flushes (if dirty) then releases
        every lease the writer still holds.
        """
        return FieldWriter(self, self.fdb.session(
            writer_id, lease_ttl=lease_ttl,
            heartbeat_interval=heartbeat_interval,
            lease_block=lease_block, lease_timeout=lease_timeout))

    def close(self) -> None:
        self.fdb.close()


class FieldWriter:
    """One producer task writing windows of shared fields under chunk-range
    leases — returned by :meth:`ChunkedFieldStore.writer`.

    Its barrier is its client's: :meth:`commit` publishes whatever any
    session of the same FDB client archived.  A writer that needs a barrier
    of its own is opened on a ``ChunkedFieldStore`` of its own (see
    :meth:`ChunkedFieldStore.writer`).  A writer that moves on to new
    fields, as an I/O server does from step to step, gives each field up
    once it is committed (:meth:`release`), so that neither its leases nor
    its cached opens grow with the fields it has written."""

    def __init__(self, store: ChunkedFieldStore, session: WriterSession):
        self._store = store
        self.session = session
        #: session-bound opens are cached per field until released
        #: (metadata re-reads are pure overhead; layout changes mid-session
        #: are not supported)
        self._opened: Dict[str, ChunkedArray] = {}

    @property
    def writer_id(self) -> str:
        return self.session.writer_id

    def _base(self, name: str) -> Dict[str, str]:
        return {"store": self._store.store, "array": name,
                "writer": self._store.writer_key}

    def _open(self, name: str) -> ChunkedArray:
        arr = self._opened.get(name)
        if arr is None:
            ts = TensorStore(None, self._base(name), session=self.session)
            arr = self._opened[name] = ts.open()
        return arr

    def write_window(self, name: str, values, *selection) -> ChunkedArray:
        """Chunk-aligned in-place update of a field window under this
        writer's leases: the covering chunk ranges are acquired at plan
        time (``LeaseConflictError`` if another writer holds any of them,
        before any byte moves) and stay held until :meth:`release` or
        :meth:`close` — a :meth:`commit` publishes the data but
        deliberately keeps the windows owned, so a producer retains them
        across commits.  The new chunk versions become visible at
        :meth:`commit`, exactly like
        :meth:`ChunkedFieldStore.write_window`.  RMW fetches for partially
        covered chunks are lease-protected, and this session's earlier
        unflushed archives pre-flush per *session*, not per client."""
        arr = self._open(name)
        tracer = self.session.fdb.tracer
        with tracer.span("field.write_window", field=name,
                         writer=self.writer_id):
            arr.write_plan(tuple(selection), values).execute(flush=False)
        return arr

    def commit(self) -> None:
        """The visibility barrier for everything this writer archived.  It
        is a client-level flush (FDB rule 3), so it also publishes what
        other sessions of the same client archived.  Held leases stay held
        — a writer keeps its windows across commits until it releases the
        field or closes."""
        self.session.flush()

    def release(self, name: str) -> int:
        """Give up field ``name``: flush if this writer is dirty, release
        every chunk-range lease it holds on the field, and drop its cached
        open.  Returns the number of leases released.  The field stays
        readable; writing it again re-opens it and re-acquires leases."""
        tracer = self.session.fdb.tracer
        with tracer.span("field.release", field=name,
                         writer=self.writer_id) as sp:
            if self.session.dirty:
                self.session.flush()
            n = self.session.release_all(self._base(name))
            self._opened.pop(name, None)
            if sp is not None:
                sp.attrs["leases"] = n
        return n

    def close(self) -> None:
        """Flush if dirty, then release every lease this writer holds."""
        self.session.close()

    def __enter__(self) -> "FieldWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class FDBDataPipeline:
    def __init__(self, corpus: str, split: str = "train",
                 fdb_config: Optional[FDBConfig] = None,
                 producer: str = "prod0", prefetch: int = 2):
        cfg = fdb_config or FDBConfig(backend="daos")
        if cfg.resolved_schema().name != "data":
            import dataclasses
            cfg = dataclasses.replace(cfg, schema=DATA_SCHEMA)
        self.fdb = FDB(cfg)
        self.corpus = corpus
        self.split = split
        self.producer = producer
        self.prefetch = prefetch

    # -- producer side -----------------------------------------------------
    def put_batch(self, shard: int, batch_idx: int,
                  batch: Dict[str, np.ndarray]) -> None:
        packed = np.concatenate(
            [batch["tokens"].reshape(-1), batch["labels"].reshape(-1)])
        meta = np.array(batch["tokens"].shape, np.int64)
        payload = meta.tobytes() + packed.astype(np.int32).tobytes()
        with self.fdb.tracer.span("data.put_batch", shard=shard,
                                  batch=batch_idx, nbytes=len(payload)):
            self.fdb.archive(self._ident(shard, batch_idx), payload)

    def commit(self) -> None:
        self.fdb.flush()

    # -- consumer side ---------------------------------------------------------
    def _ident(self, shard: int, batch_idx: int) -> Identifier:
        return Identifier({"corpus": self.corpus, "split": self.split,
                           "producer": self.producer, "shard": str(shard),
                           "batch": str(batch_idx)})

    def get_batch(self, shard: int, batch_idx: int
                  ) -> Optional[Dict[str, np.ndarray]]:
        with self.fdb.tracer.span("data.get_batch", shard=shard,
                                  batch=batch_idx):
            h = self.fdb.retrieve(self._ident(shard, batch_idx))
            if h.length() == 0:
                return None
            raw = h.read()
        meta = np.frombuffer(raw[:16], np.int64)
        B, S = int(meta[0]), int(meta[1])
        flat = np.frombuffer(raw[16:], np.int32)
        return {"tokens": flat[:B * S].reshape(B, S).copy(),
                "labels": flat[B * S:].reshape(B, S).copy()}

    def available_batches(self, shard: int) -> int:
        return sum(1 for _ in self.fdb.list(
            {"corpus": self.corpus, "split": self.split,
             "shard": str(shard)}))

    def iter_batches(self, shard: int, start: int = 0
                     ) -> Iterator[Dict[str, np.ndarray]]:
        """Prefetching iterator: retrieval overlaps consumer compute."""
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)

        def fill() -> None:
            i = start
            while True:
                b = self.get_batch(shard, i)
                q.put(b)
                if b is None:
                    return
                i += 1

        # lint: disable=L005 -- single daemon prefetch thread feeding a
        # bounded queue; not chunk I/O, so ChunkExecutor doesn't fit
        t = threading.Thread(target=fill, daemon=True)
        t.start()
        while True:
            b = q.get()
            if b is None:
                return
            yield b

    def close(self) -> None:
        self.fdb.close()
