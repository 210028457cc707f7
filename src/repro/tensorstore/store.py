"""Chunked N-D arrays over the FDB: the storage layer the paper's access
pattern wants (many independent object-granular I/Os per request).

An array is split on a :class:`~.grid.ChunkGrid`; every chunk is archived as
one FDB object whose element key encodes the chunk index (``c<i>.<j>...``,
generation-prefixed ``g<N>.c...`` after a reshard), and a small
:class:`~.meta.ArrayMeta` object rides under the reserved element value
``meta``.  Slicing ``arr[10:20, :]`` retrieves only the intersecting chunks
— in parallel, through the bounded :class:`~.executor.ChunkExecutor` — on
any of the four backends (daos / rados / posix / s3).  Selections may be
strided (``arr[::4]``): only the chunks holding a selected point are
touched, on the read and the write path alike.

The store is schema-agnostic: it binds to an existing :class:`repro.core.FDB`
plus a *base identifier* covering every schema dimension except the chunk
dimension.  With the dedicated ``tensor`` schema that base is
``{store, array, writer}``; with the ``ckpt`` schema the chunk index rides
the ``shard`` element dim so checkpoint tensors become chunked arrays without
a second catalogue.

All three data paths plan before they touch bytes — the two halves of the
paper's object-store/POSIX trade-off, plus their composition:

* **Reads** build a :class:`ReadPlan`: every intersecting chunk is resolved
  to its backend handle (catalogue only, no data I/O), and handles over the
  same storage unit — posix chunks of one data file — are grouped so adjacent
  ranges coalesce into single large reads (``FileRangeHandle`` merging),
  while object-store chunks keep one op in flight each.  ``read_ops()`` on
  the plan reports the I/O-op count a read will issue.  Execution is
  *staged*, symmetric with writes: the I/O batches are split into stages
  of at most one executor window of chunks (``ReadPlan.window``), and
  each stage is one executor task that fetches its batches, decodes every
  fetched chunk in one ``Codec.decode_batch`` call (one kernel launch per
  chunk geometry) and assembles them; a plan's stages overlap on its
  fair share of the executor's workers.
* **Writes** (``write``, ``arr[sel] = values``, ``write_at``) build a
  :class:`WritePlan` — the mirror of the read side.  Every chunk the
  selection touches is resolved to its destination storage unit
  (``FDB.archive_placement``, placement only, no I/O) and chunks landing in
  the same unit — posix chunks appending into one writer's data file — are
  grouped into batched store-level writes (``FDB.archive_batch``), while
  object-store chunks keep one archive op in flight each.
  ``write_ops()`` on the plan reports the store-level write count, the twin
  of ``ReadPlan.read_ops()``.  Encoding is batched (same-shape chunks share
  one ``Codec.encode_batch`` kernel launch, ragged edge chunks fall back
  per-chunk) and *staged*: the plan is executed in sub-batches of at most
  one executor window (``WritePlan.window`` chunks), so peak staged bytes
  are bounded no matter how large the plan — arrays far larger than memory
  archive without materialising every encoded tile at once.  Chunks fully
  covered by the selection encode from the new values outright; partially
  covered chunks do read-modify-write, with the fetches routed through a
  whole-chunk :class:`ReadPlan` (:meth:`ReadPlan.for_chunks`) so adjacent
  posix RMW reads coalesce exactly like normal reads.  Chunks never written
  before read as zeros (the Zarr fill-value convention).  A ``flush()``
  barrier after the archives preserves FDB visibility rule 3 — and partial
  writes flush *first* as well, so their RMW fetches see this writer's own
  earlier unflushed chunks.  On a *session-bound* store (multi-writer), the
  plan additionally acquires the chunk-range **leases** covering its
  selection at plan time — overlap with another writer fails fast with
  :class:`~repro.core.LeaseConflictError` — and validates its lease epochs
  before every stage of archives, so a fenced stale writer raises
  :class:`~repro.core.StaleLeaseError` instead of silently merging.
* **Reshards** (``arr.reshard(new_chunks)``) compose the two: a
  :class:`~.reshard.ReshardPlan` streams the array onto a new chunk grid —
  destination chunks in bounded rectangular batches, each batch one
  coalesced source ``ReadPlan`` and one coalesced destination ``WritePlan``
  — never materialising the whole array client-side.  The new grid's chunks
  live under a fresh layout *generation* (see :mod:`.meta`), so the flip is
  one transactional metadata replace and old-grid chunks are retained
  versioned, never readable as wrong data.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core import (FDB, FieldLocation, Identifier, LeaseConflictError,
                        MultiHandle, StaleLeaseError, WriterSession,
                        deadline_scope, group_mergeable)
from .cache import ChunkCache
from .codec import Codec, get_codec
from .executor import ChunkExecutor
from .grid import ChunkGrid, merge_id_ranges
from .meta import META_CHUNK_KEY, ArrayMeta, TreeCatalogue, auto_chunks

Index = Tuple[int, ...]


class LayoutMismatchError(ValueError):
    """Raised on re-create of an existing array with a different layout
    (unless the caller opted into ``on_mismatch="retain"``, which bumps the
    layout generation instead — see :meth:`TensorStore.create`)."""


def chunk_key(idx: Index, generation: int = 0) -> str:
    """Element-key value for a chunk index, e.g. ``c0.3.1`` — prefixed with
    the layout generation (``g2.c0.3.1``) for resharded layouts, so chunk
    keys of different grids over one array slot can never collide.

    ``.`` as separator: ``/`` is the FDB multi-value expression separator and
    ``,``/``=`` are taken by the canonical identifier form.  Generation 0
    stays unprefixed for compatibility with pre-generation (format v1)
    arrays.
    """
    key = "c" + ".".join(str(i) for i in idx)
    return key if generation == 0 else f"g{generation}.{key}"


class TensorStore:
    """A named slot for one chunked array inside an FDB.

    ``session`` (optional) binds the slot to a
    :class:`repro.core.WriterSession`: every :class:`WritePlan` built on it
    acquires the chunk-range leases covering its selection at *plan* time
    (failing fast with :class:`repro.core.LeaseConflictError` on overlap
    with another writer), validates its lease epochs before every stage of
    archives (:class:`repro.core.StaleLeaseError` fences a writer whose
    lease was broken and re-acquired), and tracks dirty/flush-barrier state
    per session — the contract that makes two writers on disjoint chunk
    ranges of one array provably safe.  Without a session the store keeps
    the original single-writer behaviour: no leases, client-level barriers.
    """

    def __init__(self, fdb: Optional[FDB], base: Mapping[str, object],
                 chunk_dim: Optional[str] = None,
                 executor: Optional[ChunkExecutor] = None,
                 session: Optional[WriterSession] = None,
                 tree: Optional[TreeCatalogue] = None):
        if session is not None:
            if fdb is None:
                fdb = session.fdb
            elif session.fdb is not fdb:
                raise ValueError("session belongs to a different FDB client")
        elif fdb is None:
            raise ValueError("TensorStore needs an FDB client or a session")
        self.fdb = fdb
        self.session = session
        schema = fdb.schema
        self.chunk_dim = chunk_dim or schema.element_dims[-1]
        if self.chunk_dim not in schema.element_dims:
            raise KeyError(f"chunk dim {self.chunk_dim!r} is not an element "
                           f"dim of schema {schema.name!r}")
        self.base = {str(k): str(v) for k, v in base.items()}
        missing = [d for d in schema.all_dims
                   if d != self.chunk_dim and d not in self.base]
        if missing:
            raise KeyError(f"tensorstore base {self.base} missing dims "
                           f"{missing} of schema {schema.name!r}")
        #: explicit executor, or None to track the FDB client's own
        self._executor = executor
        #: consolidated-metadata catalogue for this array's dataset tree
        #: (owned by the facade, e.g. ``ChunkedFieldStore``); when set,
        #: metadata flips (create, reshard) mirror into it so whole-tree
        #: opens stay one fetch
        self.tree = tree

    @property
    def executor(self) -> ChunkExecutor:
        """This store's bounded I/O executor.  When none was passed in, the
        FDB client's own (``FDB.io_executor``) is resolved *per use*, not
        cached: the client rebuilds it on an ``io_parallelism`` config
        change, and a reference taken at construction would go stale (a
        shut-down pool)."""
        if self._executor is not None:
            return self._executor
        return self.fdb.io_executor

    @property
    def client(self):
        """What archives and flush barriers route through: the bound
        :class:`~repro.core.WriterSession` when there is one (per-session
        dirty tracking), the FDB client otherwise — both expose the same
        archive/flush/dirty surface."""
        return self.session if self.session is not None else self.fdb

    # -- identifiers -----------------------------------------------------------
    def _ident(self, chunk_value: str) -> Identifier:
        return Identifier({**self.base, self.chunk_dim: chunk_value})

    # -- lifecycle -------------------------------------------------------------
    def exists(self) -> bool:
        return self.fdb.retrieve(self._ident(META_CHUNK_KEY)).length() > 0

    def create(self, shape: Sequence[int], dtype,
               chunks: Optional[Sequence[int]] = None,
               codec: str = "raw",
               on_mismatch: str = "error") -> "ChunkedArray":
        """Archive the metadata object and return the (empty) array.

        Re-creating over an existing array with an *unchanged* layout is a
        clean transactional replace (FDB rule 5): the live generation is
        kept, so every new chunk key overwrites its predecessor.  A
        different chunk grid / dtype / codec cannot reuse the old keys —
        there is no per-object delete in the FDB API, so the old grid's
        chunk objects cannot be removed.  ``on_mismatch`` picks the policy:

        * ``"error"`` (default): raise :class:`LayoutMismatchError`; wipe
          the array's dataset first if the old data is expendable (what
          :meth:`repro.data.ChunkedFieldStore.put_field` does — the *wipe*
          policy, which reclaims space).
        * ``"retain"``: bump the layout generation — the new layout's
          chunks live under fresh generation-prefixed keys
          (:func:`chunk_key`) and the metadata replace flips readers over;
          old-generation chunks are retained versioned (unreachable, never
          readable as wrong data) until the dataset is wiped.  This is the
          policy :meth:`ChunkedArray.reshard` builds on.
        """
        if on_mismatch not in ("error", "retain"):
            raise ValueError(f"on_mismatch must be 'error' or 'retain', "
                             f"got {on_mismatch!r}")
        get_codec(codec)        # validate early
        shape = tuple(int(s) for s in shape)
        dtype = np.dtype(dtype)
        if chunks is None:
            chunks = auto_chunks(shape, dtype)
        meta = ArrayMeta(shape=shape, dtype=dtype.name,
                         chunks=tuple(int(c) for c in chunks), codec=codec)
        handle = self.fdb.retrieve(self._ident(META_CHUNK_KEY))
        if handle.length():
            old = ArrayMeta.from_bytes(handle.read())
            if old.layout_matches(meta):
                meta = old          # same layout: keep the live generation,
                # so re-written chunk keys land on (and replace) their
                # predecessors instead of forking a new namespace
            elif on_mismatch == "retain":
                meta = dataclasses.replace(meta,
                                           generation=old.generation + 1)
            else:
                raise LayoutMismatchError(
                    f"array at {self.base} already exists with layout "
                    f"{old} != {meta}; wipe it before re-creating with a "
                    f"different layout, or pass on_mismatch='retain' to "
                    f"version the old chunks out")
        self.client.archive(self._ident(META_CHUNK_KEY), meta.to_bytes())
        if self.tree is not None:
            self.tree.record(self.base[self.tree.member_dim], meta,
                             client=self.client)
        return ChunkedArray(self, meta)

    def open(self) -> "ChunkedArray":
        handle = self.fdb.retrieve(self._ident(META_CHUNK_KEY))
        if handle.length() == 0:
            raise FileNotFoundError(
                f"no tensorstore array at {self.base} "
                f"(backend {self.fdb.config.backend})")
        return ChunkedArray(self, ArrayMeta.from_bytes(handle.read()))

    def save(self, values, chunks: Optional[Sequence[int]] = None,
             codec: str = "raw") -> "ChunkedArray":
        """create() + write() + flush() in one call."""
        values = np.asarray(values)
        arr = self.create(values.shape, values.dtype, chunks=chunks,
                          codec=codec)
        arr.write(values)
        return arr

    def recover(self):
        """Crash-recovery sweep of this array slot's lease scope
        (:meth:`repro.core.FDB.recover`): purge TTL-expired leases,
        quarantine dead writers' archived-but-unflushed chunk intents, and
        — when the array exists — report chunk keys from layout
        generations *newer* than the live one (the debris of a reshard
        that died before its metadata flip).  Returns the
        :class:`repro.core.RecoveryReport`."""
        live = None
        handle = self.fdb.retrieve(self._ident(META_CHUNK_KEY))
        if handle.length():
            meta = ArrayMeta.from_bytes(handle.read())
            live = f"g{meta.generation}"
        return self.fdb.recover(self._ident(META_CHUNK_KEY),
                                live_resource=live)

    def garbage_report(self) -> "GarbageReport":
        """Account the retained old-generation chunk bytes of this array.

        Reshards and ``create(on_mismatch="retain")`` version superseded
        chunks out instead of deleting them (the FDB API has no per-object
        delete), so every re-layout leaves the previous generation's chunk
        objects behind — unreachable, never wrongly readable, but holding
        space until the array's dataset is wiped.  This walks the
        catalogue's entries for the array slot (``FDB.list``, index only,
        no payload I/O) and splits them into the live generation vs
        everything else — the groundwork for an old-generation reclamation
        pass (copy live generation + wipe), and a ``bench_tensorstore``
        column so the retained-garbage cost of a reshard stays visible.

        Only *flushed* entries are visible (rule 3), and only this store's
        collocation key (its ``writer``/``host`` base value) is scanned.
        """
        arr = self.open()       # live generation comes from the metadata
        live_gen = arr.meta.generation
        live_chunks = live_bytes = garbage_chunks = garbage_bytes = 0
        gens = set()
        for ident, loc in self.fdb.list(dict(self.base)):
            value = ident[self.chunk_dim]
            if value == META_CHUNK_KEY:
                continue
            gen = 0
            head = value.split(".", 1)[0]
            if head.startswith("g") and head[1:].isdigit():
                gen = int(head[1:])
            if gen == live_gen:
                live_chunks += 1
                live_bytes += loc.length
            else:
                garbage_chunks += 1
                garbage_bytes += loc.length
                gens.add(gen)
        return GarbageReport(live_generation=live_gen,
                             live_chunks=live_chunks, live_bytes=live_bytes,
                             garbage_chunks=garbage_chunks,
                             garbage_bytes=garbage_bytes,
                             garbage_generations=tuple(sorted(gens)))


@dataclasses.dataclass(frozen=True)
class GarbageReport:
    """What :meth:`TensorStore.garbage_report` found: catalogue-indexed
    chunk objects of the live layout generation vs retained older
    generations (bytes are stored object sizes, i.e. encoded)."""
    live_generation: int
    live_chunks: int
    live_bytes: int
    garbage_chunks: int
    garbage_bytes: int
    garbage_generations: Tuple[int, ...]


class ChunkedArray:
    def __init__(self, store: TensorStore, meta: ArrayMeta):
        self.store = store
        self.meta = meta
        self.grid: ChunkGrid = meta.grid()
        self._codec: Codec = get_codec(meta.codec)

    # -- introspection ---------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return self.meta.shape

    @property
    def dtype(self) -> np.dtype:
        return self.meta.npdtype

    @property
    def chunks(self) -> Tuple[int, ...]:
        return self.meta.chunks

    @property
    def n_chunks(self) -> Tuple[int, ...]:
        return self.grid.n_chunks

    def __repr__(self) -> str:
        return (f"ChunkedArray(shape={self.shape}, dtype={self.dtype.name}, "
                f"chunks={self.chunks}, codec={self.meta.codec}"
                + (f", generation={self.meta.generation}"
                   if self.meta.generation else "") + ")")

    def chunk_ident(self, idx: Index) -> Identifier:
        """FDB identifier of chunk ``idx`` under this array's live layout
        generation."""
        return self.store._ident(chunk_key(idx, self.meta.generation))

    # -- write path ------------------------------------------------------------
    def write_plan(self, key, values) -> "WritePlan":
        """Plan a write without moving data — the mirror of
        :meth:`read_plan`: every chunk the selection touches is resolved to
        its destination storage unit and coalescible chunks are grouped
        into batched store writes, staged at most one executor window at a
        time.  Use :meth:`WritePlan.write_ops` to see the store-level write
        count before (or without) executing.

        ``values`` broadcasts against the selection shape (so
        ``arr[10:20, :] = 0.0`` works).  The selection may be strided
        (``arr[::2] = v``): stride gaps are preserved via read-modify-write
        of the touched chunks.  Negative steps work too
        (``arr[::-1] = v``, ``arr[50:10:-4] = v``): like the read path,
        the selection normalises to its positive-step mirror — the I/O
        plan visits chunks in ascending order — and ``values`` is flipped
        once client-side so elements land exactly where NumPy assignment
        would put them.
        """
        sel, squeeze, flips = self.grid.normalize_read_key(key)
        sel_shape = self.grid.selection_shape(sel)
        values = np.asarray(values)
        if squeeze and values.ndim == len(sel_shape) - len(squeeze):
            # integer-indexed axes were dropped by the caller: re-insert them
            values = np.expand_dims(values, tuple(squeeze))
        values = np.broadcast_to(values.astype(self.dtype, copy=False),
                                 sel_shape)
        if flips:
            # reversed axes: the plan's selection ascends, so the flipped
            # view of the (already broadcast) values pairs values[0] with
            # the selection's *last* point — NumPy's reversed-assignment
            # order — while the I/O below stays the positive-step plan
            values = values[tuple(slice(None, None, -1) if a in flips
                                  else slice(None)
                                  for a in range(values.ndim))]
        return WritePlan(self, sel, values)

    def write(self, values, flush: bool = True) -> List[FieldLocation]:
        """Archive every chunk through a whole-array :class:`WritePlan`:
        same-shape chunks encode in one Pallas launch, chunks bound for one
        storage unit archive as batched store writes, staged one executor
        window at a time.  ``flush=True`` commits before returning (FDB
        visibility rule 3)."""
        values = np.asarray(values)
        if values.shape != self.shape:
            raise ValueError(f"write shape {values.shape} != array shape "
                             f"{self.shape}")
        key = (slice(None),) * self.grid.ndim
        return self.write_plan(key, values).execute(flush=flush)

    def write_at(self, key, values, flush: bool = True
                 ) -> List[FieldLocation]:
        """Chunk-aligned in-place assignment: ``arr[sel] = values``.

        Only chunks the selection touches are re-archived — through a
        :class:`WritePlan`, so coalescible chunks batch into store writes.
        Fully covered chunks are encoded from ``values`` directly;
        partially covered ones (including every chunk of a strided
        selection) do read-modify-write — fetch, patch, re-archive — with
        the fetches coalesced through a whole-chunk :class:`ReadPlan`; a
        chunk never written before patches onto zeros, the Zarr fill-value
        convention.

        Visibility (FDB rule 3): when RMW is needed and this client has
        unflushed archives, the FDB is flushed *before* fetching, so its own
        earlier unflushed chunks are seen rather than lost (no barrier is
        paid when the client is clean); ``flush=True`` commits the new chunk
        versions before returning.  With lossy codecs (``field8``/``field16``) RMW
        re-quantises the whole chunk, so untouched elements of partially
        covered chunks may shift within the quantisation bound.
        """
        return self.write_plan(key, values).execute(flush=flush)

    def __setitem__(self, key, values) -> None:
        self.write_at(key, values, flush=True)

    # -- read path -------------------------------------------------------------
    def read_plan(self, key, fill_missing: bool = True) -> "ReadPlan":
        """Plan a read without moving data: resolves every intersecting
        chunk to its backend handle and groups coalescible ones.  Use
        :meth:`ReadPlan.read_ops` to see the I/O-op count before (or
        without) executing.  The selection may be strided (``arr[::4]``),
        including *negative* steps (``arr[::-1]``, ``arr[50:10:-4]``):
        reversed slices normalise to their positive-step mirror — the I/O
        plan visits chunks in ascending order exactly as if the selection
        were forward — and the assembled output is flipped client-side.
        Only chunks holding a selected point are resolved at all.

        ``fill_missing=True`` (default) reads never-written chunks as zeros
        — the Zarr fill-value convention that makes sparsely-populated
        arrays (create + partial writes) readable.  The flip side: on a
        fully ``save()``\\ d array a missing chunk means lost or
        not-yet-flushed data, and zeros would mask that — pass
        ``fill_missing=False`` to get a ``KeyError`` at plan time instead
        (consumers that require every chunk present, e.g. checkpoint
        restores of dense tensors).
        """
        sel, squeeze, flips = self.grid.normalize_read_key(key)
        return ReadPlan(self, sel, squeeze, fill_missing=fill_missing,
                        flips=flips)

    def __getitem__(self, key) -> np.ndarray:
        return self.read_plan(key).execute()

    def read(self, fill_missing: bool = True) -> np.ndarray:
        """Read the whole array.  ``fill_missing=False`` raises ``KeyError``
        on never-written chunks instead of zero-filling — for consumers of
        dense arrays where a missing chunk means lost data."""
        key = (slice(None),) * self.grid.ndim
        return self.read_plan(key, fill_missing=fill_missing).execute()

    # -- reshard path ----------------------------------------------------------
    def reshard_plan(self, new_chunks, codec: Optional[str] = None,
                     sel=None, window: Optional[int] = None,
                     fill_missing: bool = True) -> "ReshardPlan":
        """Plan a re-layout of this array onto a new chunk grid (and
        optionally a new codec, or a strided sub-selection of the source)
        without moving data — see :class:`~.reshard.ReshardPlan`.  Use
        :meth:`~.reshard.ReshardPlan.read_ops` /
        :meth:`~.reshard.ReshardPlan.write_ops` to see the coalesced I/O-op
        counts before (or without) executing.

        Resharding is a whole-array re-layout — a *single-writer*
        administrative operation, not a leased range write — so it is not
        available through a writer session."""
        if self.store.session is not None:
            raise NotImplementedError(
                "reshard is a single-writer re-layout of the whole array "
                "slot and is not supported inside a writer session; run it "
                "on a session-less TensorStore")
        from .reshard import ReshardPlan
        return ReshardPlan(self, new_chunks, codec=codec, sel=sel,
                           window=window, fill_missing=fill_missing)

    def reshard(self, new_chunks, codec: Optional[str] = None, sel=None,
                window: Optional[int] = None, fill_missing: bool = True,
                flush: bool = True) -> "ChunkedArray":
        """Rewrite this array onto a new chunk grid — streaming, never
        materialising the whole array client-side.

        Each bounded batch of destination chunks is read from the source
        grid through one coalesced :class:`ReadPlan` and archived through
        one coalesced :class:`WritePlan`; the new grid's chunks live under
        a fresh layout generation, and a final transactional metadata
        replace (plus the ``flush=True`` commit barrier) flips readers onto
        the new grid.  Old-generation chunks are retained versioned —
        unreachable, reclaimed only by wiping the array's dataset.

        ``sel`` (optional, slices only) reshards a sub-selection — possibly
        strided, e.g. every other level — so a consumer grid can subsample
        the producer's; the array's shape becomes the selection's shape.
        ``codec`` re-encodes (e.g. raw → field16) on the way through.
        Returns this array, mutated onto the new layout.
        """
        self.reshard_plan(new_chunks, codec=codec, sel=sel, window=window,
                          fill_missing=fill_missing).execute(flush=flush)
        return self


class WritePlan:
    """Materialised write-side I/O plan for one selection of a
    :class:`ChunkedArray` — the mirror of :class:`ReadPlan`.

    Construction resolves the destination storage unit of every chunk the
    selection touches (:meth:`repro.core.FDB.archive_placement` — placement
    only, no data I/O; chunks of one array share their collocation, so one
    resolve covers the plan) and splits the plan into *stages* of at most
    one executor window (:attr:`window` chunks, from the executor's
    ``max_in_flight``).  Within a stage, chunks landing in the same unit —
    posix chunks appending into one writer's data file — archive as ONE
    batched store-level write (``FDB.archive_batch`` → a single buffered
    append), while object-store chunks keep one independent archive op in
    flight each — the two sides of the paper's object-store/POSIX
    trade-off, now symmetric with reads.  :meth:`write_ops` reports the
    store-level write count :meth:`execute` will issue.

    Staging bounds memory: a stage encodes its tiles (through the codec's
    batched single-kernel-launch path, :meth:`~.codec.Codec.encode_batch`;
    ragged edge chunks fall back per-chunk, byte-identical either way),
    archives them, and releases them before the next stage starts — so peak
    staged bytes are ~one executor window of encoded chunks regardless of
    plan size.  The trade-off: a posix plan larger than the window issues
    one batched write *per stage* instead of one total, still far below
    one-per-chunk.  Partially covered chunks fetch-and-patch first, with
    the stage's fetches coalesced through :meth:`ReadPlan.for_chunks` —
    adjacent posix RMW reads merge into single ranged reads exactly like
    normal reads.
    """

    def __init__(self, array: "ChunkedArray", sel, values: np.ndarray):
        self.array = array
        self.values = values
        store = array.store
        #: this client's tracer (repro.obs) — plan lifecycle spans
        self.tracer = store.fdb.tracer
        #: the bound writer session (multi-writer mode) or None
        self.session: Optional[WriterSession] = store.session
        with self.tracer.span("plan.resolve", kind="write") as sp:
            self._resolve_plan(sel, store)
            if sp is not None:
                sp.attrs["chunks"] = len(self.tasks)

    def _resolve_plan(self, sel, store: TensorStore) -> None:
        """Placement + staging + lease acquisition — the no-data-I/O half
        of the plan, wrapped in the ``plan.resolve`` span."""
        array = self.array
        #: (chunk_idx, within_chunk_slices, value_slices, fully_covered)
        self.tasks = list(array.grid.write_plan(sel))
        #: the client's decoded-chunk cache: every archived chunk is
        #: invalidated (and pended until the flush barrier), so a reader
        #: of this client can never be served bytes this write superseded
        self._cache = store.fdb.chunk_cache
        if self._cache is not None:
            self._cache_scope = ChunkCache.scope(store.base)
            self._cache_gen = array.meta.generation
        #: staging window: most chunks encoded/held at once (executor's
        #: in-flight bound, resolved at plan time)
        self.window = max(1, store.executor.max_in_flight)
        if self.tasks:
            # the chunk dim is an element dim, so every chunk of one array
            # shares (dataset, collocation) — one placement resolve covers
            # the whole plan
            placement = store.fdb.archive_placement(
                array.chunk_ident(self.tasks[0][0]))
            self._mergeable = placement.mergeable_with(placement)
        else:
            self._mergeable = False
        #: consecutive position runs staged (encoded + archived) together
        self.stages: List[List[int]] = [
            list(range(lo, min(lo + self.window, len(self.tasks))))
            for lo in range(0, len(self.tasks), self.window)]
        #: leases covering the touched chunks: (lo, hi, epoch, created) per
        #: disjoint linear chunk-id range — acquired HERE, at plan time, so
        #: overlapping writers fail fast (LeaseConflictError) before any
        #: byte moves; ``created`` marks ranges this plan acquired (vs
        #: ranges the session already held, which it must not release)
        self.leases: List[Tuple[int, int, int, bool]] = []
        if self.session is not None and self.tasks:
            grid = array.grid
            self._lease_ident = array.chunk_ident(self.tasks[0][0])
            #: lease resource = the live layout generation's chunk-id space
            #: (a reshard opens a fresh space, so leases die with layouts)
            self._lease_resource = f"g{array.meta.generation}"
            #: protocol-checker correlation attrs (docs/analysis.md): the
            #: canonical lease scope + the owning writer id, stamped on
            #: this plan's io.archive / rmw.fetch spans
            self._lease_scope = store.fdb.lease_scope(self._lease_ident)
            acquired: List[Tuple[int, int, int, bool]] = []
            try:
                for lo, hi in merge_id_ranges(
                        grid.linear_id(t[0]) for t in self.tasks):
                    created = not self.session.holds(
                        self._lease_ident, self._lease_resource, lo, hi)
                    epoch = self.session.acquire_lease(
                        self._lease_ident, self._lease_resource, lo, hi)
                    acquired.append((lo, hi, epoch, created))
            except BaseException:
                # roll back this plan's own acquisitions on a conflict
                # mid-way, so a failed plan holds nothing
                for lo, hi, _epoch, created in acquired:
                    if created:
                        self.session.release_lease(
                            self._lease_ident, self._lease_resource, lo, hi)
                raise
            self.leases = acquired

    def check_leases(self) -> None:
        """Epoch-fencing gate (session-bound plans only): raise
        :class:`~repro.core.StaleLeaseError` unless every lease backing
        this plan is still current.  :meth:`execute` runs it before the RMW
        fetches and before each stage's archives, so a writer whose lease
        was broken and re-acquired aborts instead of committing."""
        if self.session is not None:
            for lo, hi, epoch, _created in self.leases:
                self.session.check_lease(self._lease_ident,
                                         self._lease_resource, lo, hi, epoch)

    def release_leases(self) -> None:
        """Release the leases this plan acquired (ranges the session
        already held stay held).  Called by :meth:`execute` after its
        commit barrier; call it directly to abandon a planned-but-never-
        executed write."""
        if self.session is not None:
            kept = []
            for lo, hi, epoch, created in self.leases:
                if created:
                    self.session.release_lease(
                        self._lease_ident, self._lease_resource, lo, hi)
                else:
                    kept.append((lo, hi, epoch, created))
            self.leases = kept

    def _protocol_attrs(self) -> dict:
        """Correlation attrs for the protocol checker (docs/analysis.md):
        which writer archived under which lease scope/resource on which
        client.  Empty on the single-writer (sessionless) path — there is
        no lease contract to check."""
        if self.session is None:
            return {}
        return {"owner": self.session.writer_id,
                "scope": self._lease_scope,
                "resource": self._lease_resource,
                "client": self.session.fdb.client_id}

    def _stage_groups(self, stage: List[int]) -> List[List[int]]:
        """Positions-into-tasks per batched store write within one stage."""
        if self._mergeable:
            return [list(stage)]
        return [[pos] for pos in stage]

    @property
    def groups(self) -> List[List[int]]:
        """Positions-into-tasks per batched store write, across stages."""
        return [g for stage in self.stages for g in self._stage_groups(stage)]

    @property
    def n_chunks(self) -> int:
        return len(self.tasks)

    @property
    def rmw_chunks(self) -> int:
        """Chunks only partially covered by the selection — they fetch and
        patch (read-modify-write) before re-encoding."""
        return sum(1 for _i, _c, _v, full in self.tasks if not full)

    def write_ops(self) -> int:
        """Store-level write operations :meth:`execute` will issue (after
        coalescing, one batch per storage unit per stage) — the twin of
        :meth:`ReadPlan.read_ops`."""
        return sum(len(self._stage_groups(stage)) for stage in self.stages)

    def execute(self, flush: bool = True,
                deadline: Optional[float] = None) -> List[FieldLocation]:
        """Stage by stage: fetch-and-patch (coalesced), encode (batched),
        archive (one submission per group), release — and, with
        ``flush=True``, commit (FDB visibility rule 3) and release this
        plan's leases.  Returns per-chunk :class:`FieldLocation`\\ s in
        plan order.

        Session-bound plans run the epoch-fencing gate before the RMW
        fetches and before every stage's archives; with ``flush=False`` the
        leases stay held (the chunks are archived but not yet visible — the
        session's later flush/close is the commit barrier, and releasing
        earlier would let the next holder RMW not-yet-visible bytes).

        ``deadline`` (seconds) is the *plan's* retry budget: it rides the
        ambient :func:`repro.core.deadline_scope` through the executor
        hand-off, so every facade-level retry under this plan gives up with
        :class:`repro.core.DeadlineExceeded` once the shared budget runs
        out rather than each op backing off independently.
        """
        if not self.tasks:
            return []
        with self.tracer.span("plan.execute", kind="write",
                              chunks=self.n_chunks, stages=len(self.stages),
                              rmw=self.rmw_chunks):
            with deadline_scope(deadline):
                return self._execute(flush)

    def _execute(self, flush: bool) -> List[FieldLocation]:
        arr, values = self.array, self.values
        store, codec = arr.store, arr._codec
        fdb = store.fdb
        metrics = self.tracer.metrics
        # archives/barriers route per session when one is bound — its
        # dirty bit decides the RMW pre-flush (sound because the RMW
        # chunks are covered by OUR lease: no other session's unflushed
        # archives can be hiding under them).  Deliberately the session
        # captured at PLAN time, not store.client: the leases recorded on
        # this plan belong to that session
        client = self.session or fdb
        if self.rmw_chunks and client.dirty:
            client.flush()      # make own unflushed chunks RMW-visible
        locs: List[Optional[FieldLocation]] = [None] * len(self.tasks)
        for si, stage in enumerate(self.stages):
            with self.tracer.span("plan.stage", stage=si,
                                  chunks=len(stage)):
                self._run_stage(stage, locs, client, codec, values, metrics)
        if flush:
            client.flush()
            self.release_leases()
        return locs             # type: ignore[return-value]

    def _run_stage(self, stage: List[int], locs, client, codec,
                   values: np.ndarray, metrics) -> None:
        arr, store = self.array, self.array.store
        tiles: List[Optional[np.ndarray]] = [None] * len(stage)
        rmw = [(k, pos) for k, pos in enumerate(stage)
               if not self.tasks[pos][3]]
        if rmw:             # coalesced whole-chunk fetches, then patch
            # lease-protected fetch: fence before reading bytes we are
            # about to patch — a broken lease means another writer may
            # own (and be mid-write on) these chunks
            self.check_leases()
            metrics.counter("rmw.fetched_chunks").inc(len(rmw))
            with self.tracer.span("rmw.fetch", chunks=len(rmw),
                                  **self._protocol_attrs()):
                fetch = ReadPlan.for_chunks(
                    arr, [self.tasks[pos][0] for _k, pos in rmw])
                for (k, pos), tile in zip(rmw, fetch.read_chunks()):
                    _idx, chunk_sel, val_sel, _full = self.tasks[pos]
                    tile[chunk_sel] = values[val_sel]
                    tiles[k] = tile
        for k, pos in enumerate(stage):
            _idx, _chunk_sel, val_sel, full = self.tasks[pos]
            if full:
                tiles[k] = values[val_sel]
        with self.tracer.span("codec.encode", chunks=len(stage),
                              codec=codec.name) as sp:
            blobs = codec.encode_batch(tiles)
            nbytes = sum(len(b) for b in blobs)
            if sp is not None:
                sp.attrs["nbytes"] = nbytes
        metrics.counter("codec.bytes_encoded").inc(nbytes)
        metrics.counter("codec.values_encoded").inc(
            sum(int(t.size) for t in tiles))
        idents = [arr.chunk_ident(self.tasks[pos][0]) for pos in stage]
        #: linear chunk ids per stage position — io.archive spans carry
        #: them so the checker can test lease coverage per archived chunk
        lin = ([arr.grid.linear_id(self.tasks[pos][0]) for pos in stage]
               if self.session is not None else None)

        def put(ks: List[int]) -> List[FieldLocation]:
            # one store-level submission per group: a posix group lands
            # as a single buffered append; object groups are singletons
            with self.tracer.span("io.archive", chunks=len(ks),
                                  backend=store.fdb.config.backend,
                                  **self._protocol_attrs()) as sp:
                batch_locs = client.archive_batch(
                    [(idents[k], blobs[k]) for k in ks])
                if sp is not None:
                    sp.attrs["nbytes"] = sum(len(blobs[k]) for k in ks)
                    if lin is not None:
                        sp.attrs["chunk_ids"] = [lin[k] for k in ks]
            if lin is not None:
                # crash-recovery breadcrumb: these chunks are archived but
                # not yet flushed — journal them deployment-wide so
                # fdb.recover() can quarantine them if this writer dies
                # before its commit barrier (flush clears the journal)
                self.session.mark_dirty_chunks(
                    self._lease_ident, self._lease_resource,
                    [lin[k] for k in ks])
            if self._cache is not None:
                # archived ≠ visible (rule 3): drop the superseded entry
                # and pend the key until this client's flush publishes it
                for k in ks:
                    self._cache.invalidate(
                        (self._cache_scope, self._cache_gen,
                         tuple(self.tasks[stage[k]][0])))
            return batch_locs

        # the fencing gate runs per stage, right before its archives: a
        # stale writer loses at most one in-flight stage to the race
        # window between check and archive, and can never pass another
        # barrier after its lease was re-acquired
        self.check_leases()
        # the one grouping decision lives in _stage_groups — write_ops()
        # accounting and execution must never diverge (check.sh asserts
        # on the plan's claim); stages are contiguous position runs, so
        # stage-local index = position - stage[0]
        kgroups = [[pos - stage[0] for pos in group]
                   for group in self._stage_groups(stage)]
        batches = store.executor.map_ordered(
            put, kgroups,
            describe=lambda ks: (
                f"op=io.archive backend={store.fdb.config.backend} "
                f"chunk_ids="
                f"{[lin[k] for k in ks] if lin is not None else [stage[k] for k in ks]}"))
        for ks, batch_locs in zip(kgroups, batches):
            for k, loc in zip(ks, batch_locs):
                locs[stage[k]] = loc


class ReadPlan:
    """Materialised I/O plan for one selection of a :class:`ChunkedArray`.

    Chunk identifiers are resolved to backend :class:`DataHandle`\\ s up
    front (catalogue lookups only — no payload I/O), then grouped with
    :func:`repro.core.group_mergeable`: handles over the same storage unit
    (posix chunks living in one writer's data file) merge, so adjacent
    chunks coalesce into single ranged reads — the POSIX backend's key read
    optimisation — while object-store chunks stay one independent op each,
    which is what those backends want kept in flight.

    Execution is staged, the mirror of :class:`WritePlan`: the I/O batches
    are split, in plan order, into :attr:`stages` of at most one executor
    window (:attr:`window` chunks, from the executor's ``max_in_flight``);
    a single batch larger than the window is a stage of its own.  Each
    stage is one executor task: it fetches its batches in order (an
    object-store chunk stays one independent op), decodes every fetched
    chunk in ONE :meth:`~.codec.Codec.decode_batch` call — one kernel
    launch per chunk geometry, counted in ``codec.decode_launches`` — and
    assembles them.  Stages run concurrently on the executor's workers,
    each plan on its fair share of them (all of them when it runs alone),
    so up to ``max_workers`` ops are in flight, as with one task per
    batch, with one hand-off between threads per stage, not per chunk.
    Only chunks the cache missed are staged; cached and never-written
    chunks take no I/O.

    Two consumption modes share the stages: :meth:`execute` assembles the
    selection into one output array (strided selections scatter through
    their strided within-chunk slices), while :meth:`read_chunks` — on
    plans built by :meth:`for_chunks` — returns whole decoded chunks, the
    write path's coalesced RMW fetch.
    """

    def __init__(self, array: "ChunkedArray", sel, squeeze,
                 fill_missing: bool = True,
                 flips: Sequence[int] = ()):
        self.array = array
        self.sel = sel
        self.squeeze = squeeze
        self.tracer = array.store.fdb.tracer
        #: axes to reverse client-side after assembly — how negative-step
        #: selections are served from a positive-step (ascending) I/O plan
        self.flips = tuple(flips)
        self.tasks = list(array.grid.intersecting(sel))
        self._bind_cache(array.store.fdb.chunk_cache)
        with self.tracer.span("plan.resolve", kind="read",
                              chunks=len(self.tasks)):
            self._resolve(fill_missing)

    @classmethod
    def for_chunks(cls, array: "ChunkedArray", indices: Sequence[Index],
                   fill_missing: bool = True) -> "ReadPlan":
        """Plan whole-chunk fetches for an explicit chunk-index list — the
        write path's RMW hook (:meth:`read_chunks` consumes it): the listed
        chunks resolve and coalesce exactly like a selection's, so adjacent
        posix RMW fetches merge into single ranged reads."""
        plan = cls.__new__(cls)
        plan.array = array
        plan.sel = None
        plan.squeeze = ()
        plan.flips = ()
        plan.tracer = array.store.fdb.tracer
        plan.tasks = [
            (tuple(idx),
             tuple(slice(0, n, 1) for n in array.grid.chunk_shape(idx)),
             None)
            for idx in indices]
        # RMW fetches bypass the chunk cache entirely (no lookup, no
        # populate): the fetched bytes are about to be patched and
        # re-archived, so caching them would pin a doomed version
        plan._bind_cache(None)
        with plan.tracer.span("plan.resolve", kind="chunks",
                              chunks=len(plan.tasks)):
            plan._resolve(fill_missing)
        return plan

    def _bind_cache(self, cache: Optional[ChunkCache]) -> None:
        """Attach the client's decoded-chunk cache (or None).  Hits are
        collected during :meth:`_resolve` — cached chunks never resolve a
        handle, so they are invisible to :meth:`read_ops` and issue no
        backend ops at all."""
        self._cache = cache
        #: position → decoded chunk served from the cache
        self._cached: dict = {}
        #: position → cache version token for a post-fetch populate
        self._tokens: dict = {}
        if cache is not None:
            self._cache_scope = ChunkCache.scope(self.array.store.base)
            self._cache_gen = self.array.meta.generation

    @property
    def cache_hits(self) -> int:
        """Chunks of this plan served from the decoded-chunk cache."""
        return len(self._cached)

    def _consult_cache(self) -> None:
        if self._cache is None or not self.tasks:
            return
        with self.tracer.span("cache.lookup", chunks=len(self.tasks)) as sp:
            for pos, task in enumerate(self.tasks):
                key = (self._cache_scope, self._cache_gen, tuple(task[0]))
                chunk, token = self._cache.lookup(key)
                if chunk is not None:
                    self._cached[pos] = chunk
                else:
                    self._tokens[pos] = token
            if sp is not None:
                sp.attrs["hits"] = len(self._cached)
                sp.attrs["misses"] = len(self._tokens)

    def _populate_cache(self, pos: int, chunk: np.ndarray) -> None:
        """Offer a freshly decoded chunk to the cache (no-op when the key
        was invalidated or pended since :meth:`_consult_cache` issued the
        token — a concurrent overwrite wins)."""
        token = self._tokens.get(pos) if self._cache is not None else None
        if token is not None:
            self._cache.put(
                (self._cache_scope, self._cache_gen,
                 tuple(self.tasks[pos][0])), chunk, token)

    def _resolve(self, fill_missing: bool) -> None:
        """Resolve every task's chunk to its backend handle and group
        coalescible handles into I/O batches (no data I/O)."""
        store = self.array.store
        # cache consult FIRST: a hit never resolves a handle, so cached
        # chunks are invisible to read_ops() and reach no backend at all
        self._consult_cache()
        present: List[int] = []
        handles = []
        #: positions of chunks never written — they read as zeros (the same
        #: fill-value convention the write path patches onto), no I/O
        self.missing: List[int] = []
        for pos, (idx, _chunk_sel, _out_sel) in enumerate(self.tasks):
            if pos in self._cached:
                continue
            h = store.fdb.retrieve_handle(self.array.chunk_ident(idx))
            if h is None or h.length() == 0:
                if not fill_missing:
                    raise KeyError(
                        f"missing chunk {idx} of array at {store.base}")
                self.missing.append(pos)
            else:
                present.append(pos)
                handles.append(h)
        #: (positions-into-tasks, merged handle) per I/O batch
        self.batches: List[Tuple[List[int], MultiHandle]] = [
            ([present[i] for i in group],
             MultiHandle([handles[i] for i in group]))
            for group in group_mergeable(handles)]
        #: most chunks fetched and decoded together (the executor's
        #: in-flight bound, resolved at plan time as WritePlan.window is)
        self.window = max(1, store.executor.max_in_flight)
        #: indices into ``batches`` per stage: consecutive batches holding
        #: at most ``window`` chunks, or one batch larger than that
        self.stages: List[List[int]] = []
        held = 0
        for b, (positions, _mh) in enumerate(self.batches):
            if not self.stages or held + len(positions) > self.window:
                self.stages.append([])
                held = 0
            self.stages[-1].append(b)
            held += len(positions)

    @property
    def n_chunks(self) -> int:
        return len(self.tasks)

    def read_ops(self) -> int:
        """I/O operations :meth:`execute` will issue (after coalescing)."""
        return sum(mh.read_ops() for _g, mh in self.batches)

    def read_chunks(self) -> List[np.ndarray]:
        """Decode every planned chunk *whole*, in task order — always
        writable, missing chunks as zeros (fill-value convention).  Staged
        like :meth:`execute`: coalesced reads through the bounded executor,
        one batched decode per stage — the write path's RMW fetch."""
        arr = self.array
        grid = arr.grid
        out: List[Optional[np.ndarray]] = [None] * len(self.tasks)
        for pos in self.missing:
            out[pos] = np.zeros(grid.chunk_shape(self.tasks[pos][0]),
                                arr.dtype)
        for pos, cached in self._cached.items():
            out[pos] = cached.copy()    # cached entries are read-only

        def take(positions: List[int], chunks: List[np.ndarray]) -> None:
            for pos, chunk in zip(positions, chunks):
                self._populate_cache(pos, chunk)
                out[pos] = chunk if chunk.flags.writeable else chunk.copy()
        self._run_stages(take)
        return out              # type: ignore[return-value]

    def _fetch(self, mh: MultiHandle, n_chunks: int) -> List[bytes]:
        """One coalesced backend read, wrapped in the ``io.fetch`` span
        (the ``t_io`` phase) and counted into ``codec.bytes_decoded`` —
        running on an executor worker thread with the caller's span
        context propagated."""
        backend = self.array.store.fdb.config.backend
        with self.tracer.span("io.fetch", ops=mh.read_ops(),
                              chunks=n_chunks, backend=backend) as sp:
            parts = mh.read_parts()
            nbytes = sum(len(p) for p in parts)
            if sp is not None:
                sp.attrs["nbytes"] = nbytes
        self.tracer.metrics.counter("codec.bytes_decoded").inc(nbytes)
        return parts

    def _run_stages(self, take: Callable[[List[int], List[np.ndarray]],
                                         None]) -> None:
        """One executor task per stage: fetch its I/O batches in plan
        order, decode all their chunks in one call, and hand
        (positions-into-tasks, decoded chunks) to ``take``.  The plan
        keeps at most its fair share of the executor's workers busy
        (``map_ordered(fair=True)``): a lone plan overlaps its stages on
        every worker, concurrent plans progress at equal rates."""
        backend = self.array.store.fdb.config.backend

        def chunks_of(stage: List[int]) -> list:
            return [self.tasks[pos][0] for b in stage
                    for pos in self.batches[b][0]]

        def run_stage(stage: List[int]) -> None:
            batches = [self.batches[b] for b in stage]
            positions = [pos for group, _mh in batches for pos in group]
            parts = [part for group, mh in batches
                     for part in self._fetch(mh, len(group))]
            take(positions, self._decode(positions, parts))

        self.array.store.executor.map_ordered(
            run_stage, self.stages, fair=True,
            describe=lambda stage: (
                f"op=io.fetch backend={backend} chunks={chunks_of(stage)}"))

    def _decode(self, positions: List[int],
                parts: List[bytes]) -> List[np.ndarray]:
        """Decode one stage's chunks in one batched call (equal-shape
        chunks share a kernel launch), counted into
        ``codec.values_decoded``."""
        arr = self.array
        shapes = [arr.grid.chunk_shape(self.tasks[pos][0])
                  for pos in positions]
        with self.tracer.span("codec.decode", chunks=len(positions),
                              codec=arr._codec.name):
            chunks = arr._codec.decode_batch(parts, shapes, arr.dtype)
        self.tracer.metrics.counter("codec.values_decoded").inc(
            sum(math.prod(s) for s in shapes))
        return chunks

    def execute(self, deadline: Optional[float] = None) -> np.ndarray:
        """Assemble the selection.  ``deadline`` (seconds) bounds the
        plan's facade-level retries via the ambient
        :func:`repro.core.deadline_scope`, like the write side."""
        if self.sel is None:
            raise TypeError("whole-chunk plan (for_chunks) has no selection "
                            "to assemble; use read_chunks()")
        arr = self.array
        with self.tracer.span("plan.execute", kind="read",
                              chunks=self.n_chunks,
                              batches=len(self.batches),
                              stages=len(self.stages)), \
                deadline_scope(deadline):
            out = np.empty(arr.grid.selection_shape(self.sel), arr.dtype)
            for pos in self.missing:
                out[self.tasks[pos][2]] = 0
            with self.tracer.span("plan.assemble",
                                  chunks=len(self._cached)):
                for pos, cached in self._cached.items():
                    _idx, chunk_sel, out_sel = self.tasks[pos]
                    out[out_sel] = cached[chunk_sel]

            def take(positions: List[int], chunks: List[np.ndarray]) -> None:
                # stages scatter into disjoint output regions → concurrent
                # assembly is safe
                with self.tracer.span("plan.assemble",
                                      chunks=len(positions)):
                    for pos, chunk in zip(positions, chunks):
                        self._populate_cache(pos, chunk)
                        _idx, chunk_sel, out_sel = self.tasks[pos]
                        out[out_sel] = chunk[chunk_sel]
            self._run_stages(take)
        if self.flips:          # negative-step axes: one client-side flip
            out = out[tuple(slice(None, None, -1) if a in self.flips
                            else slice(None) for a in range(out.ndim))]
        if self.squeeze:
            out = out.reshape(tuple(
                s for a, s in enumerate(out.shape) if a not in self.squeeze))
        return out
