"""The FDB facade (thesis §2.7): archive / flush / retrieve / list / axes.

Backend-agnostic: pairs any conforming Catalogue with any conforming Store
(``FDBConfig``), enforcing the API semantics (see ``docs/architecture.md``
for how the tensorstore plans lean on each rule):

1. data is visible-and-indexed or not (ACID);
2. ``archive()`` blocks until the FDB controls (a copy of) the data;
3. ``flush()`` blocks until all archived data is persistent + visible —
   archive-without-flush is not readable, *not even by the archiving
   client itself* (which is why RMW and reshard paths pre-flush);
4. visible data is immutable;
5. re-archiving an identifier transactionally replaces it — the only
   "update" primitive, and the hook layout flips (tensorstore metadata
   replace) build on.

Deliberately absent: a per-object delete.  ``wipe()`` removes whole
datasets (container destroy), so layers that re-layout data under live
identifiers must *version* superseded objects out (the tensorstore's
generation-prefixed chunk keys) rather than delete them.
"""
from __future__ import annotations

import contextvars
import dataclasses
import itertools
import re
import threading
import time
import weakref
from typing import (Callable, Dict, Iterator, List, Mapping, Optional,
                    Sequence, Tuple, Union)

import numpy as np

from .engine.daos import DaosEngine
from .engine.meter import GLOBAL_METER, Meter
from .engine.rados import RadosEngine
from .engine.s3 import S3Engine
from .faults import FaultInjector
from .handle import (DataHandle, FieldLocation, MultiHandle, PlacementHandle,
                     group_mergeable)
from .interfaces import Catalogue, Store
from .lease import Lease, LeaseConflictError, StaleLeaseError
from .retry import RetryPolicy
from .schema import (CHECKPOINT_SCHEMA, Identifier, NWP_OBJECT_SCHEMA,
                     NWP_POSIX_SCHEMA, SCHEMAS, Schema)
from repro.obs.locks import NamedLock
from repro.obs.trace import GLOBAL_TRACER, Span, Tracer

BytesLike = Union[bytes, bytearray, memoryview, np.ndarray]

#: process-wide FDB client sequence — client_id labels in spans ("c3")
#: distinguish clients when several share one tracer (GLOBAL_TRACER)
_CLIENT_SEQ = itertools.count(1)

#: ambient re-validation hook for facade-level retries: a WriterSession
#: installs its lease re-validation (``check_held``) here around its
#: archive calls, so a retried archive re-fences its epochs *before*
#: re-archiving — a broken lease aborts the retry with StaleLeaseError
#: instead of silently double-archiving into a re-acquired range.  A
#: ContextVar so it survives the executor's context hand-off.
_ON_RETRY: "contextvars.ContextVar[Optional[Callable[[], None]]]" = \
    contextvars.ContextVar("fdb_retry_revalidate", default=None)

#: element values of generation-versioned chunk keys ("g2.c0.1") — the
#: stale-generation scan recover() runs after a half-flipped reshard
_GEN_RE = re.compile(r"g(\d+)\.")


def _as_bytes(data: BytesLike) -> bytes:
    if isinstance(data, np.ndarray):
        return data.tobytes()
    return bytes(data)


def as_identifier(identifier: Union[Identifier, Mapping[str, object]]
                  ) -> Identifier:
    """Canonicalise one user-supplied identifier mapping — the single place
    non-string values are handled for every FDB entry point.

    Scalar values are stringified (``{"step": 0}`` ≡ ``{"step": "0"}``, the
    way ``axes()`` always did), and sequence values become ``/``-joined
    multi-value request expressions (``{"step": [0, 6]}`` ≡
    ``{"step": "0/6"}``), matching :meth:`Identifier.matches` semantics.
    Request expressions are only meaningful on the retrieve side;
    ``archive()`` rejects them.
    Lists/tuples keep the caller's order (which fixes the byte order of a
    multi-object ``retrieve().read()``); unordered sets are sorted *by their
    string form* ("12" < "2") purely for determinism — callers that care
    about payload order should pass a list.
    """
    if isinstance(identifier, Identifier):
        return identifier
    out: Dict[str, str] = {}
    for k, v in identifier.items():
        if isinstance(v, (set, frozenset)):
            v = "/".join(sorted(str(x) for x in v))
        elif isinstance(v, (list, tuple)):
            v = "/".join(str(x) for x in v)
        out[str(k)] = str(v)
    return Identifier(out)


@dataclasses.dataclass
class FDBConfig:
    """Deployment-time configuration (the FDB administrator's file)."""
    backend: str = "daos"                 # daos | rados | posix | s3
    schema: Union[str, Schema] = "nwp-object"
    root: str = "/tmp/fdb"                # posix backend root dir
    pool: str = "fdb"
    # engine sizing (per simulated deployment)
    daos_targets: int = 16
    rados_osds: int = 16
    rados_pg_count: int = 512
    rados_max_object_size: int = 128 * 1024 * 1024
    lustre_osts: int = 16
    lustre_stripe_count: int = 8
    lustre_stripe_size: int = 8 * 1024 * 1024
    # backend design options (thesis Fig. 3.5 sweeps)
    rados_encapsulation: str = "namespace"
    rados_object_mode: str = "per_field"
    rados_persistence: str = "immediate"
    rados_replication: int = 1
    rados_ec: Optional[Tuple[int, int]] = None
    daos_oclass: str = "OC_S1"
    s3_object_mode: str = "per_field"
    # catalogue/store cross-pairing: e.g. s3 store needs another catalogue
    catalogue_backend: Optional[str] = None
    #: batched-archive overlap depth (archive_many / tensorstore writes);
    #: <= 1 serializes archives
    io_parallelism: int = 8
    #: decoded-chunk LRU cache budget for this client's readers
    #: (``fdb.chunk_cache``); 0 disables the cache entirely — the default,
    #: so op-count accounting stays exact unless serving opts in
    #: (``ChunkedFieldStore`` turns it on)
    chunk_cache_bytes: int = 0
    chunk_cache_entries: int = 1024

    def resolved_schema(self) -> Schema:
        if isinstance(self.schema, Schema):
            return self.schema
        return SCHEMAS[self.schema]


#: process-global shared engines, keyed by config identity — multiple FDB
#: instances (writer + reader "processes") hit the same simulated cluster.
_ENGINES: Dict[Tuple, object] = {}
_ENGINES_LOCK = threading.Lock()


def shared_engine(kind: str, cfg: FDBConfig, meter: Optional[Meter] = None):
    key = (kind, cfg.pool, cfg.daos_targets, cfg.rados_osds,
           cfg.rados_pg_count, cfg.rados_max_object_size, id(meter))
    with _ENGINES_LOCK:
        eng = _ENGINES.get(key)
        if eng is None:
            if kind == "daos":
                eng = DaosEngine(n_targets=cfg.daos_targets, meter=meter)
            elif kind == "rados":
                eng = RadosEngine(n_osds=cfg.rados_osds,
                                  max_object_size=cfg.rados_max_object_size,
                                  meter=meter)
            elif kind == "s3":
                eng = S3Engine(meter=meter)
            else:
                raise ValueError(kind)
            _ENGINES[key] = eng
        return eng


def reset_engines() -> None:
    with _ENGINES_LOCK:
        _ENGINES.clear()


class FDB:
    """One FDB client instance ≈ one producer/consumer process."""

    def __init__(self, config: Optional[FDBConfig] = None,
                 meter: Optional[Meter] = None,
                 tracer: Optional[Tracer] = None,
                 retry: Optional[RetryPolicy] = None,
                 faults: Optional[FaultInjector] = None, **overrides):
        if config is None:
            config = FDBConfig(**overrides)
        elif overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config
        self.schema = config.resolved_schema()
        self.meter = meter or GLOBAL_METER
        #: structured tracing + metrics (repro.obs); defaults to the shared
        #: process tracer, disabled out of the box — pass a private
        #: ``Tracer(enabled=True)`` for an isolated per-client buffer
        self.tracer = tracer or GLOBAL_TRACER
        #: facade-level retry policy: transient backend errors on the
        #: archive / flush / retrieve-handle paths are re-driven through it
        #: (safe per rule 5 — re-archiving transactionally replaces)
        self.retry = retry if retry is not None else RetryPolicy()
        #: fault injection (tests/chaos bench): wraps the freshly built
        #: backend pair so every data-path op consults the injector
        self.faults = faults
        #: stable per-process client label carried on flush/archive spans,
        #: so the protocol checker can attribute barriers when several
        #: clients share one tracer
        self.client_id = f"c{next(_CLIENT_SEQ)}"
        self.store, self.catalogue = self._build_backends()
        if faults is not None:
            self.store, self.catalogue = faults.wrap(self.store,
                                                     self.catalogue)
        # count TTL expiries on this client's metrics (the listener fires
        # whichever client's purge sweep finds them)
        _m = self.tracer.metrics
        self.catalogue.lease_table().add_expiry_listener(
            lambda leases: _m.counter("lease.expired").inc(len(leases)))
        self._closed = False
        self._dirty = False
        self._io_executor = None        # lazily built, see io_executor
        self._io_executor_size = 0
        self._chunk_cache = None        # lazily built, see chunk_cache
        self._io_lock = NamedLock("fdb.io")
        #: serialises flush(): concurrent barriers (two writer sessions
        #: committing at once) would race the posix catalogue's
        #: getsize-then-append partial-index bookkeeping
        self._flush_lock = NamedLock("fdb.flush")
        #: archive sequence number (with its lock): flush() clears dirty
        #: flags only when no archive landed since it captured the marker,
        #: so a chunk archived *during* another session's barrier can never
        #: be marked clean while still unpublished
        self._archive_seq = 0
        self._dirty_lock = NamedLock("fdb.dirty")
        #: live writer sessions of this client (weak: an abandoned session
        #: must not keep the client's dirty bookkeeping alive)
        self._sessions: "weakref.WeakSet[WriterSession]" = weakref.WeakSet()

    # -- backend wiring ------------------------------------------------------
    def _build_backends(self) -> Tuple[Store, Catalogue]:
        cfg = self.config
        cat_kind = cfg.catalogue_backend or cfg.backend
        store: Store
        catalogue: Catalogue
        if cfg.backend == "daos":
            eng = shared_engine("daos", cfg, self.meter)
            store = DaosStore(eng, pool=cfg.pool, oclass=cfg.daos_oclass)
        elif cfg.backend == "rados":
            eng = shared_engine("rados", cfg, self.meter)
            store = RadosStore(eng, pool=cfg.pool,
                               encapsulation=cfg.rados_encapsulation,
                               object_mode=cfg.rados_object_mode,
                               persistence=cfg.rados_persistence,
                               pg_count=cfg.rados_pg_count,
                               replication=cfg.rados_replication,
                               ec=cfg.rados_ec)
        elif cfg.backend == "posix":
            sim = self._shared_lustre(cfg)
            store = PosixStore(sim)
        elif cfg.backend == "s3":
            eng = shared_engine("s3", cfg, self.meter)
            store = S3Store(eng, object_mode=cfg.s3_object_mode)
            if cfg.catalogue_backend is None:
                cat_kind = "daos"   # S3 has no conforming catalogue (§3.3)
        else:
            raise ValueError(f"unknown backend {cfg.backend!r}")

        if cat_kind == "daos":
            eng = shared_engine("daos", cfg, self.meter)
            catalogue = DaosCatalogue(eng, self.schema, pool=cfg.pool)
        elif cat_kind == "rados":
            eng = shared_engine("rados", cfg, self.meter)
            catalogue = RadosCatalogue(eng, self.schema, pool=cfg.pool,
                                       persistence=cfg.rados_persistence)
        elif cat_kind == "posix":
            catalogue = PosixCatalogue(self._shared_lustre(cfg), self.schema)
        else:
            raise ValueError(f"no conforming catalogue for {cat_kind!r}")
        return store, catalogue

    def _shared_lustre(self, cfg: FDBConfig) -> "LustreSim":
        # geometry is part of the identity (mirroring shared_engine): two
        # FDBs on one root with different OST/stripe settings must not
        # silently share a sim, or stripe-geometry sweeps measure the first
        # configuration repeatedly
        key = ("lustre", cfg.root, cfg.lustre_osts, cfg.lustre_stripe_count,
               cfg.lustre_stripe_size, id(self.meter))
        with _ENGINES_LOCK:
            sim = _ENGINES.get(key)
            if sim is None:
                sim = LustreSim(cfg.root, n_osts=cfg.lustre_osts,
                                stripe_count=cfg.lustre_stripe_count,
                                stripe_size=cfg.lustre_stripe_size,
                                meter=self.meter)
                _ENGINES[key] = sim
        return sim

    # -- the four primary API methods (Listing 2.2) -----------------------------
    def _split_archivable(self, identifier: Union[Identifier,
                                                  Mapping[str, object]]):
        """Canonicalise + split an archive identifier, rejecting multi-value
        request expressions ("0/6", or a sequence value): they would
        catalogue the object under a key no retrieve can ever expand back
        to — archive one object per fully-specified identifier."""
        ident = as_identifier(identifier)
        multi = [k for k, v in ident.items() if "/" in v]
        if multi:
            raise ValueError(
                f"archive identifier {ident!r} has multi-value request "
                f"expressions on dims {multi}; archive one object per "
                f"fully-specified identifier")
        return self.schema.split(ident)

    def archive(self, identifier: Union[Identifier, Mapping[str, object]],
                data: BytesLike) -> FieldLocation:
        return self._archive_split(self._split_archivable(identifier),
                                   _as_bytes(data))

    def _archive_split(self, split, data: bytes) -> FieldLocation:
        """Archive one pre-split (dataset, collocation, element) triple —
        the shared tail of :meth:`archive`/:meth:`archive_many`, so batch
        paths canonicalise each identifier exactly once.

        The whole store-archive + catalogue-index unit is the retry scope:
        rule 5 (re-archiving transactionally replaces) makes re-driving it
        idempotent even when the first attempt died between the two."""
        dataset, collocation, element = split

        def attempt() -> FieldLocation:
            with self.tracer.span("fdb.archive", nbytes=len(data)):
                loc = self.store.archive(data, dataset, collocation)
                self.catalogue.archive(dataset, collocation, element, loc)
            return loc

        loc = self.retry.call(attempt, op="fdb.archive",
                              metrics=self.tracer.metrics,
                              on_retry=_ON_RETRY.get())
        self._mark_dirty()
        return loc

    def _mark_dirty(self) -> None:
        with self._dirty_lock:
            self._archive_seq += 1
            self._dirty = True

    def archive_placement(self, identifier: Union[Identifier,
                                                  Mapping[str, object]]
                          ) -> PlacementHandle:
        """Resolve where an ``archive(identifier, ...)`` would land —
        placement only, no data I/O: the write-side twin of
        :meth:`retrieve_handle`.  Handles over the same storage unit (posix
        archives appending into one writer's data file) are mutually
        mergeable, so :func:`repro.core.group_mergeable` groups them into
        single :meth:`archive_batch` submissions — the tensorstore
        ``WritePlan``'s planning hook."""
        dataset, collocation, _element = self._split_archivable(identifier)
        return PlacementHandle(self.store.placement(dataset, collocation))

    def archive_batch(self, items: Sequence[Tuple[Mapping[str, object],
                                                  BytesLike]]
                      ) -> List[FieldLocation]:
        """Archive several fully-specified objects through ONE store-level
        batched write + one catalogue batch.

        The store coalesces items bound for the same storage unit into a
        single write (posix: one buffered append per data file); on object
        backends the batch degenerates to the per-item loop, so callers
        wanting op-level overlap there should submit one batch per executor
        slot (what :meth:`archive_many` and the tensorstore ``WritePlan``
        do).  Per-item semantics are rule 2/3-unchanged: on return the FDB
        controls all payloads; visibility still requires ``flush()``."""
        return self._archive_batch_split(
            [(self._split_archivable(ident), _as_bytes(data))
             for ident, data in items])

    def _archive_batch_split(self, split) -> List[FieldLocation]:
        """Batch-archive pre-split ``((dataset, collocation, element),
        bytes)`` pairs — one store submission + one catalogue batch.  The
        whole batch is one retry unit (idempotent per rule 5, like
        :meth:`_archive_split`)."""

        def attempt() -> List[FieldLocation]:
            with self.tracer.span("fdb.archive_batch", items=len(split),
                                  nbytes=sum(len(d) for _s, d in split)):
                locs = self.store.archive_batch(
                    [(data, dataset, collocation)
                     for (dataset, collocation, _e), data in split])
                self.catalogue.archive_batch(
                    [(dataset, collocation, element, loc)
                     for ((dataset, collocation, element), _d), loc
                     in zip(split, locs)])
            return locs

        locs = self.retry.call(attempt, op="fdb.archive_batch",
                               metrics=self.tracer.metrics,
                               on_retry=_ON_RETRY.get())
        if split:
            self._mark_dirty()
        return locs

    @property
    def io_executor(self):
        """This client's bounded I/O executor (``archive_many`` overlap,
        tensorstore chunk I/O), sized by ``config.io_parallelism`` — one
        per FDB instead of one per call, rebuilt if the configured depth
        changes, shut down in :meth:`close`.  A closed client refuses to
        mint a fresh pool (nothing would ever shut it down again)."""
        # lint: disable=L001 -- documented cycle-breaker: lazy import so
        # core never loads tensorstore at module import time
        from repro.tensorstore.executor import ChunkExecutor
        size = max(1, self.config.io_parallelism)
        with self._io_lock:
            # checked under the lock: close() flips _closed under the same
            # lock, so a concurrent close cannot slip between the check and
            # the build and leave a fresh pool nothing will shut down
            if self._closed:
                raise RuntimeError(
                    "FDB client is closed; its I/O executor cannot be "
                    "rebuilt")
            ex = self._io_executor
            if ex is None or self._io_executor_size != size:
                if ex is not None:
                    # lint: disable=L003 -- resize path: the drained pool
                    # must be gone before a caller can see the new one
                    ex.shutdown(wait=True)
                ex = self._io_executor = ChunkExecutor(max_workers=size)
                self._io_executor_size = size
            return ex

    @property
    def chunk_cache(self):
        """This client's shared decoded-chunk LRU cache, or ``None`` when
        ``config.chunk_cache_bytes`` is 0 (the default).  Read plans
        consult it before resolving handles; write plans invalidate the
        chunks they archive; :meth:`flush`'s clean path publishes them
        and :meth:`wipe` drops the wiped dataset's entries."""
        if self.config.chunk_cache_bytes <= 0:
            return None
        cache = self._chunk_cache
        if cache is None:
            # lint: disable=L001 -- documented cycle-breaker: lazy import so
            # core never loads tensorstore at module import time
            from repro.tensorstore.cache import ChunkCache
            with self._io_lock:
                cache = self._chunk_cache
                if cache is None:
                    cache = self._chunk_cache = ChunkCache(
                        self.config.chunk_cache_bytes,
                        self.config.chunk_cache_entries,
                        metrics=self.tracer.metrics)
        return cache

    def archive_many(self, items: Sequence[Tuple[Mapping[str, object],
                                                 BytesLike]],
                     parallelism: Optional[int] = None,
                     executor=None) -> List[FieldLocation]:
        """The thesis's efficient multi-object archive() variant.

        Batched semantics: every item is archived as an independent object
        (identifier → one store object + one catalogue entry), with two
        levers applied per the paper's findings: items whose payloads land
        in the same storage unit (posix data files, via
        :meth:`archive_placement` + :func:`group_mergeable`) coalesce into
        one batched store write, and the resulting batches are submitted
        through a bounded-depth I/O executor so independent object writes
        *overlap* instead of running as a serial per-item loop.  Returns the
        :class:`FieldLocation` of every item in input order.  Per-item API
        semantics are unchanged: on return the FDB controls (a copy of) all
        data (rule 2); visibility still requires ``flush()`` (rule 3).
        ``parallelism`` (defaulting to ``config.io_parallelism``) sets the
        overlap depth; values <= 1 fall back to the serial loop.  An
        explicit ``executor`` overrides both.
        """
        items = list(items)
        if parallelism is None:
            parallelism = self.config.io_parallelism
        if executor is None and (parallelism <= 1 or len(items) <= 1):
            return [self.archive(ident, data) for ident, data in items]
        if executor is None:
            if parallelism == self.config.io_parallelism:
                executor = self.io_executor
            else:
                # explicit non-default depth: use the shared process-global
                # pool of that size (not owned by this client, never shut
                # down here)
                # lint: disable=L001 -- documented cycle-breaker: lazy
                # import keeps core free of tensorstore at module load
                from repro.tensorstore.executor import sized_executor
                executor = sized_executor(parallelism)
        # canonicalise + split each identifier exactly once; both the
        # placement pre-pass and the archive submissions reuse the triples
        split = [(self._split_archivable(ident), _as_bytes(data))
                 for ident, data in items]
        placements = [
            PlacementHandle(self.store.placement(dataset, collocation))
            for (dataset, collocation, _e), _d in split]
        groups = group_mergeable(placements)
        if len(groups) == len(items):       # nothing coalesces (object
            return executor.map_ordered(    # backends): one op per item
                lambda pair: self._archive_split(*pair), split)
        locs: List[Optional[FieldLocation]] = [None] * len(items)
        batches = executor.map_ordered(
            lambda group: self._archive_batch_split(
                [split[pos] for pos in group]),
            groups)
        for group, batch_locs in zip(groups, batches):
            for pos, loc in zip(group, batch_locs):
                locs[pos] = loc
        return locs                          # type: ignore[return-value]

    @property
    def dirty(self) -> bool:
        """True while this client has archived data not yet flush()ed —
        i.e. a flush() barrier would actually publish something (rule 3)."""
        return self._dirty

    def flush(self) -> None:
        # serialised: two sessions' commit barriers must not interleave
        # inside the backends (the posix catalogue appends partial-index
        # records at offsets it just measured)
        with self.tracer.span("fdb.flush", backend=self.config.backend,
                              dirty=self._dirty,
                              client=self.client_id), self._flush_lock:
            # capture markers FIRST: an archive completing before a marker
            # is included in the flush below; one completing after bumps
            # its sequence, so the conditional clear leaves it dirty —
            # never clean-but-unpublished (the RMW pre-flush depends on it)
            sessions = list(self._sessions)
            marks = [(s, s._dirty_mark()) for s in sessions]
            with self._dirty_lock:
                client_mark = self._archive_seq

            def barrier() -> None:
                # retried as one unit: a re-driven store flush is a no-op
                # for already-persistent data, so a transient catalogue
                # failure cannot leave the pair half-committed
                # lint: disable=L003 -- flush IS the serialised barrier: the
                # held _flush_lock is what gives rule-3 its atomicity
                self.store.flush()
                self.catalogue.flush()  # lint: disable=L003 -- same barrier

            self.retry.call(barrier, op="fdb.flush",
                            metrics=self.tracer.metrics)
            clean = False
            with self._dirty_lock:
                if self._archive_seq == client_mark:
                    self._dirty = False
                    clean = True
            if clean:
                # barrier covered every archive this client journaled; an
                # archive racing the barrier keeps the journal (and dirty
                # flag) until the next flush — never clean-but-unpublished
                self.catalogue.lease_table().clear_dirty_client(
                    self.client_id)
                if self._chunk_cache is not None:
                    # overwritten chunks are visible now: let readers
                    # cache their fresh bytes again
                    self._chunk_cache.publish_pending()
            # one store/catalogue flush publishes everything this *client*
            # archived, whichever session produced it — so every session's
            # barrier up to its captured marker is satisfied too
            for session, mark in marks:
                session._clear_dirty_if(mark)

    # -- writer sessions + chunk-range leases -------------------------------
    def session(self, writer_id: str, lease_ttl: Optional[float] = None,
                heartbeat_interval: Optional[float] = None,
                lease_block: bool = False,
                lease_timeout: Optional[float] = None
                ) -> "WriterSession":
        """Open a :class:`WriterSession` — one logical writer identity on
        this client, with its own dirty/flush-barrier bookkeeping and a
        ledger of the chunk-range leases it holds.  Several sessions may
        share one client (the I/O-server pattern: many producer tasks, one
        FDB connection); their writes into one array are made safe by the
        catalogue-level lease table, not by schema separation.

        ``lease_ttl`` makes every lease the session acquires expire unless
        renewed (crash safety: a dead writer's ranges free themselves);
        ``heartbeat_interval`` starts a daemon thread renewing them every
        that-many seconds (requires ``lease_ttl``; pick interval well under
        the TTL — a third is conventional).

        ``lease_block=True`` makes every lease the session acquires *queue*
        on conflicting ranges (bounded by ``lease_timeout`` seconds) instead
        of failing fast — the workflow-stage posture, where transient
        overlap between concurrent writers is waited out, not errored."""
        if self._closed:
            raise RuntimeError("FDB client is closed; cannot open a session")
        session = WriterSession(self, str(writer_id), lease_ttl=lease_ttl,
                                heartbeat_interval=heartbeat_interval,
                                lease_block=lease_block,
                                lease_timeout=lease_timeout)
        self._sessions.add(session)
        return session

    def _lease_split(self, identifier: Union[Identifier,
                                             Mapping[str, object]]
                     ) -> Tuple[Identifier, Identifier]:
        """Split a lease identifier into (dataset, collocation) keys.  The
        identifier must cover the dataset + collocation dims; element dims
        are irrelevant (leases are per chunk-id *range*, not per key) and
        are ignored if present."""
        ident = as_identifier(identifier)
        need = self.schema.dataset_dims + self.schema.collocation_dims
        missing = [d for d in need if d not in ident]
        if missing:
            raise KeyError(f"lease identifier {ident!r} missing dims "
                           f"{missing} of schema {self.schema.name!r}")
        return (ident.subset(self.schema.dataset_dims),
                ident.subset(self.schema.collocation_dims))

    def lease_scope(self, identifier: Union[Identifier,
                                            Mapping[str, object]]) -> str:
        """Canonical label of the identifier's (dataset, collocation) lease
        key — the ``scope`` attr every ``lease.*`` span carries, so the
        protocol checker (``repro.analysis.protocol``) can correlate lease
        events with the archives they cover."""
        dataset, collocation = self._lease_split(identifier)
        return self._lease_scope_split(dataset, collocation)

    @staticmethod
    def _lease_scope_split(dataset: Identifier,
                           collocation: Identifier) -> str:
        return f"{dataset.canonical()}|{collocation.canonical()}"

    def acquire_lease(self, identifier: Union[Identifier,
                                              Mapping[str, object]],
                      resource: str, lo: int, hi: int, owner: str,
                      ttl: Optional[float] = None, block: bool = False,
                      timeout: Optional[float] = None) -> int:
        """Acquire an exclusive epoch-fenced lease on chunk-id range
        ``[lo, hi)`` of ``resource`` under the identifier's (dataset,
        collocation) key; returns the epoch.  Raises ``LeaseConflictError``
        on overlap with another owner.  ``ttl`` bounds the lease's life
        between :meth:`renew_lease` heartbeats (expiry = release, on the
        deployment's shared lease clock); ``block=True`` queues on a
        conflicting range until it frees — or its holder's TTL lapses —
        giving up with ``LeaseConflictError`` after ``timeout`` seconds.
        Usually reached through :meth:`WriterSession.acquire_lease`, which
        also ledgers the lease for release at session close."""
        dataset, collocation = self._lease_split(identifier)
        m = self.tracer.metrics
        attrs = {} if ttl is None else {"ttl": ttl}
        with self.tracer.span(
                "lease.acquire", resource=resource, lo=lo, hi=hi,
                owner=owner,
                scope=self._lease_scope_split(dataset, collocation),
                **attrs) as sp:
            # blocking acquires meter their queueing delay: the lease-wait
            # histogram is the workflow-level contention signal (how long
            # did assimilation writers wait on each other's windows)
            t0 = time.perf_counter() if block else 0.0
            try:
                epoch = self.catalogue.acquire_lease(dataset, collocation,
                                                     resource, lo, hi, owner,
                                                     ttl=ttl, block=block,
                                                     timeout=timeout)
            except LeaseConflictError:
                if block:
                    m.histogram("lease.wait_us").observe(
                        (time.perf_counter() - t0) * 1e6)
                m.counter("lease.conflicts").inc()
                raise
            if block:
                wait_us = (time.perf_counter() - t0) * 1e6
                m.histogram("lease.wait_us").observe(wait_us)
                if sp is not None:
                    sp.attrs["wait_us"] = round(wait_us, 1)
            if sp is not None:
                sp.attrs["epoch"] = epoch
        m.counter("lease.acquired").inc()
        return epoch

    def renew_lease(self, identifier: Union[Identifier,
                                            Mapping[str, object]],
                    resource: str, owner: str,
                    ttl: Optional[float] = None) -> int:
        """Heartbeat: re-arm the TTL of every lease ``owner`` holds on
        ``resource`` under the identifier's (dataset, collocation) key,
        preserving epochs (a renewal is *not* a re-acquire — fenced archives
        stay valid across it).  Returns the number of leases renewed; 0
        means the owner holds nothing there any more (expired and possibly
        re-leased — the writer must re-acquire and re-fence)."""
        dataset, collocation = self._lease_split(identifier)
        return self._renew_split(dataset, collocation, str(resource), owner,
                                 ttl)

    def _renew_split(self, dataset: Identifier, collocation: Identifier,
                     resource: str, owner: str,
                     ttl: Optional[float]) -> int:
        with self.tracer.span(
                "lease.renew", resource=resource, owner=owner, ttl=ttl,
                scope=self._lease_scope_split(dataset, collocation)) as sp:
            n = self.catalogue.lease_table().renew(
                self.catalogue.lease_key(dataset, collocation, resource),
                owner, ttl)
            if sp is not None:
                sp.attrs["renewed"] = n
        return n

    def mark_dirty_chunks(self, identifier: Union[Identifier,
                                                  Mapping[str, object]],
                          resource: str, owner: str,
                          chunk_ids: Sequence[int]) -> None:
        """Journal a leased writer's archived-but-unflushed chunk ids in
        the deployment-shared dirty-intent journal (on the lease table, so
        *other* clients can see them).  ``flush()`` clears this client's
        intents once the barrier publishes; intents left behind by a writer
        whose leases lapsed are what :meth:`recover` quarantines."""
        dataset, collocation = self._lease_split(identifier)
        self.catalogue.lease_table().mark_dirty(
            self.catalogue.lease_key(dataset, collocation, str(resource)),
            owner, chunk_ids, self.client_id)

    def release_lease(self, identifier: Union[Identifier,
                                              Mapping[str, object]],
                      resource: str, lo: int, hi: int, owner: str) -> None:
        """Release ``owner``'s leases overlapping ``[lo, hi)``.  Any client
        may break any owner's lease (the coordinator escape hatch for a
        presumed-dead writer) — epoch fencing rejects the broken holder's
        late archives, so breaking is safe, merely rude."""
        dataset, collocation = self._lease_split(identifier)
        self._release_lease_split(dataset, collocation, resource, lo, hi,
                                  owner, exact=False)

    def _release_lease_split(self, dataset: Identifier,
                             collocation: Identifier, resource: str,
                             lo: int, hi: int, owner: str,
                             exact: bool) -> None:
        """The one release path (facade + sessions): every lease release
        emits a ``lease.release`` span, the event the protocol checker
        orders against flush barriers."""
        with self.tracer.span(
                "lease.release", resource=str(resource), lo=lo, hi=hi,
                owner=owner, exact=exact,
                scope=self._lease_scope_split(dataset, collocation)):
            self.catalogue.release_lease(dataset, collocation,
                                         str(resource), lo, hi, owner,
                                         exact=exact)

    def lease_holders(self, identifier: Union[Identifier,
                                              Mapping[str, object]],
                      resource: str) -> List[Lease]:
        """All active leases on ``resource`` under the identifier's
        (dataset, collocation) key — observability for coordinators."""
        dataset, collocation = self._lease_split(identifier)
        return self.catalogue.lease_holders(dataset, collocation, resource)

    def check_lease(self, identifier: Union[Identifier,
                                            Mapping[str, object]],
                    resource: str, lo: int, hi: int, owner: str,
                    epoch: int) -> None:
        """Fencing gate: raise ``StaleLeaseError`` unless ``owner`` still
        holds a covering lease at exactly ``epoch``."""
        dataset, collocation = self._lease_split(identifier)
        with self.tracer.span(
                "lease.check", resource=resource, lo=lo, hi=hi, owner=owner,
                epoch=epoch,
                scope=self._lease_scope_split(dataset, collocation)):
            try:
                self.catalogue.check_lease(dataset, collocation, resource,
                                           lo, hi, owner, epoch)
            except StaleLeaseError:
                self.tracer.metrics.counter("lease.stale").inc()
                raise

    # -- crash recovery ------------------------------------------------------
    def recover(self, identifier: Union[Identifier, Mapping[str, object]],
                live_resource: Optional[str] = None) -> "RecoveryReport":
        """Scan the identifier's (dataset, collocation) lease scope for the
        wreckage of dead writers and mop it up:

        * **expired leases** are purged (epoch fencing already fences their
          holders' late archives; purging just frees the ranges);
        * **orphaned dirty intents** — chunk ids a writer journaled as
          archived-but-unflushed and then stopped heartbeating for — are
          *quarantined*: their archives lived only in the dead client's
          unflushed state (rule 3), so there is nothing to repair; the
          report tells the coordinator which chunks must be re-driven.
          Intents whose owner still holds a live lease are left alone (a
          slow writer mid-commit is not a crash);
        * with ``live_resource`` (the array's live layout generation, e.g.
          ``"g1"``), catalogue entries from *newer* generations — the
          debris of a half-flipped reshard that died between archiving
          ``g2`` chunks and replacing the array metadata — are reported as
          stale so the coordinator can re-run or ignore the reshard.

        Safe to run any time, from any client: it never touches live
        leases, and recovery of a healthy scope returns a clean report.
        Every sweep is emitted as a ``fdb.recover`` span whose ``expired``
        / ``orphans`` attrs let the protocol checker verify the recovery
        invariants (no purge under a live heartbeat)."""
        dataset, collocation = self._lease_split(identifier)
        prefix = (dataset.canonical(), collocation.canonical())
        tbl = self.catalogue.lease_table()
        m = self.tracer.metrics
        with self.tracer.span(
                "fdb.recover", client=self.client_id,
                scope=self._lease_scope_split(dataset, collocation)) as sp:
            expired = [
                {"resource": key[2], "owner": lease.owner, "lo": lease.lo,
                 "hi": lease.hi, "epoch": lease.epoch}
                for key, lease in tbl.purge_expired(prefix)]
            orphans = [
                {"resource": key[2], "owner": owner,
                 "chunk_ids": list(chunk_ids), "client": client}
                for key, owner, chunk_ids, client in tbl.take_orphans(prefix)]
            n_orphans = sum(len(o["chunk_ids"]) for o in orphans)
            if n_orphans:
                m.counter("recover.orphans").inc(n_orphans)
            stale: List[str] = []
            if live_resource is not None:
                mt = _GEN_RE.match(f"{live_resource}.")
                live_gen = int(mt.group(1)) if mt else 0
                for ident, _loc in self.catalogue.list(dataset,
                                                       dict(collocation)):
                    for value in ident.values():
                        g = _GEN_RE.match(value)
                        if g and int(g.group(1)) > live_gen:
                            stale.append(value)
            if sp is not None:
                sp.attrs["expired"] = expired
                sp.attrs["orphans"] = orphans
                sp.attrs["stale"] = len(stale)
        return RecoveryReport(self._lease_scope_split(dataset, collocation),
                              expired, orphans, sorted(set(stale)))

    def retrieve(self, identifiers: Union[Identifier, Mapping[str, object],
                                          Sequence]) -> MultiHandle:
        if isinstance(identifiers, (Identifier, Mapping)):
            identifiers = [identifiers]
        handles: List[DataHandle] = []
        for ident in identifiers:
            for e in self._expand(as_identifier(ident)):
                h = self.retrieve_handle(e)
                if h is not None:     # absence is not an error (§2.7.1)
                    handles.append(h)
        return MultiHandle(handles)

    def retrieve_handle(self, identifier: Union[Identifier,
                                                Mapping[str, object]]
                        ) -> Optional[DataHandle]:
        """Resolve one fully-specified identifier to its backend
        :class:`DataHandle` — catalogue lookup only, no data I/O.

        Unlike :meth:`retrieve` this keeps the identifier ↔ handle pairing:
        ``None`` means the object does not exist, and the returned handles
        can be regrouped by the caller (``repro.core.handle.group_mergeable``)
        into coalesced reads before any byte moves — the tensorstore read
        path's planning hook.  Multi-value expressions are not expanded here.
        """
        ident = as_identifier(identifier)
        dataset, collocation, element = self.schema.split(ident)

        def attempt() -> Optional[DataHandle]:
            loc = self.catalogue.retrieve(dataset, collocation, element)
            return None if loc is None else self.store.retrieve(loc)

        return self.retry.call(attempt, op="fdb.retrieve",
                               metrics=self.tracer.metrics)

    def _expand(self, ident: Identifier) -> List[Identifier]:
        """Expand multi-value expressions (lists) via axes (§2.7.1 axis())."""
        multi = {k: v for k, v in dict(ident).items() if "/" in v}
        if not multi:
            return [ident]
        out = [dict(ident)]
        for dim, expr in multi.items():
            values = expr.split("/")
            out = [dict(d, **{dim: v}) for d in out for v in values]
        return [Identifier(d) for d in out]

    def list(self, partial: Mapping[str, object]
             ) -> Iterator[Tuple[Identifier, FieldLocation]]:
        partial = dict(partial)
        dataset_part = {k: v for k, v in partial.items()
                        if k in self.schema.dataset_dims}
        for dataset in self._matching_datasets(dataset_part):
            yield from self.catalogue.list(dataset, partial)

    def _matching_datasets(self, dataset_part: Mapping[str, object]
                           ) -> List[Identifier]:
        if set(dataset_part) == set(self.schema.dataset_dims):
            return [Identifier(dataset_part)]
        return [d for d in self.catalogue.datasets()
                if d.matches(dataset_part)]

    def axes(self, identifier: Mapping[str, object], dim: str) -> frozenset:
        ident = as_identifier(identifier)
        dataset = ident.subset(self.schema.dataset_dims)
        collocation = ident.subset(self.schema.collocation_dims)
        return self.catalogue.axes(dataset, collocation, dim)

    def wipe(self, dataset_part: Mapping[str, object]) -> None:
        """Destroy every matching dataset — data and index together (the
        container-destroy granularity of the thesis's schema mapping).

        This is the FDB's *only* deletion primitive: there is no per-object
        delete, so wiping is also how superseded tensorstore layout
        generations (resharded arrays' old-grid chunks, retained versioned)
        are eventually reclaimed — at the cost of the whole array dataset.
        """
        for dataset in self._matching_datasets(dict(dataset_part)):
            self.store.wipe(dataset)
            self.catalogue.wipe(dataset)
        if self._chunk_cache is not None:
            self._chunk_cache.clear({str(k): str(v)
                                     for k, v in dataset_part.items()})

    # -- observability -------------------------------------------------------
    def trace(self, since: int = 0) -> List[Span]:
        """Finished spans from this client's tracer (oldest first).  Pass a
        ``tracer.mark()`` value as ``since`` for a window.  Empty unless
        tracing is enabled (``fdb.tracer.enable()`` or ``--trace``)."""
        return self.tracer.spans(since)

    def metrics(self) -> Dict[str, Dict[str, object]]:
        """Snapshot of this client's metrics registry: lease counters,
        executor queue/in-flight, codec byte counts, per-backend op latency
        histograms.  Counters (e.g. ``lease.conflicts``) update even while
        span tracing is disabled."""
        return self.tracer.metrics.snapshot()

    def check_protocol(self, since: int = 0):
        """Replay this client's trace window through the concurrency
        protocol checker (``repro.analysis.protocol.check_protocol``) and
        return its list of violations — empty on a healthy run.  Requires
        tracing to have been enabled for the window; spans record the
        lease/flush/archive events the checker orders."""
        # upward import by design: analysis sits above core in the layer
        # DAG, and this convenience hook must not make core depend on it
        # at module load
        from repro.analysis.protocol import check_protocol  # lint: disable=L001 -- lazy convenience hook; core must not import analysis at module load
        window = None
        if self._io_executor is not None:
            window = self._io_executor.max_in_flight
        return check_protocol(self.tracer.spans(since),
                              self.tracer.metrics.snapshot(),
                              max_in_flight=window)

    def abandon(self) -> None:
        """Simulate whole-client death (test/chaos hook), the client-level
        analogue of :meth:`WriterSession.abandon`: every open session is
        abandoned (leases left to lapse by TTL, dirty intents left for
        :meth:`recover`), nothing is flushed — a crashed process never
        reaches its commit barrier — and only *local* resources (the I/O
        pool) are torn down."""
        for session in list(self._sessions):
            if not session._closed:
                session.abandon()
        with self._io_lock:
            if self._io_executor is not None:
                # lint: disable=L003 -- teardown: _closed must flip
                # atomically with the pool draining (see close())
                self._io_executor.shutdown(wait=True)
                self._io_executor = None
                self._io_executor_size = 0
            self._closed = True

    def close(self) -> None:
        if not self._closed:
            self.flush()
            self.catalogue.close()
            self.store.close()
            with self._io_lock:
                # _closed flips under _io_lock so io_executor's guard and
                # this shutdown are atomic with respect to each other
                if self._io_executor is not None:
                    # lint: disable=L003 -- teardown: _closed must flip
                    # atomically with the pool draining (see io_executor)
                    self._io_executor.shutdown(wait=True)
                    self._io_executor = None
                    self._io_executor_size = 0
                self._closed = True

    def __enter__(self) -> "FDB":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@dataclasses.dataclass
class RecoveryReport:
    """What one :meth:`FDB.recover` sweep found (see its docstring).

    ``expired``: purged TTL-lapsed leases, as dicts with ``resource`` /
    ``owner`` / ``lo`` / ``hi`` / ``epoch``.  ``quarantined``: orphaned
    dirty intents — dicts with ``resource`` / ``owner`` / ``chunk_ids`` /
    ``client`` — whose chunks must be re-driven by a live writer.
    ``stale``: catalogue element values from layout generations newer than
    the live one (half-flipped reshard debris), report-only.
    """
    scope: str
    expired: List[Dict[str, object]]
    quarantined: List[Dict[str, object]]
    stale: List[str]

    @property
    def clean(self) -> bool:
        return not (self.expired or self.quarantined or self.stale)

    @property
    def orphan_chunks(self) -> int:
        return sum(len(q["chunk_ids"]) for q in self.quarantined)


class WriterSession:
    """One logical writer identity on an FDB client — the unit multi-writer
    safety is built around.

    A session carries three things a bare client cannot:

    * **identity** — ``writer_id``, the lease *owner* string used by the
      catalogue-level lease table;
    * **a lease ledger** — every chunk-range lease acquired through the
      session is recorded with its epoch, validated by :meth:`check_lease`
      (epoch fencing) before lease-protected archives commit, and released
      at :meth:`close`;
    * **a per-session flush barrier** — :attr:`dirty` tracks whether *this
      session* archived since the last client flush, so visibility
      decisions (rule 3: the RMW pre-flush) are made per session, not per
      client.  That is sound precisely *because* of leases: the chunks a
      leased writer read-modify-writes are covered by its own lease, so no
      other session's unflushed archives can be hiding under them —
      another session's dirty state is irrelevant to this session's reads.
      ``flush()`` remains a client-level barrier (one store flush publishes
      everything), which clears every session's dirty flag at once.

    Sessions are cheap; open one per producer task
    (``fdb.session("rank3")``).  :meth:`close` flushes if the session is
    dirty *before* releasing its leases — releasing a lease over unflushed
    chunks would let the next holder RMW stale bytes and race our late
    flush, the exact silent merge leases exist to prevent.
    """

    def __init__(self, fdb: FDB, writer_id: str,
                 lease_ttl: Optional[float] = None,
                 heartbeat_interval: Optional[float] = None,
                 lease_block: bool = False,
                 lease_timeout: Optional[float] = None):
        self.fdb = fdb
        self.writer_id = writer_id
        self.lease_ttl = lease_ttl
        #: session-level acquire posture: plans and bare acquire_lease()
        #: calls default to these, so a "workflow" session waits out
        #: transient overlap instead of raising LeaseConflictError
        self.lease_block = lease_block
        self.lease_timeout = lease_timeout
        self._dirty = False
        self._seq = 0           # archive sequence, see FDB.flush's markers
        self._closed = False
        self._lock = NamedLock("fdb.session")
        #: (dataset, collocation, resource, lo, hi) -> epoch
        self._held: Dict[Tuple[Identifier, Identifier, str, int, int],
                         int] = {}
        self._hb_stop = threading.Event()
        self._hb_thread: Optional["threading.Thread"] = None
        if heartbeat_interval is not None:
            if lease_ttl is None:
                raise ValueError("heartbeat_interval requires lease_ttl "
                                 "(there is nothing to renew without one)")
            # lint: disable=L005 -- the lease-heartbeat daemon is part of
            # the session lifecycle, stopped/joined in close(); Event.wait
            # paces it so stop is prompt and no bare sleep is involved
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop, args=(heartbeat_interval,),
                name=f"lease-heartbeat-{writer_id}", daemon=True)
            self._hb_thread.start()

    def _heartbeat_loop(self, interval: float) -> None:
        while not self._hb_stop.wait(interval):
            try:
                self.heartbeat()
            except Exception:
                # the daemon must not die on a transient renew hiccup; a
                # genuinely lost lease surfaces at the next fencing gate
                # (check_held / check_lease), with full context
                pass

    def _stop_heartbeat(self) -> None:
        self._hb_stop.set()
        thread, self._hb_thread = self._hb_thread, None
        if thread is not None:
            thread.join(timeout=5)

    def heartbeat(self, ttl: Optional[float] = None) -> int:
        """Renew the TTL on every (dataset, collocation, resource) group
        this session's ledger covers, preserving epochs; returns the number
        of leases renewed.  0 with a non-empty ledger means the TTLs
        already lapsed — the session's next fencing gate will raise."""
        ttl = ttl if ttl is not None else self.lease_ttl
        with self._lock:
            groups = {(d, c, r) for (d, c, r, _lo, _hi) in self._held}
        renewed = 0
        for dataset, collocation, resource in groups:
            renewed += self.fdb._renew_split(dataset, collocation, resource,
                                             self.writer_id, ttl)
        return renewed

    def abandon(self) -> None:
        """Simulate writer death (test/chaos hook): stop heartbeating and
        mark the session closed WITHOUT flushing or releasing anything —
        its leases must lapse by TTL and its journaled dirty intents wait
        for :meth:`FDB.recover`."""
        self._stop_heartbeat()
        self._closed = True

    def _bump_dirty(self) -> None:
        with self._lock:
            self._seq += 1
            self._dirty = True

    def _dirty_mark(self) -> int:
        with self._lock:
            return self._seq

    def _clear_dirty_if(self, mark: int) -> None:
        """Clear dirty unless an archive landed after ``mark`` was captured
        (that archive may not be covered by the flush that just ran)."""
        with self._lock:
            if self._seq == mark:
                self._dirty = False

    def __repr__(self) -> str:
        return (f"WriterSession({self.writer_id!r}, "
                f"leases={len(self._held)}, dirty={self._dirty}"
                + (", closed" if self._closed else "") + ")")

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(
                f"writer session {self.writer_id!r} is closed")

    # -- leases --------------------------------------------------------------
    def _ledger_key(self, identifier, resource: str, lo: int, hi: int):
        dataset, collocation = self.fdb._lease_split(identifier)
        return (dataset, collocation, str(resource), int(lo), int(hi))

    def holds(self, identifier, resource: str, lo: int, hi: int) -> bool:
        """True when this session's ledger records a lease on exactly
        ``[lo, hi)`` (used by plans to tell a fresh acquire from a
        re-acquire they must not release)."""
        key = self._ledger_key(identifier, resource, lo, hi)
        with self._lock:
            return key in self._held

    def acquire_lease(self, identifier, resource: str, lo: int, hi: int,
                      block: Optional[bool] = None,
                      timeout: Optional[float] = None) -> int:
        """Acquire ``[lo, hi)`` for this session's writer id and ledger it;
        returns the epoch.  Raises ``LeaseConflictError`` on overlap with
        another owner; re-acquiring a ledgered range is idempotent (and
        re-arms its TTL).  ``block=True`` queues on a conflicting range
        until it frees or ``timeout`` seconds pass; both default to the
        session's ``lease_block``/``lease_timeout`` posture.  The session's
        ``lease_ttl`` (if any) applies to every lease acquired here."""
        self._check_open()
        if block is None:
            block = self.lease_block
        if timeout is None:
            timeout = self.lease_timeout
        epoch = self.fdb.acquire_lease(identifier, resource, lo, hi,
                                       owner=self.writer_id,
                                       ttl=self.lease_ttl, block=block,
                                       timeout=timeout)
        key = self._ledger_key(identifier, resource, lo, hi)
        with self._lock:
            self._held[key] = epoch
        return epoch

    def release_lease(self, identifier, resource: str, lo: int,
                      hi: int) -> None:
        """Release this session's lease on exactly ``[lo, hi)`` and drop it
        from the ledger.  Holder-side release is *exact-range*: a session
        may hold overlapping leases (two plans over intersecting windows),
        and giving one back must not sweep away its siblings — overlap
        release is the coordinator's tool (:meth:`FDB.release_lease`)."""
        dataset, collocation = self.fdb._lease_split(identifier)
        self.fdb._release_lease_split(dataset, collocation, str(resource),
                                      lo, hi, self.writer_id, exact=True)
        with self._lock:
            self._held.pop((dataset, collocation, str(resource), int(lo),
                            int(hi)), None)

    def check_lease(self, identifier, resource: str, lo: int, hi: int,
                    epoch: int) -> None:
        """Epoch-fencing gate (raises ``StaleLeaseError``) — run before
        archiving into a leased range."""
        self.fdb.check_lease(identifier, resource, lo, hi,
                             owner=self.writer_id, epoch=epoch)

    def check_held(self) -> None:
        """Validate every ledgered lease is still current (epoch fencing);
        raises ``StaleLeaseError`` on the first broken one."""
        with self._lock:
            held = list(self._held.items())
        for (dataset, collocation, resource, lo, hi), epoch in held:
            self.fdb.catalogue.check_lease(dataset, collocation, resource,
                                           lo, hi, self.writer_id, epoch)

    def lease_holders(self, identifier, resource: str) -> List[Lease]:
        return self.fdb.lease_holders(identifier, resource)

    @property
    def held_leases(self) -> List[Tuple[Identifier, Identifier, str, int,
                                        int, int]]:
        """Ledger snapshot: (dataset, collocation, resource, lo, hi,
        epoch) per held lease."""
        with self._lock:
            return [k + (e,) for k, e in sorted(self._held.items(),
                                                key=lambda kv: kv[0][2:])]

    def release_all(self, identifier=None) -> int:
        """Release every ledgered lease, or, given an ``identifier``, every
        one under its (dataset, collocation) key (stale entries release as
        no-ops); returns the number released.  Releasing does not flush:
        callers flush a dirty session first (:meth:`close` does)."""
        scope = None if identifier is None else \
            self.fdb._lease_split(identifier)
        with self._lock:
            held = [k for k in self._held if scope is None or k[:2] == scope]
            for k in held:
                del self._held[k]
        for dataset, collocation, resource, lo, hi in held:
            self.fdb._release_lease_split(dataset, collocation, resource,
                                          lo, hi, self.writer_id,
                                          exact=True)
        return len(held)

    def mark_dirty_chunks(self, identifier, resource: str,
                          chunk_ids: Sequence[int]) -> None:
        """Journal this writer's archived-but-unflushed ``chunk_ids`` in
        the deployment-shared dirty-intent journal (crash-recovery
        breadcrumbs for :meth:`FDB.recover`); cleared by the client's next
        published flush."""
        self.fdb.mark_dirty_chunks(identifier, resource, self.writer_id,
                                   chunk_ids)

    # -- archive / visibility (the FDB surface plans consume) ----------------
    # each archive entry point installs the session's lease re-validation
    # as the facade retry's on_retry hook: a retried archive re-fences
    # before re-archiving (StaleLeaseError beats silent double-archive)
    def archive(self, identifier, data: BytesLike) -> FieldLocation:
        self._check_open()
        token = _ON_RETRY.set(self.check_held)
        try:
            loc = self.fdb.archive(identifier, data)
        finally:
            _ON_RETRY.reset(token)
        self._bump_dirty()
        return loc

    def archive_batch(self, items) -> List[FieldLocation]:
        self._check_open()
        token = _ON_RETRY.set(self.check_held)
        try:
            locs = self.fdb.archive_batch(items)
        finally:
            _ON_RETRY.reset(token)
        if items:
            self._bump_dirty()
        return locs

    def archive_many(self, items, parallelism: Optional[int] = None,
                     executor=None) -> List[FieldLocation]:
        self._check_open()
        items = list(items)
        token = _ON_RETRY.set(self.check_held)
        try:
            locs = self.fdb.archive_many(items, parallelism=parallelism,
                                         executor=executor)
        finally:
            _ON_RETRY.reset(token)
        if items:
            self._bump_dirty()
        return locs

    def archive_placement(self, identifier) -> PlacementHandle:
        return self.fdb.archive_placement(identifier)

    def retrieve(self, identifiers) -> MultiHandle:
        return self.fdb.retrieve(identifiers)

    def retrieve_handle(self, identifier) -> Optional[DataHandle]:
        return self.fdb.retrieve_handle(identifier)

    @property
    def dirty(self) -> bool:
        """True while *this session* has archived data not yet covered by a
        client flush — the per-session rule-3 barrier state."""
        return self._dirty

    def flush(self) -> None:
        """Client-level flush (publishes everything archived on the client;
        clears every session's dirty flag, this one's included)."""
        with self.fdb.tracer.span("session.commit", writer=self.writer_id):
            self.fdb.flush()

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Flush if dirty, then release all held leases.  Order matters:
        a lease released over unflushed chunks would let its next holder
        RMW bytes that are not yet visible and then race this client's
        late flush — the silent merge leases exist to prevent."""
        if self._closed:
            return
        self._stop_heartbeat()
        with self.fdb.tracer.span("session.close", writer=self.writer_id,
                                  leases=len(self._held)):
            if self._dirty:
                self.fdb.flush()
            self.release_all()
        self._closed = True

    def __enter__(self) -> "WriterSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# late imports to avoid cycles
from .backends.daos import DaosCatalogue, DaosStore          # noqa: E402
from .backends.posix import LustreSim, PosixCatalogue, PosixStore  # noqa: E402
from .backends.rados import RadosCatalogue, RadosStore       # noqa: E402
from .backends.s3 import S3Store                             # noqa: E402
