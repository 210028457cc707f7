"""FDB-backed distributed checkpointing — the paper's technique as the
framework's storage substrate (DESIGN.md §2).

Mapping of training state onto the FDB schema (``ckpt`` schema):

  dataset key    = {run, kind, step}       → one container/dir per step:
                                              wiping a step = container destroy
  collocation key= {host}                  → contention-free index per writer
                                              host (the paper's C7 lever)
  element key    = {tensor, shard}         → one FDB object per tensor shard

Semantics used:
  * ``archive()`` each shard (optionally field-codec compressed),
  * ``flush()``  = the checkpoint *commit barrier* (visibility rule 3),
  * restore      = ``list()`` + merged ``retrieve()`` + reassembly,
  * write+read contention (training writes step N while an evaluator reads
    step N-k) is exactly the paper's NWP producer/PGEN pattern and is safe
    under every backend's consistency model.

Async mode archives from a background thread (the paper's I/O-server
pattern: compute and storage I/O overlap); ``wait()`` joins before the next
checkpoint or at exit.  ``save_sharded()`` is the *multi-writer* variant:
one :class:`~repro.core.WriterSession` per simulated rank, each leasing and
writing its own chunk band of every tensor concurrently (chunk-range
leases, ``repro.core.lease``), with a single flush as the step commit
barrier.

Storage path (``chunked=True``, the default): every tensor is a
``repro.tensorstore`` chunked array — the chunk index rides the ``shard``
element dim, and each tensor archives through a coalesced
:class:`~repro.tensorstore.WritePlan`: same-shape chunks encode in one
Pallas codec launch, chunks bound for one storage unit (posix data files)
land as a single batched store write, and independent object writes overlap
through the FDB client's bounded I/O executor.  Restore can read partial
tensors per host (``open_tensor()``) or patch them in place
(``update_tensor()``, chunk-aligned partial writes); ``compress`` selects
the ``field8`` per-chunk codec instead of a post-hoc buffer hack.
``chunked=False`` keeps the legacy one-blob-per-shard layout (its shard
blobs now batch through ``FDB.archive_many``), and restore transparently
falls back to it for checkpoints written by older runs.

Topology changes: a run restarted with a different ``n_shards`` can restore
a checkpoint saved under the old banding as-is (``restore()`` reads whole
tensors from whatever grid they carry), and ``reshard_tensor()`` /
``reshard_step()`` re-band the saved tensors onto the new topology as a
streaming reshard (bounded batches of coalesced reads + writes, old-banding
chunks retained versioned) so sharded partial reads line up again.  A
*re-save* of a step under a new banding bumps the tensor's layout
generation (``create(on_mismatch="retain")``) instead of failing — new-grid
chunks live under fresh generation-prefixed keys, never colliding with the
old grid's.
"""
from __future__ import annotations

import contextvars
import io
import queue
import socket
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import FDB, FDBConfig, Identifier
from repro.core.schema import CHECKPOINT_SCHEMA
from repro.tensorstore import ChunkedArray, TensorStore, auto_chunks
from repro.tensorstore.codec import FieldQuantCodec

_FIELD8 = FieldQuantCodec(8)


def _tensor_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    # NB: "." not "/" — "/" is the FDB multi-value expression separator
    return ".".join(parts)


def _pack(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def _unpack(raw: bytes) -> np.ndarray:
    return np.load(io.BytesIO(raw), allow_pickle=False)


class FDBCheckpointer:
    def __init__(self, run: str, fdb_config: Optional[FDBConfig] = None,
                 n_shards: int = 1, asynchronous: bool = False,
                 compress: bool = False, host: Optional[str] = None,
                 chunked: bool = True, shutdown_timeout: float = 5.0,
                 tracer=None, faults=None, retry=None, meter=None):
        cfg = fdb_config or FDBConfig(backend="daos")
        if cfg.resolved_schema().name != "ckpt":
            import dataclasses
            cfg = dataclasses.replace(cfg, schema=CHECKPOINT_SCHEMA)
        # tracer/faults/retry/meter flow to the client so workflow forecast
        # stages can trace, chaos-test and cost-model sharded checkpoints
        self.fdb = FDB(cfg, meter=meter, tracer=tracer, faults=faults,
                       retry=retry)
        self.run = run
        self.n_shards = n_shards
        self.compress = compress
        self.chunked = chunked
        self.host = host or socket.gethostname()
        self.asynchronous = asynchronous
        self.shutdown_timeout = shutdown_timeout
        self._q: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        self._errors: List[BaseException] = []
        if asynchronous:
            self._worker = threading.Thread(target=self._drain, daemon=True)
            self._worker.start()

    # -- write path -----------------------------------------------------------
    def _dataset(self, kind: str, step: int) -> Dict[str, str]:
        return {"run": self.run, "kind": kind, "step": str(step)}

    def _tensor_store(self, kind: str, step: int, name: str) -> TensorStore:
        base = {**self._dataset(kind, step), "host": self.host,
                "tensor": name}
        return TensorStore(self.fdb, base, chunk_dim="shard")

    def _compressible(self, arr: np.ndarray) -> bool:
        return arr.dtype in (np.float32, np.float16) and arr.ndim >= 2 \
            and arr.size >= 1024

    def _tensor_chunks(self, shape, dtype):
        """n_shards > 1 splits along axis 0 (one chunk row-band per shard);
        otherwise ~1 MiB auto chunks."""
        if self.n_shards > 1 and len(shape) >= 1 and shape[0] > 1:
            first = -(-shape[0] // self.n_shards)
            return (first,) + tuple(shape[1:])
        return auto_chunks(tuple(shape), dtype)

    def _archive_tree(self, kind: str, step: int, tree) -> None:
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in flat:
            arr = np.asarray(leaf)
            if self.chunked:
                codec = "field8" if self.compress and self._compressible(arr) \
                    else "raw"
                ts = self._tensor_store(kind, step, _tensor_name(path))
                # on_mismatch="retain": a layout change across re-saves of
                # this step (e.g. a different n_shards) bumps the layout
                # generation — the new grid's chunks live under fresh
                # generation-prefixed keys and the metadata replace flips
                # readers over; old-grid chunks stay behind as versioned,
                # unreachable garbage, never as wrong reads
                chunked = ts.create(arr.shape, arr.dtype,
                                    chunks=self._tensor_chunks(arr.shape,
                                                               arr.dtype),
                                    codec=codec, on_mismatch="retain")
                # the step-level flush() in _do_save is the commit barrier
                chunked.write(arr, flush=False)
                continue
            # tombstone any chunked metadata from a previous save of this
            # step, so chunked-first restore falls through to these blobs
            # instead of returning stale chunked data
            self.fdb.archive(Identifier({**self._dataset(kind, step),
                                         "host": self.host,
                                         "tensor": _tensor_name(path),
                                         "shard": "meta"}), b"")
            payload = arr
            if self.compress and self._compressible(arr):
                payload = self._compress(arr)
            shards = np.array_split(payload.reshape(-1), self.n_shards) \
                if self.n_shards > 1 else [payload]
            # batched archive: shard blobs coalesce per storage unit (posix)
            # and overlap through the client's bounded executor elsewhere
            self.fdb.archive_many(
                [(Identifier({**self._dataset(kind, step),
                              "host": self.host,
                              "tensor": _tensor_name(path),
                              "shard": str(si)}),
                  _pack(np.asarray(shard)))
                 for si, shard in enumerate(shards)])

    def _compress(self, arr: np.ndarray) -> np.ndarray:
        """Legacy shard-blob quantiser: the blob is a ``field8`` chunk
        container, so it names its own (rows, block) geometry."""
        return np.frombuffer(_FIELD8.encode(arr), np.uint8)

    def _decompress(self, buf: np.ndarray, ref: np.ndarray) -> np.ndarray:
        """Decode a legacy shard blob: a ``field8`` container, or a
        headerless blob of older runs, whose geometry follows from its size
        under the block rule it was written with (blocks down to 1 row).
        A container's size is 1 + 4*size (mod 8) and a headerless blob's
        4*size (mod 8), so the two can never be mistaken for each other."""
        size = ref.size
        data = buf.tobytes()
        if _FIELD8.describes(data, size):
            return _FIELD8.decode(data, (size,), np.dtype(np.float32))
        rows = size // 128
        block = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1)
                     if rows % b == 0)
        n, nb = rows * 128, rows // block
        if buf.size != n + 8 * nb + 4 * (size - n):
            raise ValueError(f"compressed blob of {buf.size} bytes is neither "
                             f"a field8 container nor a headerless blob of "
                             f"a {size}-element tensor")
        q = buf[:n].view(np.int8).reshape(rows, 128)
        s = buf[n:n + 4 * nb].view(np.float32)
        m = buf[n + 4 * nb:n + 8 * nb].view(np.float32)
        tail = buf[n + 8 * nb:].view(np.float32)
        head = np.asarray(_FIELD8._decode_head(q, s, m, block))
        return np.concatenate([head.reshape(-1), tail]).astype(np.float32)

    def save_sharded(self, step: int, params, opt_state=None,
                     extra: Optional[Dict[str, Any]] = None) -> None:
        """Multi-writer checkpoint save: every simulated rank leases and
        writes its own shard band concurrently — the paper's parallel
        I/O-server archive pattern on top of writer sessions.

        Each of the ``n_shards`` ranks gets its own
        :class:`~repro.core.WriterSession`; a rank's row band of every
        tensor aligns exactly with the tensor's chunk banding
        (``_tensor_chunks``), so each rank's :class:`WritePlan` acquires
        the covering chunk-range lease (disjoint across ranks by
        construction — a misconfigured overlap fails fast with
        ``LeaseConflictError`` instead of racing), encodes and archives
        its full-cover chunks with no RMW, and all ranks' chunk I/O flows
        through the one bounded client executor.  Tensors too small to
        band (scalars, single rows) are written whole by rank 0.  One
        client ``flush()`` at the end is the step commit barrier, after
        which every rank's session closes (releasing its leases).

        Runs synchronously (unlike :meth:`save`, there is no async-queue
        variant: the ranks *are* the concurrency).  Requires the chunked
        layout.  Restore is unchanged — the result is byte-identical to a
        sequential :meth:`save` of the same state.

        Failure atomicity: if any rank fails, *nothing is flushed* — the
        step's partial archives stay invisible (rule 3) and every rank's
        leases are released, so a previous good save of the step remains
        the live one.  Retry the save (same chunk keys re-archive
        consistently) or :meth:`wipe_step` before the next barrier on this
        client publishes the leftovers.
        """
        if not self.chunked:
            raise ValueError("save_sharded requires the chunked layout "
                             "(chunked=True)")
        n_ranks = max(1, self.n_shards)
        with self.fdb.tracer.span("ckpt.save_sharded", step=step,
                                  ranks=n_ranks):
            self._save_sharded(step, n_ranks, params, opt_state, extra)

    def _save_sharded(self, step: int, n_ranks: int, params, opt_state,
                      extra) -> None:
        trees = [("params", jax.tree.map(np.asarray, params))]
        if opt_state is not None:
            trees.append(("opt", jax.tree.map(np.asarray, opt_state)))
        #: per-rank (kind, name, meta, selection, values) write jobs
        jobs: List[List[Tuple[str, str, Any, tuple, np.ndarray]]] = \
            [[] for _ in range(n_ranks)]
        for kind, tree in trees:
            flat = jax.tree_util.tree_flatten_with_path(tree)[0]
            for path, leaf in flat:
                arr = np.asarray(leaf)
                name = _tensor_name(path)
                codec = "field8" if self.compress and \
                    self._compressible(arr) else "raw"
                chunks = self._tensor_chunks(arr.shape, arr.dtype)
                created = self._tensor_store(kind, step, name).create(
                    arr.shape, arr.dtype, chunks=chunks, codec=codec,
                    on_mismatch="retain")
                banded = (n_ranks > 1 and arr.ndim >= 1 and arr.shape[0] > 1)
                if banded:
                    band = chunks[0]
                    tail = (slice(None),) * (arr.ndim - 1)
                    for r in range(n_ranks):
                        lo, hi = r * band, min((r + 1) * band, arr.shape[0])
                        if lo < hi:
                            jobs[r].append((kind, name, created.meta,
                                            (slice(lo, hi),) + tail,
                                            arr[lo:hi]))
                else:
                    jobs[0].append((kind, name, created.meta,
                                    (slice(None),) * arr.ndim, arr))
        sessions = [self.fdb.session(f"rank{r}") for r in range(n_ranks)]
        errors: List[BaseException] = []

        def run_rank(r: int) -> None:
            try:
                for kind, name, meta, sel, values in jobs[r]:
                    ts = TensorStore(
                        None, {**self._dataset(kind, step),
                               "host": self.host, "tensor": name},
                        chunk_dim="shard", session=sessions[r])
                    # bind the created metadata directly: it is not
                    # flushed yet, so an open() could not see it (rule 3)
                    ChunkedArray(ts, meta).write_plan(
                        sel, values).execute(flush=False)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        # run each rank in a copy of this context so the obs span context
        # (and meter client tags) survive the thread hop, exactly like
        # ChunkExecutor.submit does for pool workers
        threads = [threading.Thread(
                       target=contextvars.copy_context().run,
                       args=(run_rank, r), name=f"ckpt-rank{r}")
                   for r in range(n_ranks) if jobs[r]]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            # abandon WITHOUT flushing: a close() here would flush the
            # dirty sessions and publish a partial checkpoint — versioning
            # out any previous good save of this step.  The partial
            # archives stay invisible (rule 3); retrying the save rewrites
            # the same chunk keys consistently, or wipe_step() discards.
            for s in sessions:
                s.release_all()
            raise errors[0]
        if extra:
            for k, v in extra.items():
                ident = Identifier({**self._dataset("meta", step),
                                    "host": self.host, "tensor": k,
                                    "shard": "0"})
                self.fdb.archive(ident, _pack(np.asarray(v)))
        # the step commit barrier: one flush publishes every rank's chunks
        # (and clears every session's dirty flag); closing then releases
        # each rank's leases without a second flush
        self.fdb.flush()
        for s in sessions:
            s.close()

    def save(self, step: int, params, opt_state=None,
             extra: Optional[Dict[str, Any]] = None) -> None:
        """Archive a full training state; commit via flush() barrier."""
        job = ("save", step, jax.tree.map(np.asarray, params),
               jax.tree.map(np.asarray, opt_state) if opt_state is not None
               else None, extra)
        if self.asynchronous:
            self._q.put(job)
        else:
            self._do_save(*job[1:])

    def _do_save(self, step, params, opt_state, extra) -> None:
        with self.fdb.tracer.span("ckpt.save", step=step):
            self._do_save_traced(step, params, opt_state, extra)

    def _do_save_traced(self, step, params, opt_state, extra) -> None:
        self._archive_tree("params", step, params)
        if opt_state is not None:
            self._archive_tree("opt", step, opt_state)
        if extra:
            for k, v in extra.items():
                ident = Identifier({**self._dataset("meta", step),
                                    "host": self.host, "tensor": k,
                                    "shard": "0"})
                self.fdb.archive(ident, _pack(np.asarray(v)))
        # the commit barrier: data+index persistent and visible after this
        self.fdb.flush()

    def _drain(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            try:
                self._do_save(*job[1:])
            except BaseException as e:  # noqa: BLE001
                self._errors.append(e)
            finally:
                self._q.task_done()

    def wait(self) -> None:
        if self.asynchronous:
            self._q.join()
        if self._errors:
            raise self._errors[0]

    # -- read path -------------------------------------------------------------
    def available_steps(self, kind: str = "params") -> List[int]:
        steps = set()
        for ident, _loc in self.fdb.list({"run": self.run, "kind": kind}):
            steps.add(int(ident["step"]))
        return sorted(steps)

    def open_tensor(self, step: int, name: str, kind: str = "params"
                    ) -> ChunkedArray:
        """Open one tensor of a chunked checkpoint for partial reads — e.g.
        ``ck.open_tensor(step, "layer0.w")[1000:2000]`` retrieves only the
        intersecting chunks archived by this host."""
        return self._tensor_store(kind, step, name).open()

    def update_tensor(self, step: int, name: str, selection, values,
                      kind: str = "params") -> ChunkedArray:
        """Chunk-aligned in-place update of one saved tensor.

        The in-place assimilation pattern applied to training state: patch a
        slice of a saved parameter — or, with ``kind="opt"``, optimizer-state
        — tensor: ``ck.update_tensor(step, "mu.l0.w", slice(0, 4096), rows,
        kind="opt")``.  Only the chunks the selection touches are
        re-archived (partially covered chunks read-modify-write).  The
        update is committed (flushed) before returning, so a restore on any
        host sees it.  Requires a chunked checkpoint (the default layout);
        ``kind`` defaults to ``"params"`` like :meth:`open_tensor`.
        """
        arr = self.open_tensor(step, name, kind)
        arr.write_at(selection, values, flush=True)
        return arr

    def reshard_tensor(self, step: int, name: str, kind: str = "params",
                       chunks=None) -> ChunkedArray:
        """Re-chunk one saved tensor onto this checkpointer's topology —
        the restore-side half of a topology change: a run restarted with a
        different ``n_shards`` (or host count) reshards the tensors it owns
        onto its own shard banding before sharded partial reads
        (:meth:`open_tensor` row-band slices) line up again.

        Streams through :meth:`repro.tensorstore.ChunkedArray.reshard` —
        bounded batches of coalesced reads + writes, never the whole tensor
        client-side; the old banding's chunks are retained versioned under
        the previous layout generation.  ``chunks`` overrides the target
        grid (default: this checkpointer's ``_tensor_chunks`` banding).
        Requires a chunked checkpoint (the default layout).
        """
        arr = self.open_tensor(step, name, kind)
        if chunks is None:
            chunks = self._tensor_chunks(arr.shape, arr.dtype)
        return arr.reshard(chunks, flush=True)

    def reshard_step(self, step: int, template, kind: str = "params"
                     ) -> None:
        """Reshard every tensor of a saved step onto this checkpointer's
        topology (see :meth:`reshard_tensor`): restore onto a different
        chunking than the checkpoint was saved with, without a full
        client-side rewrite.  ``template`` names the tensors (any pytree
        shaped like the saved state)."""
        flat = jax.tree_util.tree_flatten_with_path(template)[0]
        for path, _leaf in flat:
            self.reshard_tensor(step, _tensor_name(path), kind)

    def _restore_tensor(self, step: int, kind: str, name: str,
                        ref: np.ndarray) -> np.ndarray:
        """Chunked-first restore; falls back to the legacy per-shard blobs
        so old checkpoints stay readable."""
        try:
            arr = self._tensor_store(kind, step, name).open()
        except FileNotFoundError:
            arr = None
        if arr is not None:
            # strict read: a saved tensor is dense, so a missing chunk is
            # lost data (unflushed writer, partial wipe) — raise rather
            # than resume training from silently zero-filled state
            return arr.read(fill_missing=False)
        shards = []
        for si in range(self.n_shards):
            handle = self.fdb.retrieve({**self._dataset(kind, step),
                                        "host": self.host,
                                        "tensor": name,
                                        "shard": str(si)})
            if handle.length() == 0:
                raise FileNotFoundError(
                    f"checkpoint step {step} missing {name}#{si}")
            shards.append(_unpack(handle.read()))
        arr = np.concatenate(shards) if len(shards) > 1 else shards[0]
        if arr.dtype == np.uint8 and ref.dtype != np.uint8:
            arr = self._decompress(arr, ref)
        return arr

    def restore(self, step: int, template, kind: str = "params"):
        """Rebuild a pytree like ``template`` from archived tensors."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = []
        with self.fdb.tracer.span("ckpt.restore", step=step, kind=kind,
                                  tensors=len(flat)):
            for path, leaf in flat:
                ref = np.asarray(leaf)
                arr = self._restore_tensor(step, kind, _tensor_name(path),
                                           ref)
                arr = arr.reshape(ref.shape) if arr.size == ref.size else arr
                leaves.append(arr.astype(ref.dtype))
        return treedef.unflatten(
            [jax.numpy.asarray(a) for a in leaves])

    def restore_latest(self, template, kind: str = "params"
                       ) -> Tuple[Optional[int], Any]:
        steps = self.available_steps(kind)
        if not steps:
            return None, template
        step = steps[-1]
        return step, self.restore(step, template, kind)

    def wipe_step(self, step: int) -> None:
        for kind in ("params", "opt", "meta"):
            self.fdb.wipe(self._dataset(kind, step))

    def close(self) -> None:
        if self.asynchronous:
            self.wait()
            self._q.put(None)
            if self._worker:
                self._worker.join(timeout=self.shutdown_timeout)
                if self._worker.is_alive():
                    # a silently-dropped join here would let close() return
                    # with a save possibly still archiving — the caller
                    # would tear down (or exit) under a half-written,
                    # unflushed step believing it durable
                    raise RuntimeError(
                        f"checkpoint async worker failed to shut down "
                        f"within {self.shutdown_timeout}s "
                        f"({max(0, self._q.unfinished_tasks - 1)} save "
                        f"job(s) still "
                        f"pending); a save may still be in flight — "
                        f"the step is NOT durable until flush")
        self.fdb.close()
