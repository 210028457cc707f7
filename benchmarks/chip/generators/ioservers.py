"""An output step written by several I/O servers, each behind its own
barrier: the paper's operational archive pattern.

The configuration's ``writers`` I/O servers are ``ChunkedFieldStore``
clients of one deployment, each with its own FDB client, executor and
flush, sharing the store name, the collocation key and so the lease table
(threads of one process).  The ``(variable, level)`` fields of a step are
dealt round-robin among them.  A step: the producer declares the step's
arrays; every writer, in a thread of its own, writes each of its fields as
one level window under chunk-range leases, commits, and releases the
arrays; the step is done when the last writer has; the producer then wipes
the step that falls out of ``keep_steps``.  The writer of the traffic file
runs steps in a closed loop.

Data, references and the consumer are the field store's
(:mod:`benchmarks.chip.fields`, :mod:`benchmarks.chip.load`).  After the
window the consumer reads back the seeded sample of every kept step and,
strictly, one chunk of every writer's share of it.
"""
from __future__ import annotations

import threading
import traceback
from typing import List, Tuple

from repro.data.pipeline import ChunkedFieldStore

from benchmarks.chip import load
from benchmarks.chip.fields import make_fields as make_data  # noqa: F401
from benchmarks.chip.load import References  # noqa: F401

if not hasattr(ChunkedFieldStore, "declare_field"):
    raise ImportError("the I/O servers fill arrays declared before their "
                      "values exist: this program has no "
                      "ChunkedFieldStore.declare_field")


class Store(load.Store):
    """The field store's producer and consumer, and ``writers`` I/O-server
    clients with one :class:`~repro.data.pipeline.FieldWriter` each."""

    def __init__(self, config: dict, tracer=None):
        super().__init__(config, tracer)
        p = self.producer
        self.servers = [ChunkedFieldStore(p.store, p.fdb.config,
                                          writer=p.writer_key, tracer=tracer)
                        for _ in range(int(config["writers"]))]
        self.writers = [s.writer(f"io{i}")
                        for i, s in enumerate(self.servers)]

    def close(self) -> None:
        for w in self.writers:
            w.close()
        for s in self.servers:
            s.close()
        super().close()


class Traffic(load.Traffic):
    """The field store's closed-loop writer, its steps written by the
    store's I/O servers."""

    def __init__(self, spec: dict, store: Store, host, seed: int):
        super().__init__(spec, store, host, seed)
        fields = [(f, level) for f in store.fields
                  for level in range(self.shapes[f][0])]
        n = len(store.writers)
        #: the (field, level) pairs each writer writes, dealt round-robin
        self.shares: List[List[Tuple[str, int]]] = [fields[i::n]
                                                    for i in range(n)]

    def _serve(self, i: int, k: int) -> None:
        """Writer ``i``'s share of step ``k``: a level window a field,
        then its commit, then the release of the step's arrays."""
        st, src = self.store, k % self.distinct
        w = st.writers[i]
        names = {}
        for field, level in self.shares[i]:
            name = names[field] = st.array_name(field, k)
            with load._annotate("bench.write_window"):
                w.write_window(name, self.host[field][src][level:level + 1],
                               slice(level, level + 1))
        with load._annotate("bench.commit"):
            w.commit()
        for name in names.values():
            w.release(name)

    def write_step(self) -> int:
        st, k = self.store, self.next_step
        self.next_step += 1
        with load._annotate("bench.declare"):
            for name, f in st.fields.items():
                st.producer.declare_field(
                    st.array_name(name, k), self.shapes[name],
                    self.host[name].dtype, chunks=tuple(f["chunks"]),
                    codec=f["codec"])
        errors: List[BaseException] = []

        def serve(i: int) -> None:
            try:
                self._serve(i, k)
            except Exception as exc:     # the step fails, the run goes on
                errors.append(exc)

        threads = [threading.Thread(target=serve, args=(i,))
                   for i in range(len(st.writers))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]
        with self._lock:
            self.committed.append(k)
        old = k - int(st.config["keep_steps"])
        if old >= 0:
            with load._annotate("bench.wipe"):
                for name in st.fields:
                    st.producer.wipe_field(st.array_name(name, old))
        return sum(self.host[f][k % self.distinct].nbytes
                   for f in st.fields)

    def read_back(self) -> None:
        super().read_back()
        rng = self._rng(6)
        with self._lock:
            steps = self.committed[-int(self.store.config["keep_steps"]):]
        for step in steps:
            for share in self.shares:
                field, level = share[int(rng.integers(len(share)))]
                size = self.store.chunks(field)[1]
                points = self.shapes[field][1]
                lo = size * int(rng.integers(-(-points // size)))
                sel = (slice(level, level + 1),
                       slice(lo, min(lo + size, points)))
                try:
                    out = self.read(field, step, sel)
                except Exception:   # an acknowledged share is missing
                    self.unreadable += 1
                    with self._lock:
                        if self.first_error is None:
                            self.first_error = traceback.format_exc()
                    continue
                self.answers.append(
                    load.Answer((field, step % self.distinct), sel, out))
