"""The field store's traffic generator: the store of one configuration,
and the clients that one traffic file lists.  ``generators/fields.py``
hands it to ``run.py``, for every traffic file that names no other
generator; ``Work`` and ``Answer`` are what every generator reports (the
names a generator module exposes: :mod:`.spec`).

A configuration (``configs/<name>.json``) gives the fields, their chunks
and codecs, the store's client settings and how steps are laid out:

* ``array_per_step``: every output step is a new array per field
  (``<field>_s<step>``), archived behind one ``commit()``; a step is wiped
  once ``keep_steps`` newer ones exist;
* ``time_axis``: one array per field with a leading time axis, one chunk
  per time, all ``distinct_steps`` times archived in set-up.

A traffic file (``traffic/<name>.json``) lists the clients, each part
optional:

* ``writer``: archives the next step in a closed loop, or open loop every
  ``interval_s`` seconds;
* ``readers``: ``clients`` closed-loop product readers on one consumer
  client, dealt requests from ``kinds`` (a field, a weight and one spec
  per axis; see :class:`Deck`) over the newest committed step;
* ``loaders``: ``clients`` closed-loop training loaders, each sample one
  random time of every field through ``read_window``, stacked and put on
  the chip;
* ``prefill_steps``: steps archived in set-up; ``check``: how many
  answers are kept for the comparison.

An axis spec is ``{"index": true}`` (one random index) or a window of
``"len"`` indices, or ``"frac"`` of the axis, with an optional ``"step"``
and an optional ``"within": [lo, hi]`` range; its start is drawn from the
seed.  Every client draws from its own generator seeded by ``--seed``.
Clients stop issuing at the deadline, a reader once it has also finished
its round of kinds, so that every run reads whole rounds; the window
closes when the last piece of work issued completes.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

import numpy as np

from .reference import RefArray

BITS = {"field8": 8, "field16": 16}
#: how long after the deadline a client may still finish its work before
#: it counts as unanswered (it is waited for all the same)
GRACE_S = 60.0


@dataclasses.dataclass
class Answer:
    """What the timed path returned for one selection of one stored array:
    ``array`` is ``(field, distinct step)`` for a per-step array and
    ``(field, None)`` for a time-axis array."""
    array: Tuple[str, Optional[int]]
    sel: tuple
    result: np.ndarray


@dataclasses.dataclass
class Work:
    """One piece of work a client issued: a step, a read or a sample."""
    kind: str
    due: float
    start: float
    done: float = 0.0
    nbytes: int = 0
    error: Optional[str] = None


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Store:
    """One deployment of a configuration: a producer client and a consumer
    client of one simulated store."""

    def __init__(self, config: dict, tracer=None):
        from repro.core import FDBConfig
        from repro.data.pipeline import ChunkedFieldStore
        self.config = config
        self.fields = {f["name"]: f for f in config["fields"]}
        self.per_step = config["layout"] == "array_per_step"
        kw = dict(store=config["name"], writer="w0",
                  fdb_config=FDBConfig(**config["fdb"]),
                  cache_bytes=int(config["cache_bytes"]), tracer=tracer)
        self.producer = ChunkedFieldStore(**kw)
        self.consumer = ChunkedFieldStore(**kw)

    def array_name(self, field: str, step: Optional[int]) -> str:
        return f"{field}_s{step}" if self.per_step else field

    def chunks(self, field: str) -> Tuple[int, ...]:
        c = tuple(self.fields[field]["chunks"])
        return c if self.per_step else (1,) + c

    def stored_bytes(self, name: str) -> int:
        """Bytes of every object the store holds for array ``name``."""
        fdb = self.producer.fdb
        return sum(loc.length for _ident, loc in
                   fdb.list({"store": self.config["name"], "array": name}))

    def close(self) -> None:
        self.producer.close()
        self.consumer.close()


def _axis(rng, spec: dict, size: int):
    if spec.get("index"):
        lo, hi = spec.get("within", (0, size))
        return int(rng.integers(lo, hi))
    lo, hi = spec.get("within", (0, size))
    n = int(spec["len"]) if "len" in spec else max(
        1, int(round(float(spec["frac"]) * (hi - lo))))
    start = lo + int(rng.integers(0, hi - lo - n + 1))
    return slice(start, start + n, int(spec.get("step", 1)))


class Deck:
    """Request kinds dealt in shuffled rounds: each round holds every kind
    in proportion to its ``weight`` (the lightest once), so every seed
    sends the same mix of sizes, in another order."""

    def __init__(self, rng, kinds: List[dict], shapes: Dict[str, tuple]):
        self.rng, self.kinds, self.shapes = rng, kinds, shapes
        w = [float(k.get("weight", 1)) for k in kinds]
        self._round = [i for i, x in enumerate(w)
                       for _ in range(int(round(x / min(w))))]
        self._left: List[int] = []

    @property
    def round_done(self) -> bool:
        return not self._left

    def draw(self):
        """(kind number, field, selection) of the next request."""
        if not self._left:
            self._left = list(self.rng.permutation(self._round))
        i = int(self._left.pop())
        k = self.kinds[i]
        shape = self.shapes[k["field"]]
        return i, k["field"], tuple(_axis(self.rng, a, n)
                                    for a, n in zip(k["axes"], shape))


class Traffic:
    """The clients of one traffic file on one :class:`Store`."""

    def __init__(self, spec: dict, store: Store, host: Dict[str, np.ndarray],
                 seed: int):
        self.spec = spec
        self.store = store
        self.host = host
        self.seed = int(seed)
        self.distinct = int(store.config["distinct_steps"])
        self.shapes = {f: tuple(s["shape"]) for f, s in store.fields.items()}
        self.check = spec.get("check", {})
        self._lock = threading.Lock()
        self.committed: List[int] = []
        self.next_step = 0
        self.work: List[Work] = []
        self.answers: List[Answer] = []
        self._kept: List[tuple] = []
        self.first_error: Optional[str] = None
        self.unfinished = 0
        #: answers that could not be read back after the window
        self.unreadable = 0

    def _rng(self, *role: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *role])

    # -- the writer ----------------------------------------------------------
    def write_step(self) -> int:
        """Archive the next step of every field, commit, and wipe the step
        that falls out of ``keep_steps``; returns the raw bytes archived."""
        st, k = self.store, self.next_step
        self.next_step += 1
        raw = 0
        for name, f in st.fields.items():
            values = self.host[name][k % self.distinct]
            with _annotate("bench.put_field"):
                st.producer.put_field(st.array_name(name, k), values,
                                      chunks=tuple(f["chunks"]),
                                      codec=f["codec"])
            raw += values.nbytes
        with _annotate("bench.commit"):
            st.producer.commit()
        with self._lock:
            self.committed.append(k)
        old = k - int(st.config["keep_steps"])
        if old >= 0:
            with _annotate("bench.wipe"):
                for name in st.fields:
                    st.producer.wipe_field(st.array_name(name, old))
        return raw

    def _archive_time_axis(self) -> None:
        st = self.store
        for name, f in st.fields.items():
            st.producer.put_field(name, self.host[name],
                                  chunks=st.chunks(name), codec=f["codec"])
        st.producer.commit()

    # -- readers and loaders -------------------------------------------------
    def read(self, field: str, step: Optional[int], sel: tuple) -> np.ndarray:
        st = self.store
        with _annotate("bench.read_window"):
            return st.consumer.read_window(st.array_name(field, step), *sel,
                                           fill_missing=False)

    def newest(self) -> int:
        with self._lock:
            return self.committed[-1]

    def load_sample(self, t: int):
        import jax
        parts = [self.read(f, None, (t,)) for f in self.store.fields]
        with _annotate("bench.device_put"):
            sample = np.stack(parts).reshape((-1,) + parts[0].shape[1:])
            dev = jax.device_put(sample)
            dev.block_until_ready()
        return dev

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        """Archive what the window reads and run every shape it uses once."""
        if not self.store.per_step:
            self._archive_time_axis()
        for _ in range(int(self.spec.get("prefill_steps", 0))):
            self.write_step()
        readers = self.spec.get("readers")
        if readers:
            step = self.newest() if self.store.per_step else None
            rng = self._rng(99)
            regions = set()
            for k in readers["kinds"]:
                _i, field, sel = Deck(rng, [k], self.shapes).draw()
                self.read(field, step, sel)
                # a hot set is read whole once, so the cache holds it
                within = tuple(tuple(a.get("within", ())) for a in k["axes"])
                if any(within) and (field, within) not in regions:
                    regions.add((field, within))
                    self.read(field, step, tuple(
                        slice(*w) if w else slice(None) for w in within))
        if self.spec.get("loaders"):
            self.load_sample(0)

    # -- the window ----------------------------------------------------------
    def _record(self, w: Work) -> None:
        with self._lock:
            self.work.append(w)

    def _fail(self, w: Work, exc: BaseException) -> None:
        w.error = f"{type(exc).__name__}: {exc}"
        with self._lock:
            if self.first_error is None:
                self.first_error = traceback.format_exc()

    def _writer(self, t0: float, deadline: float, go: threading.Event):
        spec = self.spec["writer"]
        interval = spec.get("interval_s")
        go.wait()
        i = 0
        while True:
            due = t0 + i * interval if interval else time.perf_counter()
            if due >= deadline:
                return
            if interval:
                time.sleep(max(0.0, due - time.perf_counter()))
            w = Work("step", due, time.perf_counter())
            try:
                w.nbytes = self.write_step()
            except Exception as exc:     # counted as failed, run goes on
                self._fail(w, exc)
            w.done = time.perf_counter()
            self._record(w)
            i += 1

    def _reader(self, cid: int, deadline: float, go: threading.Event):
        spec = self.spec["readers"]
        deck = Deck(self._rng(1, cid), spec["kinds"], self.shapes)
        keep_rng = self._rng(2, cid)
        keep_p = float(self.check.get("keep_p", 0.0))
        keep_max = int(self.check.get("keep_per_client", 0))
        kept, seen = 0, set()
        go.wait()
        while time.perf_counter() < deadline or not deck.round_done:
            kind, field, sel = deck.draw()
            step = self.newest() if self.store.per_step else None
            w = Work("read", time.perf_counter(), time.perf_counter())
            try:
                out = self.read(field, step, sel)
                w.nbytes = out.nbytes
            except Exception as exc:     # counted as failed, run goes on
                self._fail(w, exc)
                out = None
            w.done = time.perf_counter()
            self._record(w)
            keep = keep_rng.random() < keep_p and kept < keep_max
            first = cid == 0 and kind not in seen
            if out is not None and (keep or first):
                kept += keep
                seen.add(kind)
                src = None if step is None else step % self.distinct
                with self._lock:
                    self.answers.append(Answer((field, src), sel, out))

    def _loader(self, cid: int, deadline: float, go: threading.Event):
        rng = self._rng(3, cid)
        keep_rng = self._rng(4, cid)
        keep_p = float(self.check.get("keep_p", 0.0))
        keep_max = int(self.check.get("keep_per_client", 0))
        kept = 0
        go.wait()
        while time.perf_counter() < deadline:
            t = int(rng.integers(self.distinct))
            w = Work("sample", time.perf_counter(), time.perf_counter())
            try:
                dev = self.load_sample(t)
                w.nbytes = dev.nbytes
            except Exception as exc:     # counted as failed, run goes on
                self._fail(w, exc)
                dev = None
            w.done = time.perf_counter()
            self._record(w)
            # a loader's first sample is always kept, so every run checks
            if dev is not None and kept < keep_max \
                    and (kept == 0 or keep_rng.random() < keep_p):
                kept += 1
                with self._lock:
                    self._kept.append((t, dev))

    def run(self, seconds: float) -> Tuple[float, float]:
        """Drive every client for ``seconds``; returns the window's (start,
        end) on the ``perf_counter`` clock."""
        go = threading.Event()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        threads = []
        if self.spec.get("writer"):
            threads.append(threading.Thread(
                target=self._writer, args=(t0, deadline, go)))
        for cid in range(int((self.spec.get("readers") or {})
                             .get("clients", 0))):
            threads.append(threading.Thread(
                target=self._reader, args=(cid, deadline, go)))
        for cid in range(int((self.spec.get("loaders") or {})
                             .get("clients", 0))):
            threads.append(threading.Thread(
                target=self._loader, args=(cid, deadline, go)))
        for th in threads:
            th.start()
        go.set()
        for th in threads:
            th.join(timeout=max(0.0, deadline + GRACE_S - time.perf_counter()))
        self.unfinished = sum(th.is_alive() for th in threads)
        for th in threads:          # a late client is waited for all the same
            th.join()
        end = max([w.done for w in self.work], default=time.perf_counter())
        return t0, end

    # -- after the window ----------------------------------------------------
    def read_back(self) -> None:
        """Read a seeded sample of chunks of every step still stored back
        through the consumer: the storage round trip of the steps archived."""
        n = int(self.check.get("stored_chunks", 0))
        if not (n and self.store.per_step and self.spec.get("writer")):
            return
        rng = self._rng(5)
        with self._lock:
            keep = int(self.store.config["keep_steps"])
            steps = self.committed[-keep:]
        for step in steps:
            for field in self.store.fields:
                shape, chunks = self.shapes[field], self.store.chunks(field)
                grid = [-(-s // c) for s, c in zip(shape, chunks)]
                for _ in range(n):
                    idx = [int(rng.integers(g)) for g in grid]
                    sel = tuple(slice(i * c, min((i + 1) * c, s))
                                for i, c, s in zip(idx, chunks, shape))
                    try:
                        out = self.read(field, step, sel)
                    except Exception:   # an acknowledged step is missing
                        self.unreadable += 1
                        with self._lock:
                            if self.first_error is None:
                                self.first_error = traceback.format_exc()
                        continue
                    self.answers.append(
                        Answer((field, step % self.distinct), sel, out))

    def collect(self) -> List[Answer]:
        """Every kept answer, with loaders' samples copied off the chip."""
        fields = list(self.store.fields)
        for t, dev in self._kept:
            host = np.asarray(dev).reshape(
                (len(fields), -1) + dev.shape[1:])
            for i, f in enumerate(fields):
                self.answers.append(Answer((f, None), (t,), host[i]))
        self._kept = []
        return self.answers

    def stored_ratio(self) -> Optional[float]:
        """Stored bytes over raw bytes of every array still stored."""
        st = self.store
        if st.per_step:
            with self._lock:
                steps = self.committed[-int(st.config["keep_steps"]):]
            names = [(f, s) for s in steps for f in st.fields]
        else:
            names = [(f, None) for f in st.fields]
        if not names:
            return None
        stored = sum(st.stored_bytes(st.array_name(f, s)) for f, s in names)
        raw = sum(self.host[f][0].nbytes if s is not None
                  else self.host[f].nbytes for f, s in names)
        return stored / raw


class References:
    """The reference's copy of every stored array, made when first asked."""

    def __init__(self, store: Store, host: Dict[str, np.ndarray],
                 bits_of=None):
        self.store = store
        self.host = host
        self.bits_of = bits_of or (lambda bits: bits)
        self._arrays: Dict[tuple, RefArray] = {}

    def codec(self, key: Tuple[str, Optional[int]]) -> str:
        """The codec the field of stored array ``key`` declares."""
        return self.store.fields[key[0]]["codec"]

    def __getitem__(self, key: Tuple[str, Optional[int]]) -> RefArray:
        arr = self._arrays.get(key)
        if arr is None:
            field, src = key
            values = self.host[field] if src is None else self.host[field][src]
            bits = self.bits_of(BITS[self.store.fields[field]["codec"]])
            arr = self._arrays[key] = RefArray(values,
                                               self.store.chunks(field), bits)
        return arr
