"""Async chunk-I/O executor: thread pool + futures with a bounded in-flight
window.

The paper's core result is that object stores win when clients keep many
independent object-granular I/Os in flight; this executor is the client-side
half of that — ``submit()`` admits at most ``max_in_flight`` outstanding
tasks (queued + running) and blocks the producer beyond that, bounding the
memory held by encoded chunks while keeping the pipe full.

Callers' :mod:`contextvars` context (the engine meter's ``client_context``
and the obs layer's active span) is propagated into worker threads so op
attribution — and span parentage — survives the hop.

This module's only ``repro`` import is the dependency-free
:mod:`repro.obs` package: :mod:`repro.core.fdb` reaches for the executor
lazily without creating an import cycle, and ``repro.obs`` imports nothing
back.

When a caller submits from inside a traced span, the time between
``submit()`` and the task starting on a worker is recorded as an
``executor.queue`` span (parented under the caller's span) plus an
``executor.queue_us`` histogram and ``executor.in_flight`` gauge — the
``t_queue`` phase of the bench columns.  Untraced submissions skip all of
it via one context-var read.
"""
from __future__ import annotations

import contextvars
import threading
import time
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor
from concurrent.futures import wait as wait_futures
from typing import Any, Callable, Iterable, List, Optional

from repro.obs import trace as _obs

DEFAULT_WORKERS = 8


def annotate_error(e: BaseException, note: str) -> None:
    """Attach ``note`` to an in-flight exception without re-raising a new
    one: ``add_note`` on 3.11+, an extra ``args`` element (visible in the
    rendered message) on 3.10."""
    add = getattr(e, "add_note", None)
    if add is not None:
        add(note)
    else:
        e.args = e.args + (note,)


class ChunkExecutor:
    def __init__(self, max_workers: int = DEFAULT_WORKERS,
                 max_in_flight: Optional[int] = None):
        if max_workers < 1:
            raise ValueError("max_workers must be >= 1")
        self.max_workers = max_workers
        self.max_in_flight = max_in_flight or 4 * max_workers
        if self.max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1")
        self._pool = ThreadPoolExecutor(max_workers=max_workers,
                                        thread_name_prefix="fdbx-io")
        self._window = threading.Semaphore(self.max_in_flight)
        self._lock = threading.Lock()
        self._in_flight = 0
        self.peak_in_flight = 0
        #: ``fair`` map_ordered calls running now
        self._fair_calls = 0

    # -- core API -------------------------------------------------------------
    def submit(self, fn: Callable[..., Any], *args: Any, **kw: Any) -> Future:
        """Schedule ``fn(*args, **kw)``; blocks while the window is full."""
        self._window.acquire()
        with self._lock:
            self._in_flight += 1
            self.peak_in_flight = max(self.peak_in_flight, self._in_flight)
            depth = self._in_flight
        ctx = contextvars.copy_context()
        parent = _obs.current_span()
        if parent is not None and parent.tracer.enabled:
            tracer = parent.tracer
            tracer.metrics.gauge("executor.in_flight").set(depth)
            t_submit = time.perf_counter_ns()

            def task(_fn=fn, _args=args, _kw=kw):
                now = time.perf_counter_ns()
                tracer.record_complete("executor.queue", t_submit, now,
                                       parent=parent)
                tracer.metrics.histogram("executor.queue_us").observe(
                    (now - t_submit) / 1_000.0)
                return _fn(*_args, **_kw)
        else:
            task = None
        try:
            if task is not None:
                fut = self._pool.submit(ctx.run, task)
            else:
                fut = self._pool.submit(ctx.run, fn, *args, **kw)
        except BaseException:
            self._leave()
            raise
        fut.add_done_callback(lambda _f: self._leave())
        return fut

    def _leave(self) -> None:
        with self._lock:
            self._in_flight -= 1
        self._window.release()

    @property
    def in_flight(self) -> int:
        with self._lock:
            return self._in_flight

    def map_ordered(self, fn: Callable[[Any], Any],
                    items: Iterable[Any],
                    describe: Optional[Callable[[Any], str]] = None,
                    fair: bool = False) -> List[Any]:
        """Run ``fn`` over ``items`` concurrently; results in input order.

        Items may be wildly mixed-size units of work — the tensorstore write
        path mixes direct chunk encodes with read-modify-write fetches, the
        read path maps stages that each fetch and decode up to a window of
        chunks — the bounded window simply admits whatever comes next.

        The first raised exception propagates (after all futures settle, so
        no task outlives the call with shared state in hand) — annotated
        with which item failed (its input position, ``describe(item)`` when
        a describer is given, and how many sibling tasks also failed), so a
        retried-then-exhausted chunk op surfaces with its context instead
        of a bare backend error.

        ``fair`` calls keep at most their share of the workers busy:
        ``max_workers`` divided by the fair calls running at that moment
        (at least one).  Concurrent fair callers — readers whose items are
        long stages of decode work — then progress at equal rates, however
        many items each has, while a lone one uses every worker.
        """
        items = list(items)
        if not fair:
            return self._settle([self.submit(fn, item) for item in items],
                                items, describe)
        with self._lock:
            self._fair_calls += 1
        futures: List[Future] = []
        try:
            for item in items:
                while True:
                    running = [f for f in futures if not f.done()]
                    if len(running) < max(1, self.max_workers
                                          // self._fair_calls):
                        break
                    wait_futures(running, return_when=FIRST_COMPLETED)
                futures.append(self.submit(fn, item))
            return self._settle(futures, items, describe)
        finally:
            with self._lock:
                self._fair_calls -= 1

    @staticmethod
    def _settle(futures: List[Future], items: List[Any],
                describe: Optional[Callable[[Any], str]]) -> List[Any]:
        """Wait for every future; results in order, or the first error
        annotated with its item (see :meth:`map_ordered`)."""
        results: List[Any] = []
        first_error, first_pos, n_failed = None, -1, 0
        for pos, fut in enumerate(futures):
            try:
                results.append(fut.result())
            except BaseException as e:  # noqa: BLE001
                n_failed += 1
                if first_error is None:
                    first_error, first_pos = e, pos
                results.append(None)
        if first_error is not None:
            label = ""
            if describe is not None:
                try:
                    label = f" ({describe(items[first_pos])})"
                except Exception:   # a broken describer must not mask
                    label = ""      # the real failure
            annotate_error(
                first_error,
                f"first failure of {n_failed}/{len(futures)} executor "
                f"task(s): item {first_pos}{label}")
            raise first_error
        return results

    def shutdown(self, wait: bool = True) -> None:
        self._shut = True
        self._pool.shutdown(wait=wait)

    @property
    def is_shutdown(self) -> bool:
        """True once :meth:`shutdown` ran — lets owners (and tests) observe
        an executor's lifecycle; submitting to a shut-down pool raises."""
        return getattr(self, "_shut", False)

    def __enter__(self) -> "ChunkExecutor":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.shutdown()


#: process-global shared executors, one per requested depth (threads are
#: created lazily by the pool, so idle entries cost almost nothing)
_SHARED: dict = {}
_SHARED_LOCK = threading.Lock()


def sized_executor(max_workers: int) -> ChunkExecutor:
    """Shared executor with exactly ``max_workers`` of overlap depth."""
    with _SHARED_LOCK:
        ex = _SHARED.get(max_workers)
        if ex is None:
            ex = _SHARED[max_workers] = ChunkExecutor(max_workers=max_workers)
        return ex


def default_executor() -> ChunkExecutor:
    return sized_executor(DEFAULT_WORKERS)
