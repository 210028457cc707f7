"""Run one cell of ``BENCHMARK.json`` once on the chip and print its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The run refuses to start, printing no result, unless JAX's devices are
TPUs and at least as many as the cell asks for.  Everything the cell
deploys comes from its generator module (``generators/<name>.py``, named
by the traffic file, ``fields`` by default; what it exposes:
:mod:`spec`), so this file names no deployment.  Set-up makes the cell's
data on the chip from ``--seed`` (``make_data``), builds the store and
its clients, which archive what the window reads and run every shape the
window uses once (JAX's persistent compilation cache lives in
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise).  The window then drives the cell's traffic for ``--seconds``;
with ``--trace 1`` a profiler trace and the program's spans cover it and
the per-layer metrics are reported, otherwise the end-to-end ones, which
count the window's ``Work`` by kind.  After the window every kept answer
is held to the generator's plain reference (:mod:`check`).  The last
line of standard output is one JSON object; the numbers compared, with
their limits, close standard error.

``--control 1`` puts the lower-precision reference in the program's place
for the comparison; it must come out as not correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[2]
for _p in (CHECKOUT / "src", CHECKOUT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402
from repro.core import reset_engines  # noqa: E402
from repro.obs.trace import Tracer  # noqa: E402

from benchmarks.chip import check, trace_reduce  # noqa: E402
from benchmarks.chip.context import Context  # noqa: E402
from benchmarks.chip.peaks import codec_hbm_bytes, peaks  # noqa: E402
from benchmarks.chip.spec import Cell, find_cell  # noqa: E402


class CompileClock:
    """Counts and sums XLA backend compilations while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(
            self._on_event)


def _count_codec(counts: dict):
    """Count the elements through the codec's batch entry points, and
    annotate them in the profiler trace; returns the undo."""
    import jax
    from repro.tensorstore.codec import CODECS
    lock = threading.Lock()
    patched = []
    for codec in CODECS.values():
        bits = getattr(codec, "bits", None)
        if bits is None:
            continue

        def encode_batch(arrs, _f=codec.encode_batch, _bits=bits):
            n = sum(int(np.size(a)) for a in arrs)
            with jax.profiler.TraceAnnotation("codec.encode_batch"):
                out = _f(arrs)
            with lock:
                counts["encode_elements"] += n
                counts["encode_hbm_bytes"] += codec_hbm_bytes(n, _bits)
            return out

        def decode_batch(datas, shapes, dtype, _f=codec.decode_batch,
                         _bits=bits):
            n = sum(int(np.prod(s)) for s in shapes)
            with jax.profiler.TraceAnnotation("codec.decode_batch"):
                out = _f(datas, shapes, dtype)
            with lock:
                counts["decode_elements"] += n
                counts["decode_hbm_bytes"] += codec_hbm_bytes(n, _bits)
            return out

        codec.encode_batch = encode_batch
        codec.decode_batch = decode_batch
        patched.append(codec)

    def undo():
        for codec in patched:
            del codec.encode_batch, codec.decode_batch
    return undo


def _counters(registry) -> dict:
    return {name: snap["value"] for name, snap in registry.snapshot().items()
            if snap.get("type") == "counter"}


def end_to_end(name: str, work, window_s: float, setup_s: float,
               stored_ratio):
    done = [w for w in work if w.error is None]
    if name == "setup_s":
        return setup_s
    if name == "write_gbps":
        return sum(w.nbytes for w in done if w.kind == "step") / window_s / 1e9
    if name == "read_gbps":
        return sum(w.nbytes for w in done
                   if w.kind in ("read", "sample")) / window_s / 1e9
    if name == "read_p95_ms":
        lat = [1e3 * (w.done - w.start) for w in done if w.kind == "read"]
        return float(np.percentile(lat, 95)) if lat else None
    if name == "stored_ratio":
        return stored_ratio
    raise KeyError(f"no end-to-end metric {name!r} in the harness")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool = False,
             control: bool = False, peak_table: dict = None) -> dict:
    """Set up, drive and check one run of ``cell`` on JAX's default
    device; returns the result line as a dict."""
    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    # every program the run compiles, however small, is found again by
    # the next run in this checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    dev = jax.devices()[0]
    t_init = time.perf_counter()

    gen = cell.generator
    made = gen.make_data(cell.config, seed)
    host = {k: np.asarray(v) for k, v in made.items()}
    for v in made.values():
        v.delete()
    del made
    t_data = time.perf_counter()

    tracer = Tracer(enabled=False, capacity=1 << 21)
    reset_engines()
    store = gen.Store(cell.config, tracer)
    traffic = gen.Traffic(cell.traffic, store, host, seed)
    traffic.setup()
    t_ready = time.perf_counter()
    setup = {"setup_s": t_ready - T_START, "init_s": t_init - T_START,
             "data_s": t_data - t_init, "warmup_s": t_ready - t_data,
             "compile_s": clock.seconds}

    compiles_before = clock.count
    codec_counts = dict.fromkeys(("encode_elements", "encode_hbm_bytes",
                                  "decode_elements", "decode_hbm_bytes"), 0)
    tmp = None
    if trace:
        undo = _count_codec(codec_counts)
        tracer.enable()
        mark = tracer.mark()
        counters0 = _counters(tracer.metrics)
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        # host annotations only: the Python tracer would slow the host
        # path it measures and write a trace of hundreds of MB
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
    cpu0 = time.process_time()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0, t1 = traffic.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
            tracer.disable()
            undo()
    # the process's CPU seconds in the window: a host-bound run that is
    # slow with as many of them was slowed by its host, not by more work
    cpu_s = time.process_time() - cpu0
    window_s = t1 - t0
    compiles = clock.count - compiles_before
    clock.close()
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    t_check = time.perf_counter()
    traffic.read_back()
    answers = traffic.collect()
    stored_ratio = traffic.stored_ratio()
    store.close()
    reset_engines()

    refs = gen.References(store, host)
    lower = gen.References(store, host, check.LOWER_BITS.get) \
        if control else None
    err = check.compare(answers, refs, lower)
    work = traffic.work
    failed = sum(w.error is not None for w in work)
    check_s = time.perf_counter() - t_check
    checks = check.decide(err, cell.config["check"]["err_ratio_limit"],
                          len(answers), failed + traffic.unreadable,
                          traffic.unfinished)

    metrics, breakdown = {}, None
    if trace:
        xplane = trace_reduce.find_xplane(tmp)
        reduced = trace_reduce.reduce(xplane) if xplane else None
        shutil.rmtree(tmp, ignore_errors=True)
        counters1 = _counters(tracer.metrics)
        ctx = Context(
            trace=reduced, spans=tracer.spans(mark),
            counters={k: v - counters0.get(k, 0)
                      for k, v in counters1.items()},
            codec=codec_counts, work=work, window_s=window_s,
            peaks=peak_table or {})
        for m in cell.per_layer:
            value = m.read(ctx)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            breakdown = {"device_ops": reduced.top_ops(10),
                         "idle_gaps": reduced.idle_gaps(10)}
    else:
        for m in cell.end_to_end:
            value = end_to_end(m.name, work, window_s, setup["setup_s"],
                               stored_ratio)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}

    steps = [w for w in work if w.kind == "step"]
    late = [1e3 * (w.start - w.due) for w in steps]
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": len(work), "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["setup"] = setup
    out["window"] = {
        "seconds": window_s, "compiles": compiles,
        "steps": len(steps),
        "step_s_mean": (sum(w.done - w.start for w in steps) / len(steps)
                        if steps else None),
        "reads": sum(w.kind == "read" for w in work),
        "samples": sum(w.kind == "sample" for w in work),
        "writer_late_ms_max": max(late, default=None),
        "step_s": [round(w.done - w.start, 4) for w in steps],
        "cpu_s": cpu_s,
        "stored_ratio": stored_ratio, "codec": codec_counts,
        "check_s": check_s, "control": control,
        "first_error": traffic.first_error}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = find_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s); "
              f"nothing was run", file=sys.stderr)
        return 1
    table = peaks(devices[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      bool(args.control), table)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
