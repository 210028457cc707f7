"""Benchmark runner: one function per thesis table/figure.

Prints ``name,us_per_call,derived`` CSV rows; ``--json FILE`` additionally
dumps the rows (with their structured read_ops/write_ops/throughput fields)
to a perf-trajectory file — the repo commits one ``BENCH_<n>.json`` per perf
PR so regressions are diffable.  ``--suites a,b`` selects suites,
``--tiny`` switches suites that support it onto their CI smoke profile.

``--trace FILE`` enables the process tracer (:mod:`repro.obs`) for the
whole run and writes one combined Chrome ``trace_event`` JSON — each suite
becomes a Perfetto process row (``pid`` = suite index) so the plan
lifecycle spans (``plan.resolve`` → ``io.fetch``/``codec.decode`` → ...)
of every benchmark land on one timeline.  Open it at
https://ui.perfetto.dev.
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import traceback

SUITES = [
    ("ior", "bench_ior"),                      # Figs. 4.5-4.7 / 4.19-4.20
    ("fieldio", "bench_fieldio"),              # Figs. 4.8-4.11
    ("hammer", "bench_hammer"),                # Figs. 4.12-4.13 / 4.21-4.25
    ("rados_options", "bench_rados_options"),  # Fig. 3.5
    ("small_objects", "bench_small_objects"),  # Fig. 4.26
    ("redundancy", "bench_redundancy"),        # Figs. 4.27-4.28
    ("ckpt", "bench_ckpt"),                    # §3.1.3 operational pattern
    ("tensorstore", "bench_tensorstore"),      # chunk size x parallelism
    ("workflow", "bench_workflow"),            # NWP cycle + chaos gate
    ("roofline", "roofline"),                  # §Roofline deliverable
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--suites", default=None,
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="also dump rows as JSON to FILE")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny CI profile for suites that support it")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="enable I/O tracing and write a Chrome trace_event "
                         "JSON (load in https://ui.perfetto.dev)")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    wanted = None if args.suites is None else {
        s.strip() for s in args.suites.split(",") if s.strip()}
    selected = [(n, m) for n, m in SUITES if wanted is None or n in wanted]
    if wanted is not None:
        unknown = wanted - {n for n, _m in SUITES}
        if unknown:
            sys.exit(f"unknown suites: {sorted(unknown)} "
                     f"(known: {[n for n, _m in SUITES]})")

    tracer = None
    if args.trace:
        from repro.obs.trace import GLOBAL_TRACER
        tracer = GLOBAL_TRACER
        tracer.enable()

    import importlib
    print("name,us_per_call,derived")
    failures = 0
    json_rows = []
    trace_events = []
    for pid, (name, modname) in enumerate(selected):
        if tracer is not None:
            mark = tracer.mark()
        try:
            mod = importlib.import_module(f"benchmarks.{modname}")
            kwargs = {}
            if args.tiny and "tiny" in inspect.signature(
                    mod.run).parameters:
                kwargs["tiny"] = True
            for row in mod.run(**kwargs):
                print(row.line(), flush=True)
                json_rows.append({"suite": name, **row.to_json()})
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},,ERROR", flush=True)
            traceback.print_exc()
        if tracer is not None:
            trace_events.append({"name": "process_name", "ph": "M",
                                 "pid": pid, "tid": 0,
                                 "args": {"name": f"suite:{name}"}})
            trace_events.extend(tracer.chrome_events(since=mark, pid=pid))
    if tracer is not None:
        with open(args.trace, "w") as f:
            json.dump({"traceEvents": trace_events,
                       "displayTimeUnit": "ms"}, f)
        if tracer.dropped:
            print(f"[trace buffer overflow: {tracer.dropped} oldest spans "
                  f"evicted — raise repro.obs.trace.DEFAULT_CAPACITY or "
                  f"trace fewer suites]", file=sys.stderr)
        print(f"trace written to {args.trace} "
              f"(open in https://ui.perfetto.dev)", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"suites": [n for n, _m in selected],
                       "tiny": args.tiny, "rows": json_rows}, f, indent=1)
            f.write("\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
