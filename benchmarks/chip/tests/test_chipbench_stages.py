"""The codec's stages and the plans' assembly as the benchmark reads them:
the per-layer metrics over the program's own spans, in tiny traced runs of
every traffic kind on the CPU and over a program that opens no such span;
the program's counters of values through the codec against the harness's
own count, and the kernels' device time per counted value."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmarks.chip import fields, load, run  # noqa: E402
from benchmarks.chip.context import Context  # noqa: E402
from benchmarks.chip.spec import find_cell, load_reader  # noqa: E402
from benchmarks.chip.trace_reduce import Device, Trace  # noqa: E402
from repro.core import reset_engines  # noqa: E402
from repro.obs.trace import Tracer  # noqa: E402
# the module's tree of tiny cells and its guard of the compile cache
from test_chipbench_cells import _run, no_compile_cache, tree  # noqa

METRICS = Path(__file__).resolve().parents[1] / "metrics"


@pytest.mark.parametrize("cell,metrics", [
    ("archive", {"encode_host_ms_per_gb.archive",
                 "write_cpu_ms_per_gb.archive"}),
    ("pgen", {"decode_host_ms_per_req.pgen", "assemble_ms_per_req.pgen"}),
    ("train", {"decode_host_ms_per_gb.train"}),
    ("hot", {"assemble_ms_per_req.hot"}),
])
def test_traced_run_reports_the_stage_metrics(tree, cell, metrics):
    r = _run(tree, cell, trace=True)
    assert r["correct"], r["checks"]
    assert metrics <= set(r["metrics"])
    for name in metrics:
        assert r["metrics"][name]["value"] >= 0


@pytest.mark.parametrize("cell", ["archive", "pgen", "train", "hot"])
def test_value_counters_match_the_codec_counts(tree, cell):
    """Over a window, the program's ``codec.values_encoded`` and
    ``codec.values_decoded`` count what the harness counts from outside at
    the codec's batch entry points, read when the window closes."""
    c = find_cell(cell, tree)
    seed = 2 ** 31 + 11
    host = {k: np.asarray(v)
            for k, v in fields.make_fields(c.config, seed).items()}
    tracer = Tracer(enabled=False)
    reset_engines()
    store = load.Store(c.config, tracer)
    traffic = load.Traffic(c.traffic, store, host, seed)
    traffic.setup()
    counts = dict.fromkeys(("encode_elements", "encode_hbm_bytes",
                            "decode_elements", "decode_hbm_bytes"), 0)
    before = run._counters(tracer.metrics)
    undo = run._count_codec(counts)
    try:
        traffic.run(0.4)
    finally:
        undo()
    after = run._counters(tracer.metrics)
    store.close()
    reset_engines()

    def grew(name):
        return after.get(name, 0) - before.get(name, 0)
    assert grew("codec.values_encoded") == counts["encode_elements"]
    assert grew("codec.values_decoded") == counts["decode_elements"]
    assert counts["encode_elements"] + counts["decode_elements"] > 0


def test_stage_metrics_say_nothing_of_a_program_without_stages():
    """Over a program that opens no codec stage or ``plan.assemble`` span
    and measures no span's CPU time, the readers of those metrics return
    None."""
    old = [SimpleNamespace(name=n, duration_us=5.0)
           for n in ("codec.encode", "codec.decode", "io.archive",
                     "io.fetch", "plan.execute")]
    work = [load.Work(kind, 0.0, 0.0, 1.0, nbytes=10 ** 9)
            for kind in ("step", "read", "sample")]
    ctx = Context(trace=None, spans=old, counters={}, codec={}, work=work,
                  window_s=1.0, peaks={})
    names = ("encode_host_ms_per_gb.archive",
             "write_cpu_ms_per_gb.archive", "decode_host_ms_per_req.pgen",
             "assemble_ms_per_req.pgen", "decode_host_ms_per_gb.train",
             "assemble_ms_per_req.hot")
    for name in names:
        assert load_reader(METRICS / f"{name}.py")(ctx) is None, name
    # the same readers over the spans of the stages
    stages = [SimpleNamespace(name="codec.encode.stack", duration_us=2000.0),
              SimpleNamespace(name="codec.encode.pack", duration_us=500.0),
              SimpleNamespace(name="codec.encode.d2h", duration_us=9000.0)]
    worked = SimpleNamespace(name="io.archive", duration_us=1.0,
                             cpu_ns=3_000_000)
    ctx.spans = old + stages + [worked]
    read = load_reader(METRICS / "encode_host_ms_per_gb.archive.py")
    assert read(ctx) == pytest.approx(2.5)
    read = load_reader(METRICS / "write_cpu_ms_per_gb.archive.py")
    assert read(ctx) == pytest.approx(3.0)


@pytest.mark.parametrize("name,module,counter", [
    ("field_encode_ns_per_value.archive", "jit_field_encode",
     "codec.values_encoded"),
    ("field_decode_ns_per_value.train", "jit_field_decode",
     "codec.values_decoded"),
])
def test_kernel_time_per_counted_value(name, module, counter):
    """Device time of the kernel's jitted function over the values the
    program counted; nothing where the program counts no values (as a
    program without the counters) or the trace holds no such function."""
    read = load_reader(METRICS / f"{name}.py")
    # window 0..1000 ns: two 100 ns runs of the module, one of another
    dev = Device(ops=[("k", 100, 200, module), ("k", 300, 400, module),
                      ("x", 500, 900, "jit_other")], modules=[])
    ctx = Context(trace=Trace((0, 1000), [dev], []), spans=[],
                  counters={counter: 50}, codec={}, work=[],
                  window_s=1e-6, peaks={})
    assert read(ctx) == pytest.approx(4.0)
    ctx.counters = {}
    assert read(ctx) is None
    ctx.counters = {counter: 50}
    ctx.trace = Trace((0, 1000), [Device(ops=[("x", 500, 900, "jit_other")],
                                         modules=[])], [])
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None
