"""Milliseconds of the threads' CPU time (the ``cpu_ns`` slot) in
``codec.encode`` and ``io.archive`` spans per GB of raw field archived in
the window: the write path's work, apart from its waits."""
from benchmarks.chip.stages import per_gb, span_cpu_ms


def read(ctx):
    return per_gb(ctx, span_cpu_ms(ctx, "codec.encode", "io.archive"),
                  "step")
