"""Milliseconds of the host stages of encoding (``codec.encode.stack`` and
``codec.encode.pack`` spans) per GB of raw field archived in the window."""
from benchmarks.chip.stages import per_gb, span_ms


def read(ctx):
    return per_gb(ctx, span_ms(ctx, "codec.encode.stack",
                               "codec.encode.pack"), "step")
