"""Milliseconds of the host stages of decoding (``codec.decode.stack`` and
``codec.decode.unpack`` spans) per GB of samples delivered to the chip in
the window."""
from benchmarks.chip.stages import per_gb, span_ms


def read(ctx):
    return per_gb(ctx, span_ms(ctx, "codec.decode.stack",
                               "codec.decode.unpack"), "sample")
