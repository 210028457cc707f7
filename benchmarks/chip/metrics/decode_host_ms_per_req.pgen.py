"""Milliseconds of the host stages of decoding (``codec.decode.stack`` and
``codec.decode.unpack`` spans) per product request completed in the
window."""
from benchmarks.chip.stages import per_done, span_ms


def read(ctx):
    return per_done(ctx, span_ms(ctx, "codec.decode.stack",
                                 "codec.decode.unpack"), "read")
