"""Milliseconds of ``codec.decode`` spans per GB of samples delivered to
the chip in the window."""


def read(ctx):
    return ctx.span_ms_per_gb("codec.decode", "sample")
