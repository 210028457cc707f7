"""Milliseconds of ``codec.encode`` spans, summed over the I/O servers'
threads, per GB of raw field archived in the window."""


def read(ctx):
    return ctx.span_ms_per_gb("codec.encode", "step")
