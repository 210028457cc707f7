"""Milliseconds of ``codec.encode`` spans per GB of raw field archived in
the window: the host side of encoding (stacking, transfers, containers)."""


def read(ctx):
    return ctx.span_ms_per_gb("codec.encode", "step")
