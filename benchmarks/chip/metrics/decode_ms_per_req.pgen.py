"""Milliseconds of ``codec.decode`` spans per product request completed in
the window."""


def read(ctx):
    reads = ctx.done("read")
    if not reads or not ctx.spans:
        return None
    return ctx.span_ms("codec.decode") / len(reads)
