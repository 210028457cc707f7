"""Milliseconds of ``plan.assemble`` spans (chunks copied into the answer,
and into the cache) per product request completed in the window."""
from benchmarks.chip.stages import per_done, span_ms


def read(ctx):
    return per_done(ctx, span_ms(ctx, "plan.assemble"), "read")
