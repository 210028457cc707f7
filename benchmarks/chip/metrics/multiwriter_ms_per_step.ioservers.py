"""Milliseconds a step spends on what several writers of one array need
and one writer does not: declaring the step's arrays (``field.declare``),
acquiring chunk-range leases (``lease.acquire``) and giving the arrays up
(``field.release``), summed over the threads, per step done in the
window."""
from benchmarks.chip.stages import per_done, span_ms


def read(ctx):
    return per_done(ctx, span_ms(ctx, "field.declare", "lease.acquire",
                                 "field.release"), "step")
