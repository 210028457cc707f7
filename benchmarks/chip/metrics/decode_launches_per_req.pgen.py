"""Kernel launches of the codec's decodes (program counter
``codec.decode_launches``) per product request completed in the window;
nothing where the program does not count them."""
from benchmarks.chip.stages import per_done


def read(ctx):
    launches = ctx.counters.get("codec.decode_launches")
    if launches is None:
        return None
    return per_done(ctx, float(launches), "read")
