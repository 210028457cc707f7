"""Gigabytes a second of product-read results delivered in the traced
window: PGEN's read bandwidth.  Its runs spread too widely for an
end-to-end bound, so it stands here, beside the tail that it moves with."""


def read(ctx):
    reads = ctx.done("read")
    if not reads or ctx.window_s <= 0:
        return None
    return sum(w.nbytes for w in reads) / ctx.window_s / 1e9
