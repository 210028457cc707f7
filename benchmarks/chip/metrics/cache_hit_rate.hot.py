"""Percent of chunk lookups in the window that the decoded-chunk cache
served (program counters ``cache.hits`` and ``cache.misses``)."""


def read(ctx):
    return ctx.hit_rate()
