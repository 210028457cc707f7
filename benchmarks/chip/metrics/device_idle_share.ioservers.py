"""Percent of the traced window in which no operation ran on the chip."""


def read(ctx):
    return ctx.idle_share()
