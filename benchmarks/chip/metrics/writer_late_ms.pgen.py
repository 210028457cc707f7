"""Mean milliseconds by which the open-loop writer started its steps
after they were due: a late writer offers less write load than the cell
states."""


def read(ctx):
    steps = ctx.done("step")
    if not steps:
        return None
    return 1e3 * sum(w.start - w.due for w in steps) / len(steps)
