"""Median over the window's steps of how far apart the I/O servers'
barriers fell: the end of the last writer's ``session.commit`` span less
the first's, in milliseconds.  A step's commits are those that end within
its ``step`` work record."""
import statistics


def read(ctx):
    ends = sorted(s.t1_ns / 1e9 for s in ctx.spans
                  if s.name == "session.commit" and s.t1_ns is not None)
    skews = []
    for w in ctx.done("step"):
        inside = [t for t in ends if w.start <= t <= w.done]
        if len(inside) >= 2:
            skews.append(1e3 * (inside[-1] - inside[0]))
    return statistics.median(skews) if skews else None
