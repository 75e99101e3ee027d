"""Graphs scored per coalescer flush in the window (`ServiceStats`)."""


def read(ctx):
    c = ctx.counters
    return c["graphs_scored"] / c["flushes"] if c["flushes"] else None
