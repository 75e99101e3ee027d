"""Device milliseconds per step in collective operations on device 0 (the
mesh step's all-reduce of loss and gradients), from the trace's op line,
over the steps of the traced part of the window."""


def read(ctx):
    secs = ctx.trace["collective_s"]
    steps = ctx.counters["traced_steps"]
    if not secs or not steps:
        return None
    return 1e3 * secs / steps
