"""Prediction-cache hits over lookups in the window, in percent
(`ServiceStats.cache` of the measured service, read at the close)."""


def read(ctx):
    c = ctx.counters
    looked = c["hits"] + c["misses"]
    return 100.0 * c["hits"] / looked if looked else None
