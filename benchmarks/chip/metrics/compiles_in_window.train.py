"""Executables compiled or loaded from the persistent cache inside the
window (`jax.monitoring` events)."""


def read(ctx):
    return float(ctx.counters["executables"])
