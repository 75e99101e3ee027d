"""Host milliseconds per call of the jitted predict function, each call
ending in the host copy of its scores (the harness wraps the function)."""


def read(ctx):
    c = ctx.counters
    if not c["model_calls"]:
        return None
    return 1e3 * c["model_seconds"] / c["model_calls"]
