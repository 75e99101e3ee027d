"""Segments per program the service segmented in the window
(`ServiceStats.segments` over `ServiceStats.segmented_graphs`)."""


def read(ctx):
    c = ctx.counters
    if not c.get("segmented_graphs"):
        return None
    return c["segments"] / c["segmented_graphs"]
