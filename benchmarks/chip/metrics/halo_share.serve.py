"""Halo copies the segments carried over the nodes they own, in percent,
for the programs the service segmented in the window (`ServiceStats`): the
GNN's extra rows on the segmented path."""


def read(ctx):
    c = ctx.counters
    if not c.get("owned_nodes"):
        return None
    return 100.0 * c["halo_nodes"] / c["owned_nodes"]
