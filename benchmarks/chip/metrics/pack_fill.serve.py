"""Real nodes over node capacity of the packs the service scored, averaged
over packs, in percent (`BucketStats`)."""


def read(ctx):
    c = ctx.counters
    return 100.0 * c["pack_fill_sum"] / c["packs"] if c["packs"] else None
