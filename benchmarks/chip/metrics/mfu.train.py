"""Three times the forward FLOPs of the real graphs of every step in the
window (`common.train_flops`) over the window and the chips' peak, in
percent."""


def read(ctx):
    flops = ctx.counters["flops"]
    if not flops:
        return None
    return 100.0 * flops / ctx.window_s / (ctx.peak["flops"] * ctx.chips)
