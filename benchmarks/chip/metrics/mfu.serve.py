"""The forward FLOPs of the graphs the model computed in the window (cache
hits and padding not counted; `common.forward_flops`) over the window
and the chips' peak, in percent."""


def read(ctx):
    flops = ctx.counters["model_flops"]
    if not flops:
        return None
    return 100.0 * flops / ctx.window_s / (ctx.peak["flops"] * ctx.chips)
