"""Mean milliseconds of the trainer's train-step call: the transfer of the
numpy batch and the launch (`repro.train.dispatch` spans of the traced
window)."""
import spans


def read(ctx):
    return spans.mean_ms(ctx.trace_dir, "repro.train.dispatch")
