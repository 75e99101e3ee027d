"""Mean milliseconds to encode one pack into the model's input arrays
(`repro.serve.encode` spans of the traced window)."""
import spans


def read(ctx):
    return spans.mean_ms(ctx.trace_dir, "repro.serve.encode")
