"""Mean milliseconds of one call of the service's predict function on one
pack, ending in the host copy of its scores (`repro.serve.predict` spans of
the traced window): the inside counterpart of `model_ms_per_call.serve`."""
import spans


def read(ctx):
    return spans.mean_ms(ctx.trace_dir, "repro.serve.predict")
