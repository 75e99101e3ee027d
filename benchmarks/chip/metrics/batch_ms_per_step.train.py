"""Mean milliseconds of the trainer's `sampler.batch(step)` call
(`repro.train.batch` spans of the traced window): the inside counterpart
of `sampler_ms_per_step.train`."""
import spans


def read(ctx):
    return spans.mean_ms(ctx.trace_dir, "repro.train.batch")
