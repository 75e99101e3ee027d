"""Host milliseconds per `batch(step)` of the sampler the harness hands
to `CostModelTrainer`: draw, encode and pack of one global batch."""


def read(ctx):
    c = ctx.counters
    if not c["sampler_steps"]:
        return None
    return 1e3 * c["sampler_seconds"] / c["sampler_steps"]
