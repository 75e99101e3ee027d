"""Device milliseconds per step of the jitted train-step executable
(`mesh_step`), from the trace's executable line on device 0, over the
steps of the traced part of the window."""


def read(ctx):
    secs = sum(v for k, v in ctx.trace["module_s"].items()
               if "mesh_step" in k)
    steps = ctx.counters["traced_steps"]
    if not secs or not steps:
        return None
    return 1e3 * secs / steps
