"""Mean milliseconds of one request's pass through the service's front:
hashing, cache lookup and coalescer add (self time of the
`repro.serve.lookup` spans of the traced window: a flush that a full
coalescer starts inside the add is left out, since it is the pack,
encode and predict stages' time)."""
import spans


def read(ctx):
    return spans.mean_ms(ctx.trace_dir, "repro.serve.lookup", own=True)
