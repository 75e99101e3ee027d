"""Mean milliseconds a request waits in the server's admission queue, from
admission to the scoring worker taking it (`repro.serve.queue_wait` spans
of the traced window)."""
import spans


def read(ctx):
    return spans.mean_ms(ctx.trace_dir, "repro.serve.queue_wait")
