"""Mean milliseconds to cut one program above the node budget into its
segments (`repro.serve.segment` spans of the traced window, one a
segmented graph, nested in the graph's `repro.serve.encode` span)."""
import spans


def read(ctx):
    return spans.mean_ms(ctx.trace_dir, "repro.serve.segment")
