"""Mean milliseconds a connection thread spends decoding one request: JSON
parse of the frame body and the graphs' `KernelGraph.from_dict`
(`repro.serve.decode` spans of the traced window)."""
import spans


def read(ctx):
    return spans.mean_ms(ctx.trace_dir, "repro.serve.decode")
