"""Share of the traced window in which the scoring worker is inside a pass
(the union of `repro.serve.pass` spans), in percent."""
import spans


def read(ctx):
    return spans.union_pct(ctx.trace_dir, "repro.serve.pass")
