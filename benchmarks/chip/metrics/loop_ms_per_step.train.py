"""Mean milliseconds of one trainer loop iteration outside its batch draw:
`repro.train.step` less the `repro.train.batch` inside it, over the
traced window's steps."""
import spans


def read(ctx):
    return spans.outside_ms(ctx.trace_dir, "repro.train.step",
                            "repro.train.batch")
