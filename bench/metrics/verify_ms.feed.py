"""Time a batch spends computing column-file CRCs: the program's
``colfile.verify`` spans, clipped to the traced window, over the
``feed.batch`` spans that end inside it."""
from bench import spans


def read(ctx):
    return spans.per_batch_ms(ctx.get("events"), "colfile.verify")
