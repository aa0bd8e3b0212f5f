"""Time a batch spends reading column files whole from storage: the
program's ``storage.read`` spans, clipped to the traced window, over the
``feed.batch`` spans that end inside it."""
from bench import spans


def read(ctx):
    return spans.per_batch_ms(ctx.get("events"), "storage.read")
