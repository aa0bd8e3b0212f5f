"""Time a batch spends in the per-chip decode: the program's
``decode.chips`` spans (``chips_decode_batch``: the packed words on the host
to the batch's ids ready on every chip), clipped to the traced window, over
the ``feed.batch`` spans that end inside it. A program that decodes on one
device has no such span, and reads nothing."""
from bench import spans

SPAN = "decode.chips"


def read(ctx):
    events = ctx.get("events")
    if not events or not spans.named(events, SPAN):
        return None
    return spans.per_batch_ms(events, SPAN)
