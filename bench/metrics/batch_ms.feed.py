"""Mean time the feed takes to make one batch: the program's ``feed.batch``
spans (``HostPipeline._make_batch``, split reopen to assembled arrays) that
end inside the traced window."""
from bench import spans


def read(ctx):
    return spans.batch_ms(ctx.get("events"))
