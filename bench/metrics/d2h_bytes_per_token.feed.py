"""Bytes copied from the chips back to the host per token: the ``bytes`` of
the program's ``decode.d2h`` spans (decoded ids) that end inside the traced
window, over the tokens of the ``feed.batch`` spans that end inside it. A
feed that keeps its batch on the chips has no such span, and reads nothing."""
from bench import spans


def read(ctx):
    return spans.bytes_per_token(ctx.get("events"), ("decode.d2h",), ctx["batch_tokens"])
