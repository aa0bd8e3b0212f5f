"""Bytes copied from the host to the chips per token: the ``bytes`` of the
program's ``decode.dispatch`` spans (packed words and the padded dictionary
table) and of the loop's ``bench.place_batch`` spans (the batch's host
arrays), each that ends inside the traced window, over the tokens of the
``feed.batch`` spans that end inside it."""
from bench import spans


def read(ctx):
    return spans.bytes_per_token(ctx.get("events"), ("decode.dispatch", "bench.place_batch"),
                                 ctx["batch_tokens"])
