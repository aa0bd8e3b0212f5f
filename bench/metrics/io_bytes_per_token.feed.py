"""Column-file bytes opened per token made: ``ScanStats.bytes_io`` over the
tokens of every batch the counters cover (one snapshot)."""


def read(ctx):
    if not ctx.get("tokens_made"):
        return None
    return ctx["counters"]["bytes_io"] / ctx["tokens_made"]
