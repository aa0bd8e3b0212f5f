"""Share of the traced window in which no operation ran on the device:
1 - (union of the device operations' intervals) / window."""


def read(ctx):
    if ctx.get("events") is None or not ctx["window_s"] > 0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
