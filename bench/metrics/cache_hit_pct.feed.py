"""Share of the block cache's counted lookups that hit:
``cache_hits / (cache_hits + cache_misses)`` of the same ``ScanStats``."""


def read(ctx):
    c = ctx["counters"]
    n = c["cache_hits"] + c["cache_misses"]
    return 100.0 * c["cache_hits"] / n if n else None
