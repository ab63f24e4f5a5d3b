"""Segments the searcher evaluated per batch over the window:
``PruneStats.segments_visited / batches``."""


def read(ctx):
    c = ctx.counters
    if not c.get("batches"):
        return None
    return c["segments_visited"] / c["batches"]
