"""Percent of the ingest window spent inside ``DistributedIndexer.commit``
(benchmark-side spans around each call): the flush of the group, the
segment writes and the commit's sync."""


def read(ctx):
    if not ctx.commit_s or ctx.window_s <= 0:
        return None
    return 100.0 * sum(ctx.commit_s) / ctx.window_s
