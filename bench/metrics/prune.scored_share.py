"""Percent of the candidate blocks that the compacted path decoded and
scored over the window: ``PruneStats.blocks_scored / blocks_candidate``
(probes and bucket padding included, as the counter counts them)."""


def read(ctx):
    c = ctx.counters
    if not c.get("blocks_candidate"):
        return None
    return 100.0 * c["blocks_scored"] / c["blocks_candidate"]
