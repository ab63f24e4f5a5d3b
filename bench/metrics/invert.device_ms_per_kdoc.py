"""Device time of the jitted inversion (``invert_shard``) per thousand
documents flushed in the traced span, in ms."""
from lib.readers import INVERT_PROGRAMS


def read(ctx):
    t, docs = ctx.trace, ctx.counters.get("traced_docs")
    if t is None or not docs:
        return None
    seconds, count = t.modules_matching(INVERT_PROGRAMS)
    if count == 0:
        return None
    return 1e3 * seconds / (docs / 1e3)
