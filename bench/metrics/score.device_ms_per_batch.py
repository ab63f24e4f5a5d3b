"""Device time of the survivor-scoring programs (the Pallas scoring
kernels with the scatter and top-k jitted around them) per batch, from
the trace: their summed device time over the batches traced, in ms."""
from lib.readers import SCORER_PROGRAMS


def read(ctx):
    t, n = ctx.trace, ctx.counters.get("traced_batches")
    if t is None or not n:
        return None
    seconds, count = t.modules_matching(SCORER_PROGRAMS)
    if count == 0:
        return None
    return 1e3 * seconds / n
