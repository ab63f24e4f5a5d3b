"""Time the searcher's host spends launching device work and waiting for
its results, per batch served in the traced span, in ms: the metadata
pass, the phase-1 probe, the survivor scorer and the cross-segment
top-k, each with its fetch (self time of the program's ``prune.meta``,
``prune.probe``, ``score.survivors`` and ``search.merge`` spans; the
device's own work is inside)."""
from lib.spans import ms_per_batch


def read(ctx):
    return ms_per_batch(ctx, ("prune.meta", "prune.probe", "score.survivors",
                              "search.merge"))
