"""Host time of the pruning decision per batch served in the traced
span, in ms: the block-max bound test with term elimination, and the
compaction of the surviving blocks (self time of the program's
``prune.bound`` and ``prune.compact`` spans)."""
from lib.spans import ms_per_batch


def read(ctx):
    return ms_per_batch(ctx, ("prune.bound", "prune.compact"))
