"""Host time of the merge policy's byte model of each flushed segment
(``MergeDriver.add_flush``: the segment's modelled index bytes) per
thousand documents flushed in the traced span, in ms (self time of the
program's ``flush.account`` span)."""
from lib.spans import ms_per_kdoc


def read(ctx):
    return ms_per_kdoc(ctx, ("flush.account",))
