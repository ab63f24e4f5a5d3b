"""Host time of planning a pruned search per batch served in the traced
span, in ms: global idf, each segment's score bound and the visit order
(self time of the program's ``search.plan`` span)."""
from lib.spans import ms_per_batch


def read(ctx):
    return ms_per_batch(ctx, ("search.plan",))
