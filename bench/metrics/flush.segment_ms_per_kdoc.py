"""Host time of building the flushed segment from the inverted run
(``segment_from_run``) per thousand documents flushed in the traced
span, in ms (self time of the program's ``flush.segment`` span)."""
from lib.spans import ms_per_kdoc


def read(ctx):
    return ms_per_kdoc(ctx, ("flush.segment",))
