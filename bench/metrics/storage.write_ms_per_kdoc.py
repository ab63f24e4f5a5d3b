"""Host time of the directory's file writes per thousand documents
flushed in the traced span, in ms (self time of the program's
``directory.write`` span)."""
from lib.spans import ms_per_kdoc


def read(ctx):
    return ms_per_kdoc(ctx, ("directory.write",))
