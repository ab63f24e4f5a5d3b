"""Host time of the directory's durability barriers (fsync) per thousand
documents flushed in the traced span, in ms (self time of the program's
``directory.sync`` span)."""
from lib.spans import ms_per_kdoc


def read(ctx):
    return ms_per_kdoc(ctx, ("directory.sync",))
