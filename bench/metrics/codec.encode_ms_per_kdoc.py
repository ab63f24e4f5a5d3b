"""Host time of encoding segments into their files (``encode_segment``)
per thousand documents flushed in the traced span, in ms (self time of
the program's ``codec.encode`` span)."""
from lib.spans import ms_per_kdoc


def read(ctx):
    return ms_per_kdoc(ctx, ("codec.encode",))
