"""Host time of the flush's copies per thousand documents flushed in the
traced span, in ms: the tokens to the device and the inverted run back
(self time of the program's ``flush.to_device`` and ``flush.to_host``
spans)."""
from lib.spans import ms_per_kdoc


def read(ctx):
    return ms_per_kdoc(ctx, ("flush.to_device", "flush.to_host"))
