"""95th percentile of a request's wait from its due time to the launch
of the ``QueryScheduler.step`` that takes it, in ms (benchmark-side
timestamps, every request of the window)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.queue_ms, 95)) if ctx.queue_ms else None
