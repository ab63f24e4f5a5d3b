"""Percent of the scheduler's slots filled per step over the window:
served / steps / slots (the scheduler's own counters)."""


def read(ctx):
    c = ctx.counters
    if not c.get("steps"):
        return None
    return 100.0 * c["served"] / c["steps"] / c["slots"]
