"""Percent of the traced span of an ingest window in which no operation
ran on the device."""
from lib.readers import idle_share


def read(ctx):
    return idle_share(ctx)
