"""Bytes the ingest window wrote to the target directory
(``FSDirectory.bytes_written``) per user byte, a user byte being 4 per
non-pad token fed."""


def read(ctx):
    c = ctx.counters
    if not c.get("tokens"):
        return None
    return c["bytes_written"] / (4.0 * c["tokens"])
