"""Programs compiled, or loaded from the persistent cache, inside the
measured serving window (JAX's monitoring events); set-up should leave
none."""


def read(ctx):
    return ctx.counters.get("programs_in_window")
