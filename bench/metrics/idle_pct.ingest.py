"""Device idle share of the traced ingest window, in %."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
