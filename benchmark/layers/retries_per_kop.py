"""Attempts beyond the first per thousand operations of the window: the
load feeding itself through timeouts would show here first."""


def read(ctx):
    o = ctx.outcome
    return 1000.0 * o.retries / o.attempted if o.attempted else None
