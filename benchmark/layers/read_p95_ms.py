"""95th percentile over all linearizable reads due in the window, from due
time.  Observed, not bounded: see ``write_p95_ms``."""


def read(ctx):
    lat = ctx.outcome.lat[ctx.READ]
    return ctx.percentile(lat, 95) * 1e3 if lat else None
