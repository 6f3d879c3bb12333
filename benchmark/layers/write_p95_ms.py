"""95th percentile over all writes of the window, from due time (open loop)
or first submission (closed loop) to the acknowledgement, retries included;
a failed write counts as the deadline.  Where a leader change or a stall
shows.  A tail beside the bounded medians: its run-to-run spread is too
wide for a bound (PERF.md), so it is observed."""


def read(ctx):
    lat = ctx.outcome.lat[ctx.WRITE]
    return ctx.percentile(lat, 95) * 1e3 if lat else None
