"""Median length of one engine dispatch as the host sees it (staging,
``device_put``, the program, the pull of its egress), on the profiler's
clock."""


def read(ctx):
    spans = ctx.trace["dispatch_s"] if ctx.trace else []
    return ctx.percentile(spans, 50) * 1e3 if spans else None
