"""How late the generator ran, 95th percentile: open loop, submission after
the due time; closed loop, the next submission after the answer that freed
its caller.  A starved generator must not read as a fast server."""


def read(ctx):
    late = ctx.outcome.late
    return ctx.percentile(late, 95) * 1e3 if late else None
