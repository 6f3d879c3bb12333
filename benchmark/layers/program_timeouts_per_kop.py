"""``read`` attempts the program itself completed ``TIMEOUT`` inside the
window per thousand read attempts it completed there: the inside view of
the lost forwarded answers (``Tracer.outcomes()`` of every live tracer;
attempts are counted by the whole seconds whose middle lies in the
window, so the count is good to about a second in 48)."""


def read(ctx, tracers=None):
    if tracers is None:
        try:
            from dragonboat_tpu.obs import trace

            tracers = trace.live()
        except Exception:
            return None  # a program without the accessor
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    timeouts = attempts = 0
    for tr in tracers:
        out = tr.outcomes()
        timeouts += sum(1 for t, kind, code in out["events"]
                        if kind == "read" and code == "TIMEOUT"
                        and lo <= t < hi)
        attempts += sum(n for sec, counts in out["by_second"].items()
                        if lo <= sec + 0.5 < hi
                        for (kind, _code), n in counts.items()
                        if kind == "read")
    return 1000.0 * timeouts / attempts if attempts else None
