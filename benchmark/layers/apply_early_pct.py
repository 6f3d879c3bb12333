"""Share of the window's commit-carrying updates whose committed entries went
to the apply queue BEFORE the update's own persist (they were durable
already: ``Update.fast_apply``), against those that waited for its fsync:
``Tracer.apply_handoffs()`` of every live tracer, summed over the whole
seconds whose middle lies in the window; the counts go on an earlier line.
At 100 no acknowledgement waits for a WAL cycle it does not depend on.
``None`` where the program keeps no such count (an older commit, the plain
reference in its place) or no update committed anything in the window."""
import json


def read(ctx, tracers=None):
    if tracers is None:
        try:
            from dragonboat_tpu.obs import trace

            tracers = trace.live()
        except Exception:
            return None  # a program without the accessor
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    early = after = 0
    for tr in tracers:
        series = getattr(tr, "apply_handoffs", None)
        if series is None:
            return None  # a program without the counter
        for sec, (e, a) in series().items():
            if lo <= sec + 0.5 < hi:
                early += e
                after += a
    if not early + after:
        return None
    print(json.dumps({"event": "apply_handoffs", "early": early,
                      "after_sync": after}), flush=True)
    return 100.0 * early / (early + after)
