"""Acknowledgements per engine dispatch while the profiler ran: how much a
device round batches across groups.  Dispatches are the benchmark's own
spans around ``eng.step`` / ``eng.step_rounds`` of every host."""


def read(ctx):
    n = len(ctx.trace["dispatch_s"]) if ctx.trace else 0
    return ctx.acks_in_trace / n if n else None
