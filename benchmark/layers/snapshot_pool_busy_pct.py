"""Share of the snapshot pools' time spent on tasks: the busy seconds the
hosts' pools counted in the window over their workers (8 a NodeHost) times
the window.  At 100 every worker is busy all the time and a snapshot that
falls due waits or is skipped."""
from benchmark.layers import snapshot_plane as sp


def read(ctx):
    busy = sp.count(ctx, "pool_busy_s")
    workers = (sp.select(ctx)[1] or {}).get("workers")
    if busy is None or not workers:
        return None
    return 100.0 * busy / (workers * ctx.seconds)
