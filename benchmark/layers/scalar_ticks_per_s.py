"""``LOCAL_TICK`` messages a second that step workers took off replicas'
queues, all hosts, over the whole seconds of the window: what the replicas
whose raft clock the host ticks cost it (one message and one step-worker
turn a replica a ``rtt_millisecond``: 30,720 a second for 3,072 replicas at
100 ms).  0 where every replica's clock is the device tick kernel's.
``None`` where the program does not count them."""
from benchmark.layers import quiesce_plane as qp


def read(ctx):
    found = qp.replicas()
    if not found:
        return None
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    n = sum(p.window(lo, hi).get("scalar_ticks", 0) for p in found)
    return n / ctx.seconds
