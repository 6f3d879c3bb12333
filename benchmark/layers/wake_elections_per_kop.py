"""Sampled operations whose wake started an election (``elected`` of their
``quiesce_wake`` span: a replica of the group campaigned between the wake
and the operation's commit or confirmation) per thousand sampled operations
of the window.  Must be 0 while leaders live: a woken follower's election
clock starts from the wake.  ``None`` where the program notes no wake."""
from benchmark.layers import quiesce_plane as qp


def read(ctx):
    wakes, ops = qp.select(ctx)
    if not ops:
        return None
    return 1000.0 * sum(1 for s in wakes if s.get("elected")) / ops
