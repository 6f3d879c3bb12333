"""Median time from the wake of a sleeping group to the confirmation of the
READ that woke it (``wake_ms`` of the window's ``quiesce_wake`` spans with
``op`` ``read``): at a follower's host the follower's wake and the forward,
the leader's wake, its hinted heartbeat, the echoes (which wake the other
follower) and the confirming round.  ``None`` where the program has no such
span or no sampled read found its group asleep."""
from benchmark.layers import quiesce_plane as qp


def read(ctx):
    return qp.median_wake_ms(ctx, "read")
