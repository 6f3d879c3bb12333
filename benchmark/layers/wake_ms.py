"""Median time from the wake of a sleeping group to the commit of the WRITE
that woke it (``wake_ms`` of the window's ``quiesce_wake`` spans with ``op``
``write``): the leader's wake, the replication round that wakes the
followers, their acknowledgements and the device round that commits.
``None`` where the program has no such span or no sampled write found its
group asleep."""
from benchmark.layers import quiesce_plane as qp


def read(ctx):
    return qp.median_wake_ms(ctx, "write")
