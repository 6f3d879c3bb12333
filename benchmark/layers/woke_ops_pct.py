"""Share of the window's sampled operations that found their group asleep
(one ``quiesce_wake`` span each) among all sampled operations submitted in
the window.  A descriptor of the traffic, not a lever (about
``exp(-threshold / mean gap)``): its ``better`` means nothing.  ``None``
where the program notes no wake."""
from benchmark.layers import quiesce_plane as qp


def read(ctx):
    wakes, ops = qp.select(ctx)
    return 100.0 * len(wakes) / ops if ops else None
