"""Median time of the state machine's ``save`` in a periodic snapshot: a
regular state machine holds the group's applies out for as long, and the
wait for that lock is in it (``sm_save_ms`` of the window's
``snapshot_save`` spans)."""
from benchmark.layers import snapshot_plane as sp


def read(ctx):
    return sp.median(ctx, "sm_save_ms")
