"""Median wait of a periodic save from the update that found it due to the
snapshot-pool worker that took it: the apply worker's hand-over and the
pool's queue (``queue_ms`` of the window's ``snapshot_save`` spans)."""
from benchmark.layers import snapshot_plane as sp


def read(ctx):
    return sp.median(ctx, "queue_ms")
