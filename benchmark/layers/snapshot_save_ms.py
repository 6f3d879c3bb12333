"""Median time a snapshot-pool worker spent on one periodic save: the state
machine's ``save``, the commit of the image and the log and snapshot
compaction behind it (``save_ms`` of the window's ``snapshot_save`` spans).
``None`` where the program has no such span or no replica snapshots."""
from benchmark.layers import snapshot_plane as sp


def read(ctx):
    return sp.median(ctx, "save_ms")
