"""Median time of the compaction behind a periodic save: the log reader's
and the LogDB's entries up to ``compaction_overhead`` behind the snapshot,
and the snapshots older than the newest three (``compact_ms`` of the
window's ``snapshot_save`` spans; ``dbtpu:compact`` in the idle gaps)."""
from benchmark.layers import snapshot_plane as sp


def read(ctx):
    return sp.median(ctx, "compact_ms")
