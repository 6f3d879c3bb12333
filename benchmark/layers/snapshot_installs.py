"""InstallSnapshot messages leaders sent in the window: a follower that
fell further behind than the compacted log keeps (``compaction_overhead``
entries) is sent the whole image.  0 where every follower keeps up."""
from benchmark.layers import snapshot_plane as sp


def read(ctx):
    n = sp.count(ctx, "installs_sent")
    return None if n is None else float(n)
