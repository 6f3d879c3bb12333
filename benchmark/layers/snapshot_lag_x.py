"""How far behind its cadence a periodic save starts: the median, over the
window's ``snapshot_save`` spans, of the entries applied since the previous
snapshot when a pool worker took the task (``entries_since``) over the
group's ``snapshot_entries``.  1.0 when the cadence holds; above it the pool,
not the setting, sets how often a group snapshots."""
from benchmark.layers import snapshot_plane as sp


def read(ctx):
    vals = [s["entries_since"] / s["snapshot_entries"]
            for s in sp.select(ctx)[0]
            if s.get("snapshot_entries") and s.get("entries_since") is not None]
    return ctx.percentile(vals, 50) if vals else None
