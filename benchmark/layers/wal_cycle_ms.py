"""Mean wall time of a committer cycle's log save (``save_raft_state``: the
write batches built, committed and synced), over the window: 1000 x
``commit_s`` / ``cycles`` of ``Tracer.wal_cycles()`` (the selection and the
earlier line are ``wal_syncs_per_cycle``'s).  An update waits out the rest
of the running cycle and its own, so ``stage_wal_ms.*`` is about 1.5 of it.
``None`` where the program keeps no such count or no cycle ran in the
window."""
from benchmark.layers import wal_syncs_per_cycle as wsc


def read(ctx, tracers=None):
    sums = wsc.window_sums(ctx, tracers)
    if sums is None:
        return None
    cycles, _sync_batches, _updates, commit_s = sums
    return 1000.0 * commit_s / cycles
