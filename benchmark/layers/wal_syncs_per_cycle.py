"""Durable write batches a committer cycle's log save committed, over the
window: ``Tracer.wal_cycles()`` of every live tracer, summed over the whole
seconds whose middle lies in the window; the sums go on an earlier line
``wal_cycles``.  1 where a step worker's groups live in one LogDB shard (a
cycle is one ``fdatasync``, one hand-off of the interpreter); with more
shards than workers it is the shards a cycle touches, paid one after the
other.  ``None`` where the program keeps no such count (an older commit, the
plain reference in its place) or no cycle ran in the window."""
import json


def window_sums(ctx, tracers=None):
    """``(cycles, sync_batches, updates, commit_s)`` of the window, or
    ``None``."""
    if tracers is None:
        try:
            from dragonboat_tpu.obs import trace

            tracers = trace.live()
        except Exception:
            return None  # a program without the accessor
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    sums = [0, 0, 0, 0.0]
    for tr in tracers:
        series = getattr(tr, "wal_cycles", None)
        if series is None:
            return None  # a program without the counter
        for sec, counts in series().items():
            if lo <= sec + 0.5 < hi:
                for i, c in enumerate(counts):
                    sums[i] += c
    return tuple(sums) if sums[0] else None


def read(ctx, tracers=None):
    sums = window_sums(ctx, tracers)
    if sums is None:
        return None
    cycles, sync_batches, updates, commit_s = sums
    print(json.dumps({"event": "wal_cycles", "cycles": cycles,
                      "sync_batches": sync_batches, "updates": updates,
                      "commit_s": round(commit_s, 4)}), flush=True)
    return sync_batches / cycles
