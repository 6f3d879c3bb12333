"""What the program recorded about its snapshots, compactions and
check-quorum windows, cut to the window: the shared selection of the
``snapshot_*`` readers (not a metric).

A replica whose group sets ``snapshot_entries`` saves a snapshot on its
NodeHost's snapshot pool and compacts the log behind it.  A program that
has the replica instruments (``dragonboat_tpu.obs.instruments.ReplicaObs``,
attached whenever a NodeHost's tracer is on) writes one ``snapshot_save``
span a save into the ring the round spans are in, with ``queue_ms`` /
``sm_save_ms`` / ``commit_ms`` / ``compact_ms`` / ``save_ms``,
``image_bytes``, ``entries_since`` beside the group's ``snapshot_entries``,
``save_kind`` and ``saved``; and it counts saves, refused saves,
compactions, InstallSnapshot messages, the pool's busy seconds and the
check-quorum windows by the whole second, whatever the ring still holds.
A program without them (an older commit, the plain reference in its place),
or a run in which no group snapshots, leaves every reader with ``None``.
"""
from __future__ import annotations

import json

SPAN = "snapshot_save"
#: the annotations of a save, as ``reduce.py``'s ``idle_gaps`` key them
GAPS = ("host:dbtpu:snapshot_save", "host:dbtpu:compact")


def ring():
    """The spans of the program's ring, oldest first, or None."""
    try:
        from dragonboat_tpu import obs

        return obs.default_recorder().spans()
    except Exception:
        return None


def live():
    """The replica instruments of every running NodeHost, or None where
    the program has none."""
    try:
        from dragonboat_tpu.obs import instruments

        return instruments.replica_obs_live()
    except Exception:
        return None


def select(ctx, spans=ring, planes=live):
    """``(saves, counts)``: the window's committed periodic saves (spans
    whose ``t0`` lies in ``[outcome.t0, t_end)``) and the counters' sums
    over the whole seconds of the window with ``workers``, the pool
    workers of all hosts; ``counts`` is None where the program has no
    counters.  Cached on ``ctx``; what was found goes on an earlier line."""
    got = getattr(ctx, "_snapshot_plane", None)
    if got is not None:
        return got
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    saves = [s for s in (spans() or ())
             if s is not None and s.get("kind") == SPAN and s.get("saved")
             and s.get("save_kind") == "periodic" and lo <= s["t0"] < hi]
    counts = None
    found = planes()
    if found:
        counts = {"workers": sum(p.workers for p in found)}
        for p in found:
            for name, n in p.window(lo, hi).items():
                counts[name] = counts.get(name, 0) + n
    ctx._snapshot_plane = (saves, counts)
    if saves or (counts and counts.get("saves")):
        line = {"event": "snapshot_plane", "spans_in_ring": len(saves)}
        if counts:
            line.update({k: round(v, 3) for k, v in sorted(counts.items())})
            line["saves_per_s"] = round(
                counts.get("saves", 0) / ctx.seconds, 2)
        sizes = sorted(s["image_bytes"] for s in saves if "image_bytes" in s)
        if sizes:
            line["image_bytes_p50_max"] = [sizes[len(sizes) // 2], sizes[-1]]
        trace = getattr(ctx, "trace", None)
        if trace and trace.get("idle_gaps"):
            gaps = dict(map(tuple, trace["idle_gaps"]))
            line["idle_gaps_s"] = {
                g: round(gaps[g], 4) for g in GAPS if g in gaps}
        print(json.dumps(line), flush=True)
    return ctx._snapshot_plane


def median(ctx, field):
    """Median of ``field`` over the window's committed periodic saves;
    None where none carries it."""
    vals = [s[field] for s in select(ctx)[0] if s.get(field) is not None]
    return ctx.percentile(vals, 50) if vals else None


def count(ctx, name):
    """The window's count of ``name``, or None where the program has no
    counters or no replica saved a snapshot and none was installed."""
    counts = select(ctx)[1]
    if not counts or not (counts.get("saves") or counts.get("installs_sent")
                          or counts.get("installs_received")):
        return None
    return counts.get(name, 0)
