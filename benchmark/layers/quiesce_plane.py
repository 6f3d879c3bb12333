"""What the program recorded about its groups' sleep, cut to the window: the
shared selection of the quiesce readers (not a metric).

A configuration that sets ``Config.quiesce`` (``idle1024x3``) lets a group
with no activity for ``10 x election_rtt`` ticks sleep on every replica: no
heartbeat, no tick message, until a request wakes it.  A program that keeps
the idle clock on the device tick plane writes, where its tracer is on:

- into every ``coord_round`` span ``rows_quiesced`` (replicas of the host
  asleep now) beside ``rows``, and ``quiesce_enters`` / ``quiesce_wakes``
  since the last recorded round;
- one ``quiesce_wake`` span a SAMPLED operation that found its group asleep
  (``dragonboat_tpu.obs.instruments.CoordObs.quiesce_wake``): ``t0`` the
  wake of the replica its step reached first, ``t1`` its commit (a write)
  or confirmation (a read), ``wake_ms``, ``op`` (``write`` / ``read``),
  ``woke`` (``leader`` / ``follower``), ``elected`` (a replica of the group
  campaigned in between);
- on the sampled request itself (``dragonboat_tpu.obs.trace.Trace.woke``)
  the same wake, so the sampled operations that found their group awake are
  counted too;
- ``scalar_ticks`` by the whole second
  (``dragonboat_tpu.obs.instruments.ReplicaObs.window``): ``LOCAL_TICK``
  messages that step workers took off replicas' queues.

A program without them (an older commit, the plain reference in its place)
leaves every reader here with ``None``.
"""
from __future__ import annotations

import json

from benchmark.layers import program_spans as ps

SPAN = "quiesce_wake"


def ring():
    """The spans of the program's ring, oldest first, or None."""
    try:
        from dragonboat_tpu import obs

        return obs.default_recorder().spans()
    except Exception:
        return None


def sampled():
    """Every finished sampled request the running NodeHosts' tracers still
    remember, or None where the program does not note a wake on them."""
    try:
        from dragonboat_tpu.obs import trace

        if "woke" not in trace.Trace.__slots__:
            return None
        return [t for tr in trace.live() for t in tr.finished()]
    except Exception:
        return None


def replicas():
    """The replica instruments of every running NodeHost, or None where
    the program does not count scalar ticks."""
    try:
        from dragonboat_tpu.obs import instruments

        if not hasattr(instruments.ReplicaObs, "scalar_ticks"):
            return None
        return instruments.replica_obs_live()
    except Exception:
        return None


def select(ctx, spans=ring, requests=sampled):
    """``(wakes, ops)``: the window's ``quiesce_wake`` spans (``t0`` in
    ``[outcome.t0, t_end)``) and the number of sampled operations submitted
    in it; ``ops`` is None where the program notes no wake.  Cached on
    ``ctx``; what was found goes on an earlier line."""
    got = getattr(ctx, "_quiesce_plane", None)
    if got is not None:
        return got
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    wakes = [s for s in (spans() or ())
             if s is not None and s.get("kind") == SPAN and lo <= s["t0"] < hi]
    found = requests()
    ops = None
    if found is not None:
        ops = sum(1 for t in found if lo <= t.t0 < hi)
    ctx._quiesce_plane = (wakes, ops)
    if wakes or ops:
        by = {}
        for s in wakes:
            key = f"{s.get('op')}:{s.get('woke')}"
            by[key] = by.get(key, 0) + 1
        print(json.dumps({
            "event": "quiesce_plane", "sampled_ops": ops,
            "wakes": len(wakes), "by_op_and_role": by,
            "elected": sum(1 for s in wakes if s.get("elected")),
            "not_completed": sum(
                1 for s in wakes if s.get("outcome") != "completed"),
        }), flush=True)
    return ctx._quiesce_plane


def median_wake_ms(ctx, op):
    """Median ``wake_ms`` of the window's wakes of ``op``; None where none."""
    vals = [s["wake_ms"] for s in select(ctx)[0]
            if s.get("op") == op and s.get("wake_ms") is not None]
    return ctx.percentile(vals, 50) if vals else None


def rounds(ctx):
    """The window's ``coord_round`` spans of a host with a quiesce group."""
    return [s for s in ps.spans(ctx, ps.ROUND)
            if s.get("rows_quiesced") is not None and s.get("rows")]
