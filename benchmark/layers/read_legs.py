"""Where a linearizable read spends its time, leg by leg, cut to the window:
the shared selection of the ``read_*_ms.read`` readers (not a metric).

A program that follows its sampled ReadIndex contexts writes, at the leader
that confirmed one, a ``read_ctx`` span into the ring the round spans are in
(``dragonboat_tpu.obs.instruments.CoordObs.read_ctx``): ``t0`` the instant
the leader's step accepted the context, ``t1`` the instant it answered the
requesters, and between them the chain ``echo_trip_ms`` (the hinted
heartbeat to a follower and its echo back, until the echo that completes
the quorum is staged) + ``echo_wait_ms`` (that echo waiting for the round
thread) + ``confirm_ms`` (the drain's rest, the device step, its egress, to
the fan-out) + ``release_ms`` (the wake and the step worker's turn that
answers), which add up to ``leader_ms``; ``rounds``, ``origin``, ``path``,
and the context ``(cluster_id, low, high)``.  The requester's sampled
``Trace`` (``dragonboat_tpu.obs.trace``) carries the same context
(``read_ctx``) and ``read_origin`` (``local`` / ``forwarded``) beside its
stamps ``propose`` / ``ingress`` / ``raft_step`` / ``read_confirm`` /
``apply`` / ``egress``.  One process, one ``perf_counter``: the two legs
that cross hosts (``forward``: the requester's ``raft_step`` to the
leader's accept; ``answer``: the leader's release to the requester's
``read_confirm``) are real intervals here.

Joined by the context, a read's latency from ``propose`` to ``egress`` is
the sum of its legs; the earlier line ``read_legs`` gives the MEAN of each
by origin and path (means add up, medians do not) and ``unnamed_pct``, the
share of a joined forwarded read's mean latency that no leg covers (a leg
whose ends were not both seen counts as nothing).  A program without the
span (an older commit, the plain reference in its place) leaves every
reader here with ``None``.
"""
from __future__ import annotations

import json

SPAN = "read_ctx"
#: the leader's chain, in order; their sum is ``leader_ms``
CHAIN = ("echo_trip_ms", "echo_wait_ms", "confirm_ms", "release_ms")
#: a joined read's legs in order, requester - leader - requester
LEGS = ("ingress_ms", "submit_wait_ms", "forward_ms") + CHAIN + (
    "answer_ms", "finish_ms")


def ring():
    """The spans of the program's ring, oldest first, or None."""
    try:
        from dragonboat_tpu import obs

        return obs.default_recorder().spans()
    except Exception:
        return None


def sampled():
    """Every finished sampled request that the tracers of the running
    NodeHosts still remember (``Tracer.finished``: a window's worth; an
    older program's ``traces``, its newest 256), or None where the program
    has none."""
    try:
        from dragonboat_tpu.obs import trace

        return [t for tr in trace.live()
                for t in getattr(tr, "finished", tr.traces)()]
    except Exception:
        return None


def _stamps(t) -> dict:
    """stage -> the instant of its (last) stamp."""
    return {stage: ts for stage, ts, _thread in t.events}


def _legs(t, span) -> dict:
    """The legs of one finished read (milliseconds); the leader's and the
    two that cross hosts only where ``span`` is its context's."""
    at = _stamps(t)
    out = {}

    def leg(name, a, b):
        if a is not None and b is not None:
            out[name] = (b - a) * 1e3

    leg("ingress_ms", t.t0, at.get("ingress"))
    leg("submit_wait_ms", at.get("ingress"), at.get("raft_step"))
    leg("finish_ms", at.get("read_confirm"), at.get("egress"))
    leg("whole_ms", t.t0, at.get("egress"))
    if span is not None and span.get("leader_ms") is not None:
        leg("forward_ms", at.get("raft_step"), span["t0"])
        leg("answer_ms", span["t1"], at.get("read_confirm"))
        for name in CHAIN + ("leader_ms",):
            if span.get(name) is not None:
                out[name] = span[name]
    return out


def _mean(vals):
    return round(sum(vals) / len(vals), 4) if vals else None


def select(ctx, spans=ring, traces=sampled):
    """``{"spans": [...], "reads": [...]}``: the window's ``read_ctx``
    spans that reached their release (``t0`` in ``[outcome.t0, t_end)``),
    and one ``(origin, path, legs)`` for every sampled read that completed
    and was submitted inside the window (``path`` None where no span was
    joined).  Cached on ``ctx``; what was found goes on an earlier line."""
    got = getattr(ctx, "_read_legs", None)
    if got is not None:
        return got
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    by_ctx = {}
    window = []
    for s in spans() or ():
        if s is None or s.get("kind") != SPAN:
            continue
        by_ctx[(s.get("cluster_id"), s.get("low"), s.get("high"))] = s
        if lo <= s["t0"] < hi and s.get("leader_ms") is not None:
            window.append(s)
    reads = []
    for t in traces() or ():
        if (getattr(t, "kind", None) != "read" or not t.done
                or t.outcome != "completed" or not lo <= t.t0 < hi):
            continue
        key = getattr(t, "read_ctx", None)
        if not key:
            continue  # a program that does not follow its contexts
        span = by_ctx.get((t.cluster_id,) + tuple(key))
        if span is not None and span.get("leader_ms") is None:
            span = None  # dropped at that leader: answered by a retry
        reads.append((getattr(t, "read_origin", None),
                      span.get("path") if span is not None else None,
                      _legs(t, span)))
    ctx._read_legs = got = {"spans": window, "reads": reads}
    if window or any(path for _o, path, _l in reads):
        print(json.dumps(_line(ctx, window, reads)), flush=True)
    return got


def _line(ctx, window, reads) -> dict:
    line = {"event": "read_legs", "spans_in_window": len(window),
            "sampled_reads": len(reads)}
    groups = {}
    for origin, path, legs in reads:
        if path is not None:
            groups.setdefault(f"{origin}/{path}", []).append(legs)
    for name, rows in sorted(groups.items()):
        line[name] = dict(
            {"n": len(rows)},
            **{k: _mean([r[k] for r in rows if k in r])
               for k in LEGS + ("leader_ms", "whole_ms")
               if any(k in r for r in rows)})
    for origin in ("local", "forwarded"):
        mine = [(path, legs) for o, path, legs in reads if o == origin]
        if mine:
            line[f"joined_pct_{origin}"] = round(
                100.0 * sum(1 for path, _l in mine if path) / len(mine), 2)
    fwd = [legs for o, path, legs in reads
           if o == "forwarded" and path is not None and "whole_ms" in legs]
    if fwd:
        whole = sum(r["whole_ms"] for r in fwd)
        named = sum(r.get(k, 0.0) for r in fwd for k in LEGS)
        line["unnamed_pct"] = round(100.0 * (whole - named) / whole, 2)
        line["chain_complete_pct"] = round(
            100.0 * sum(1 for r in fwd if all(k in r for k in CHAIN))
            / len(fwd), 2)
    for field in ("stage_wait_ms", "first_echo_ms"):
        vals = [s[field] for s in window if s.get(field) is not None]
        if vals:
            line[field + "_p50"] = round(ctx.percentile(vals, 50), 4)
    paths = {}
    for s in window:
        paths[s.get("path")] = paths.get(s.get("path"), 0) + 1
    line["paths"] = paths
    lat = getattr(ctx.outcome, "lat", None)
    if lat and lat.get(ctx.READ):
        line["generator_read_mean_ms"] = _mean(
            [v * 1e3 for v in lat[ctx.READ]])
    return line


def span_median(ctx, field):
    """Median of ``field`` over the window's released ``read_ctx`` spans;
    None where none carries it."""
    vals = [s[field] for s in select(ctx)["spans"]
            if s.get(field) is not None]
    return ctx.percentile(vals, 50) if vals else None


def leg_median(ctx, leg, origin=None, joined=False):
    """Median of ``leg`` over the window's sampled reads (of ``origin``
    where given; those a span was joined to where ``joined``); None where
    none has it."""
    vals = [legs[leg] for o, path, legs in select(ctx)["reads"]
            if leg in legs and (origin is None or o == origin)
            and (path is not None or not joined)]
    return ctx.percentile(vals, 50) if vals else None
