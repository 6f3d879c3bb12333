"""What the program recorded about itself, cut to the window: the shared
selection of the span readers (not a metric).

The program's coordinator and engine write their spans into one ring,
``dragonboat_tpu.obs.default_recorder()``, whenever a NodeHost's tracer is
on (a traced run).  A span is an interval on ``time.perf_counter()``, the
clock of the generator's window, with the NodeHost it belongs to
(``host``): ``coord_round`` spans carry ``wait_ms`` / ``drain_ms`` /
``fanout_ms`` and the round's read-echo counts, ``dispatch`` / ``fused``
spans (one per ``eng.step`` / ``eng.step_rounds``) carry ``row_sync_ms`` /
``stage_ms`` / ``transfer_ms`` / ``launch_ms`` / ``egress_wait_ms`` /
``decode_ms``.  A program without them (an older commit, the plain
reference in its place) leaves every reader here with ``None``.
"""
from __future__ import annotations

import json

ROUND = ("coord_round",)
DISPATCH = ("dispatch", "fused")
#: the field a span of each kind carries once it is final (a round still
#: running, a dispatch whose egress is still in flight, is not read)
FINAL = {"coord_round": "wall_ms", "dispatch": "egress_ms",
         "fused": "egress_ms"}


def ring():
    """(spans oldest first, records ever written, capacity) of the
    program's ring, or None where the program has none."""
    try:
        from dragonboat_tpu import obs

        rec = obs.default_recorder()
        return rec.spans(), rec.to_json(limit=1)["count"], rec.capacity
    except Exception:
        return None


def select(ctx, source=ring):
    """The finished spans whose ``t0`` lies in ``[outcome.t0, t_end)``, by
    kind (``FINAL``); ``{}`` where there are none.  If the ring has wrapped past the
    window's start, what it still covers is read and an earlier line says
    so.  Cached on ``ctx``: every span reader of a run sees one
    selection."""
    got = getattr(ctx, "_program_spans", None)
    if got is not None:
        return got
    by_kind = {}
    found = source()
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    if found is not None:
        spans, written, capacity = found
        spans = [s for s in spans if s is not None and "t0" in s]
        if spans and written > capacity and spans[0]["t0"] > lo:
            print(json.dumps({
                "event": "span_ring_wrapped", "capacity": capacity,
                "written": written,
                "covered_from_s": round(spans[0]["t0"] - lo, 3),
                "window_s": round(hi - lo, 3)}), flush=True)
        for s in spans:
            if lo <= s["t0"] < hi and s.get(FINAL.get(s["kind"])) is not None:
                by_kind.setdefault(s["kind"], []).append(s)
    ctx._program_spans = by_kind
    return by_kind


def spans(ctx, kinds):
    """The window's finished spans of ``kinds``."""
    by_kind = select(ctx)
    return [s for k in kinds for s in by_kind.get(k, ())]


def median_ms(ctx, kinds, *fields):
    """Median over the window's spans of ``kinds`` of the sum of their
    ``fields`` (milliseconds); None where no span carries them."""
    vals = [sum(s[f] for f in fields) for s in spans(ctx, kinds)
            if all(s.get(f) is not None for f in fields)]
    return ctx.percentile(vals, 50) if vals else None


def total(ctx, kinds, field):
    return sum(s.get(field) or 0 for s in spans(ctx, kinds))
