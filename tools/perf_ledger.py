#!/usr/bin/env python
"""Regenerate the PERF.md A/B ledger tables from BENCH_DETAIL.json.

Tables in PERF.md must be traceable to a bench artifact — hand-transcribed
tables drifted from the record they cited.  This
script derives the ledger tables MECHANICALLY from the bench artifact and
splices them between the ``perf-ledger`` markers in PERF.md, so the table
and the artifact cannot disagree.  Run it at capture time
(``make perf-ledger``); it exits nonzero when the markers are missing or
the artifact is unreadable, and touches nothing outside the marker block.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BEGIN = "<!-- perf-ledger:begin (generated by tools/perf_ledger.py) -->"
END = "<!-- perf-ledger:end -->"


def _fmt(v, unit="", scale=1.0, nd=1):
    if not isinstance(v, (int, float)):
        return "—"
    return f"{v / scale:,.{nd}f}{unit}"


def _lat(d):
    lat = d.get("commit_latency_ms")
    if not isinstance(lat, dict):
        return "—"
    return f"p50 {_fmt(lat.get('p50'))} / p99 {_fmt(lat.get('p99'))} ms"


def _ejects(d):
    fl = d.get("fastlane")
    if isinstance(fl, dict) and "ejects" in fl:
        return str(fl["ejects"])
    if isinstance(fl, list):  # full per-rank stats: sum the eject maps
        return str(sum(
            sum((r.get("eject_reasons") or {}).values())
            for r in fl if isinstance(r, dict)
        ))
    return "—"


def _e2e_row(label, d):
    if not isinstance(d, dict) or d.get("error"):
        err = (d or {}).get("error", "missing") if isinstance(d, dict) else "missing"
        return f"| {label} | — | — | — | — | error: {str(err)[:40]} |"
    return (
        f"| {label} | {_fmt(d.get('groups'), nd=0)} "
        f"| {_fmt(d.get('writes_per_sec'), nd=1)} | {_lat(d)} "
        f"| {_ejects(d)} | {d.get('sm', '—')}/{d.get('engine', '—')} |"
    )


def _ladder_row(label, d, note):
    if not isinstance(d, dict) or d.get("error"):
        err = (d or {}).get("error", "missing") if isinstance(d, dict) else "missing"
        return f"| {label} | — | — | — | — | error: {str(err)[:60]} |"
    wps = d.get("writes_per_sec")
    k = d.get("rounds_per_dispatch")
    extra = []
    mixed = d.get("mixed")
    if isinstance(mixed, dict):
        if mixed.get("read_ratio"):
            extra.append(f"{mixed['read_ratio']}:1 mixed")
        if mixed.get("read_dispatch_p99_ms") is not None:
            extra.append(
                f"read-dispatch p99 {_fmt(mixed['read_dispatch_p99_ms'])} ms"
            )
    if d.get("probe_reads_per_sec"):
        extra.append(f"{_fmt(d['probe_reads_per_sec'], nd=0)} probe reads/s")
    if d.get("recycled_groups"):
        extra.append(f"{d['recycled_groups']:,} recycles")
    reads = d.get("reads_per_sec")
    return (
        f"| {label} | {_fmt(d.get('groups'), nd=0)}"
        f" | {_fmt(wps, scale=1e6, nd=2)}M"
        f" | {_fmt(reads, scale=1e6, nd=2) + 'M' if reads else '—'}"
        f" | {k if k is not None else '—'}"
        f" | {d.get('platform', '—')}"
        f"{(' · ' + ', '.join(extra)) if extra else ''}"
        f"{(' · ' + note) if note else ''} |"
    )


def _idle_axis_row(d):
    if not isinstance(d, dict) or d.get("error"):
        err = (d or {}).get("error", "missing") if isinstance(d, dict) else "missing"
        return f"| idle-groups axis | — | — | — | — | error: {str(err)[:60]} |"
    return (
        f"| idle-groups axis | {_fmt(d.get('active_groups'), nd=0)} active"
        f" + {_fmt(d.get('idle_groups'), nd=0)} idle"
        f" | {_fmt(d.get('writes_per_sec_with_idle'), scale=1e6, nd=2)}M"
        f" | — | {d.get('rounds_per_dispatch', '—')}"
        f" | {d.get('platform', '—')} · alone "
        f"{_fmt(d.get('writes_per_sec_alone'), scale=1e6, nd=2)}M, "
        f"delta {_fmt(d.get('idle_delta_pct'))}% (<10% asserted) |"
    )


def _pctile(vals, q):
    """Nearest-rank percentile over a sorted list (dependency-free —
    this script must run on a bare checkout)."""
    if not vals:
        return None
    i = min(len(vals) - 1, max(0, int(round(q / 100.0 * (len(vals) - 1)))))
    return vals[i]


def _obs_rows(detail):
    """Observability rows: dispatch-latency histogram and multidev-wait
    columns derived from the flight recorder's span dump in the bench
    artifact (ISSUE 5 satellite) — the ledger reads the record itself,
    not hand-transcribed numbers."""
    d = detail.get("obs_axis")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [f"| obs axis | — | — | — | — | error: {str(d['error'])[:60]} |"]
    spans = ((d.get("recorder") or {}).get("spans")) or []
    disp = sorted(
        s["dispatch_ms"] for s in spans
        if isinstance(s.get("dispatch_ms"), (int, float))
    )
    egress = sorted(
        s["egress_ms"] for s in spans
        if isinstance(s.get("egress_ms"), (int, float))
    )
    mu_total = sum(
        s.get("mu_wait_ms") or 0.0 for s in spans
        if isinstance(s.get("mu_wait_ms"), (int, float))
    )
    lat = (
        f"p50 {_fmt(_pctile(disp, 50))} / p99 {_fmt(_pctile(disp, 99))} ms"
        if disp else "—"
    )
    elat = (
        f"p50 {_fmt(_pctile(egress, 50))} / p99 {_fmt(_pctile(egress, 99))} ms"
        if egress else "—"
    )
    return [
        f"| obs axis ({_fmt(d.get('active_groups'), nd=0)} groups) "
        f"| {len(spans)} "
        f"| {lat} | {elat} | {_fmt(mu_total, nd=3)} "
        f"| obs-on {_fmt(d.get('writes_per_sec_obs_on'), scale=1e6, nd=2)}M vs "
        f"obs-off {_fmt(d.get('writes_per_sec_obs_off'), scale=1e6, nd=2)}M, "
        f"overhead {_fmt(d.get('obs_overhead_pct'))}% (<5% asserted), "
        f"{_fmt(d.get('device_metric_families'), nd=0)} device families |"
    ]


def _live_coord_rows(detail):
    """Live-coordinator rows (ISSUE 7 satellite): the adaptive-K A/B —
    fused vs single-round live w/s per K — plus the warm-enable seconds
    and compile-cache hit/miss counts, all sourced from the bench
    artifact's ``live_coord`` section so the "live past scalar" claim is
    ledger-backed, not prose."""
    d = detail.get("live_coord")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| live coord | — | — | — | — | error: {str(d['error'])[:60]} |"
        ]
    rows = []
    for k, v in sorted(
        (d.get("k_axis") or {}).items(), key=lambda kv: int(kv[0])
    ):
        rows.append(
            f"| K={k} ({_fmt(d.get('groups'), nd=0)} groups) "
            f"| {_fmt(v.get('writes_per_sec_fused'), nd=0)} "
            f"| {_fmt(v.get('writes_per_sec_single'), nd=0)} "
            f"| {_fmt(v.get('speedup'), nd=2)}x "
            f"| {d.get('platform', '—')} "
            f"| {'quiet round (same single-round program)' if k == '1' else 'fused multi-round dispatch'} |"
        )
    cold = d.get("cache_cold") or {}
    hot = d.get("cache_hot") or {}
    rows.append(
        f"| warm enable | {_fmt(d.get('warm_programs'), nd=0)} programs "
        f"| cold {_fmt(d.get('warm_enable_seconds'), nd=2)}s "
        f"({_fmt(cold.get('misses'), nd=0)} misses) "
        f"| cache-hot {_fmt(hot.get('enable_seconds'), nd=2)}s "
        f"({_fmt(hot.get('hits'), nd=0)} hits, "
        f"{_fmt(hot.get('misses'), nd=0)} misses) "
        f"| {d.get('platform', '—')} "
        f"| {_fmt(d.get('fused_dispatches'), nd=0)} fused dispatches, "
        f"{_fmt(d.get('stalled_spans'), nd=0)} stalled spans |"
    )
    return rows


def _mesh_rows(detail):
    """Mesh-dispatch rows (ISSUE 16): the shard-count axis — aggregate
    and per-shard fused w/s per mesh size with the per-doubling scaling
    factor — plus the live-migration and dispatch-concurrency evidence,
    sourced from the artifact's ``mesh_axis`` section.  The old
    multidev-wait column has no mesh analog to report: per-shard
    dispatch streams hold no global dispatch lock (``mu_wait_ms`` is
    structurally zero on mesh engines)."""
    d = detail.get("mesh_axis")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| mesh axis | — | — | — | — | error: {str(d['error'])[:60]} |"
        ]
    rows = []
    prev = None
    for n, v in sorted(
        (d.get("shards_axis") or {}).items(), key=lambda kv: int(kv[0])
    ):
        ws = v.get("writes_per_sec")
        per_shard = (ws / int(n)) if ws else None
        scaling = (
            f"{_fmt(ws / prev, nd=2)}x/doubling" if prev and ws else "—"
        )
        prev = ws or prev
        rows.append(
            f"| shards={n} ({_fmt(d.get('groups'), nd=0)} groups) "
            f"| {_fmt(ws, nd=0)} "
            f"| {_fmt(per_shard, nd=0)} "
            f"| {scaling} "
            f"| {d.get('platform', '—')} "
            f"| {d.get('noise', 'fused steady-state')} |"
        )
    mig = d.get("migration") or {}
    if mig:
        rows.append(
            f"| migration | {_fmt(mig.get('count'), nd=0)} moves "
            f"| {_fmt(mig.get('wall_ms_p50'), nd=2)} ms p50 "
            f"| commit watermark preserved "
            f"| {d.get('platform', '—')} "
            f"| peak dispatch concurrency "
            f"{_fmt(d.get('concurrency_peak'), nd=0)} |"
        )
    return rows


def _host_plane_rows(detail):
    """Host-plane rows (ISSUE 8 satellite): the many-session A/B —
    compartments on vs off writes/s per (sessions, device) rung, with
    fsyncs/s and the measured fsync amortization factor — sourced from
    the artifact's ``host_plane.axis`` section."""
    d = detail.get("host_plane")
    if not isinstance(d, dict):
        return []
    axis = d.get("axis") or {}
    rows = []
    for dev_key, dev_label in (
        ("fast", "box disk (~0.1ms fsync)"),
        ("slow_2ms", "serialized device, SIMULATED 2ms/barrier"),
    ):
        for ab in axis.get(dev_key) or []:
            off, on = ab.get("off") or {}, ab.get("on") or {}
            lat_on = (on.get("commit_latency_ms") or {})
            lat_off = (off.get("commit_latency_ms") or {})
            rows.append(
                f"| {_fmt(on.get('sessions'), nd=0)} sess / "
                f"{_fmt(on.get('groups'), nd=0)} grp "
                f"| {_fmt(on.get('writes_per_sec'), nd=0)} "
                f"| {_fmt(off.get('writes_per_sec'), nd=0)} "
                f"| {_fmt(ab.get('speedup'), nd=2)}x "
                f"| {_fmt(on.get('fsyncs_per_sec'), nd=0)} vs "
                f"{_fmt(off.get('fsyncs_per_sec'), nd=0)} "
                f"| {_fmt(on.get('amortization'), nd=2)} "
                f"| p50 {_fmt(lat_on.get('p50'))} vs "
                f"{_fmt(lat_off.get('p50'))} ms · {dev_label} |"
            )
    return rows


def _host_workers_rows(detail):
    """Host-workers rows (ISSUE 12): the multi-process host plane A/B —
    ``host_workers=0`` vs N writes/s on the many-session axis — sourced
    from the artifact's ``host_workers.axis`` section.  Single-core
    captures are parity runs and say so (no parallelism exists to win;
    the assertion there is parity-within-noise, the scaling target is
    gated on ``os.cpu_count()``)."""
    d = detail.get("host_workers")
    if not isinstance(d, dict):
        return []
    rows = []
    for ab in d.get("axis") or []:
        off, on = ab.get("off") or {}, ab.get("on") or {}
        hp = on.get("hostproc") or []
        hp0 = {
            "restarts": sum(h.get("restarts", 0) for h in hp),
            "fallbacks": {
                k: sum((h.get("fallbacks") or {}).get(k, 0) for h in hp)
                for k in ("encode", "wal", "apply")
            },
        }
        core_label = (
            f"single-core box (parity run, {_fmt(d.get('cores'), nd=0)} "
            "cpu)" if d.get("single_core")
            else f"{_fmt(d.get('cores'), nd=0)} cores"
        )
        rows.append(
            f"| {_fmt(on.get('sessions'), nd=0)} sess / "
            f"{_fmt(on.get('groups'), nd=0)} grp / "
            f"{_fmt(on.get('host_workers'), nd=0)} workers "
            f"| {_fmt(on.get('writes_per_sec'), nd=0)} "
            f"| {_fmt(off.get('writes_per_sec'), nd=0)} "
            f"| {_fmt(ab.get('speedup'), nd=2)}x "
            f"| {_fmt(hp0.get('restarts'), nd=0)} restarts, "
            f"{sum((hp0.get('fallbacks') or {}).values())} fallbacks "
            f"| {core_label} |"
        )
    return rows


#: canonical pipeline order for the attribution table (stages a path
#: does not visit are simply absent from its artifact section)
_STAGE_ORDER = (
    "ipc", "ingress", "raft_step", "wal", "device_round", "read_confirm",
    "lease_read", "apply", "egress",
)


def _trace_rows(detail):
    """Latency-attribution rows (ISSUE 9): per-stage p50/p99 and
    share-of-e2e for each engine path, sourced from the bench trace axis
    (``trace_axis.engines.<engine>.attribution``) — the ROADMAP tail
    questions get answered by artifact, not prose."""
    d = detail.get("trace_axis")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| trace axis | — | — | — | — | error: {str(d['error'])[:60]} |"
        ]
    rows = []
    for engine, e in sorted((d.get("engines") or {}).items()):
        att = e.get("attribution") or {}
        stages = att.get("stages") or {}
        ordered = [s for s in _STAGE_ORDER if s in stages] + [
            s for s in sorted(stages) if s not in _STAGE_ORDER
        ]
        for stage in ordered:
            v = stages[stage]
            rows.append(
                f"| {engine} | {stage} | {_fmt(v.get('p50_ms'), nd=3)} "
                f"| {_fmt(v.get('p99_ms'), nd=2)} "
                f"| {_fmt(v.get('share_pct'))}% "
                f"| n={_fmt(v.get('n'), nd=0)} |"
            )
        e2e = att.get("e2e") or {}
        rows.append(
            f"| {engine} | **e2e** | {_fmt(e2e.get('p50_ms'), nd=3)} "
            f"| {_fmt(e2e.get('p99_ms'), nd=2)} | 100% "
            f"| {_fmt(att.get('traces'), nd=0)} traces, trace-on overhead "
            f"{_fmt(e.get('trace_overhead_pct'))}%"
            + (
                f" ± {_fmt(e.get('trace_overhead_sem_pct'))} SEM"
                if e.get("trace_overhead_sem_pct") is not None else ""
            )
            + " (<5% asserted), "
            f"{_fmt(e.get('writes_per_sec_trace_on'), nd=0)} vs "
            f"{_fmt(e.get('writes_per_sec_trace_off'), nd=0)} w/s"
            + (
                f", {_fmt(e.get('fused_dispatches'), nd=0)} fused dispatches"
                if e.get("fused_dispatches") is not None else ""
            )
            + " |"
        )
    return rows


def _read_plane_rows(detail):
    """Read-plane rows (ISSUE 10): lease-served vs ReadIndex-fallback
    reads on the cross-domain rung (``crossdomain`` artifact section) —
    the leader near, the follower quorum one injected far link away."""
    d = detail.get("crossdomain")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| crossdomain | — | — | — | — | — | error: "
            f"{str(d['error'])[:60]} |"
        ]
    rows = []
    for label, key in (("lease reads", "lease_on"),
                       ("ReadIndex fallback", "lease_off")):
        v = (d.get("variants") or {}).get(key)
        if not isinstance(v, dict):
            continue
        rl = v.get("read_latency_ms") or {}
        wl = v.get("write_latency_ms") or {}
        lease = v.get("lease") or {}
        hit = lease.get("hit_ratio")
        notes = []
        if hit is not None:
            notes.append(f"lease hit ratio {_fmt(hit * 100)}%")
        if key == "lease_on" and d.get("read_p99_speedup"):
            notes.append(f"p99 {_fmt(d['read_p99_speedup'])}x faster")
        rows.append(
            f"| {label} | {_fmt(rl.get('p50'))} / {_fmt(rl.get('p99'))} "
            f"| {_fmt(wl.get('p50'))} / {_fmt(wl.get('p99'))} "
            f"| {_fmt(v.get('ops_per_sec'), nd=0)} "
            f"| {_fmt(v.get('reads'), nd=0)}r/{_fmt(v.get('writes'), nd=0)}w "
            f"| {_fmt(d.get('far_one_way_ms'), nd=0)}ms one-way "
            f"| {', '.join(notes) if notes else '—'} |"
        )
    return rows


def _devsm_rows(detail):
    """Device SM rows (ISSUE 11): device-resident KV apply vs host apply
    on the ``devsm`` artifact section (``bench_e2e.run_devsm``) — same
    3-host topology, same 9:1 mixed KV load, ``Config.device_kv``
    flipped."""
    d = detail.get("devsm")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| devsm | — | — | — | — | — | error: "
            f"{str(d['error'])[:60]} |"
        ]
    rows = []
    for label, key, share_key in (
        ("device apply (devsm)", "devsm_on", "apply_share_pct_devsm"),
        ("host apply", "devsm_off", "apply_share_pct_host"),
    ):
        v = (d.get("variants") or {}).get(key)
        if not isinstance(v, dict):
            continue
        rl = v.get("read_latency_ms") or {}
        wl = v.get("write_latency_ms") or {}
        share = d.get(share_key)
        notes = []
        dv = v.get("devsm") or {}
        if dv.get("served_ratio") is not None:
            notes.append(
                f"{_fmt(dv['served_ratio'] * 100)}% reads device-served"
            )
        if dv.get("ops_staged"):
            notes.append(f"{_fmt(dv['ops_staged'], nd=0)} ops staged")
        rows.append(
            f"| {label} | {_fmt(rl.get('p50'))} / {_fmt(rl.get('p99'))} "
            f"| {_fmt(wl.get('p50'))} / {_fmt(wl.get('p99'))} "
            f"| {_fmt(v.get('ops_per_sec'), nd=0)} "
            f"| {_fmt(v.get('reads'), nd=0)}r/{_fmt(v.get('writes'), nd=0)}w "
            f"| {_fmt(share)}% "
            f"| {', '.join(notes) if notes else '—'} |"
        )
    return rows


def _commit_attr_rows(detail):
    """Commit-attribution rows (ISSUE 14): per-peer quorum attribution
    from the cross-domain rung's attribution phase
    (``crossdomain.commit_attribution`` in the artifact;
    ``bench_e2e._xdom_commit_attribution``) — which follower's ack
    closes the far quorum, who laggards it, and the closing path's
    stage decomposition."""
    xd = detail.get("crossdomain")
    if not isinstance(xd, dict):
        return []
    d = xd.get("commit_attribution")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| attribution | — | — | — | error: "
            f"{str(d['error'])[:60]} |"
        ]
    rows = []
    summ = d.get("summary") or {}
    for peer, p in sorted((summ.get("peers") or {}).items()):
        rows.append(
            f"| peer {peer} ({p.get('cls', '?')}) "
            f"| {_fmt(p.get('rtt_p50_ms'))} / {_fmt(p.get('rtt_p99_ms'))} "
            f"| {_fmt(p.get('closer'), nd=0)} "
            f"| {_fmt(p.get('laggard'), nd=0)} "
            f"| {_fmt(p.get('acks'), nd=0)} acks |"
        )
    close = summ.get("close_ms") or {}
    shares = summ.get("close_stage_share_pct") or {}
    share_txt = ", ".join(
        f"{k} {_fmt(v)}%" for k, v in sorted(
            shares.items(), key=lambda kv: -kv[1]
        )
    )
    rows.append(
        f"| **quorum close** | {_fmt(close.get('p50'))} / "
        f"{_fmt(close.get('p99'))} | — | — "
        f"| n={_fmt(close.get('n'), nd=0)}, "
        f"{_fmt(summ.get('commits_attributed'), nd=0)} commits "
        f"attributed, wire share {_fmt(d.get('wire_share_pct'))}% |"
    )
    if share_txt:
        rows.append(f"| **close stage share** | — | — | — | {share_txt} |")
    rows.append(
        f"| **overhead** | — | — | — | trace-on "
        f"{_fmt(d.get('writes_per_sec_trace_on'), nd=0)} vs off "
        f"{_fmt(d.get('writes_per_sec_trace_off'), nd=0)} w/s, "
        f"{_fmt(d.get('trace_overhead_pct'))}%"
        + (
            f" ± {_fmt(d.get('trace_overhead_sem_pct'))} SEM"
            if d.get("trace_overhead_sem_pct") is not None else ""
        )
        + " (<5% + 2·SEM asserted) |"
    )
    return rows


def _hier_rows(detail):
    """Hierarchical-commit rows (ISSUE 18): the sub-quorum A/B from the
    hier rung (``hier`` in the artifact; ``bench_e2e.run_hier``) — the
    closer table flipping far→near, the commit close dropping from the
    far RTT to the near RTT, and the far-domain read coalescing."""
    d = detail.get("hier")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| hier | — | — | — | error: {str(d['error'])[:60]} |"
        ]
    rows = []
    for label, key in (
        ("classic quorum (hier off)", "hier_off"),
        ("sub-quorum (hier on)", "hier_on"),
    ):
        v = (d.get("variants") or {}).get(key)
        if not isinstance(v, dict):
            continue
        close = v.get("close_ms") or {}
        cls = v.get("closer_by_class") or {}
        wl = v.get("write_latency_ms") or {}
        notes = [f"write p99 {_fmt(wl.get('p99'))} ms"]
        h = v.get("hier")
        if h:
            notes.append(
                f"{_fmt(h.get('subquorum_closes'), nd=0)} sub-quorum / "
                f"{_fmt(h.get('fallback_closes'), nd=0)} fallback closes"
            )
        rows.append(
            f"| {label} "
            f"| {_fmt(close.get('p50'))} / {_fmt(close.get('p99'))} "
            f"| {_fmt(v.get('ops_per_sec'), nd=0)} "
            f"| near {_fmt(cls.get('A'), nd=0)} / "
            f"far {_fmt(cls.get('B'), nd=0)} "
            f"| {', '.join(notes)} |"
        )
    fr = ((d.get("variants") or {}).get("hier_on") or {}).get("far_read")
    if isinstance(fr, dict):
        rows.append(
            f"| **far-domain reads** | — "
            f"| {_fmt(fr.get('reads_per_sec'), nd=0)} r/s | — "
            f"| {_fmt(fr.get('reads'), nd=0)} coalesced onto "
            f"{_fmt(fr.get('leader_round_trips'), nd=0)} leader round "
            f"trips ({_fmt(fr.get('reads_coalesced'), nd=0)} piggybacked) |"
        )
    rows.append(
        f"| **speedup on/off** "
        f"| close p99 {_fmt(d.get('close_p99_speedup'))}x "
        f"| {_fmt(d.get('ops_ratio_on_off'))}x | — "
        f"| far link {_fmt(d.get('far_one_way_ms'), nd=0)} ms one-way |"
    )
    return rows


def _health_rows(detail):
    """Cluster-health rows (ISSUE 13): per-detector open/close counts
    and recovery-duration percentiles from the bench health axis
    (``health_axis`` in the artifact; ``bench_e2e.run_health_axis``) —
    the merged per-host sampler rings are the source, and the axis
    ships one host's ring dump (``health_axis.ring``) as evidence."""
    d = detail.get("health_axis")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| health axis | — | — | — | — | error: {str(d['error'])[:60]} |"
        ]
    rows = []
    detectors = d.get("detectors") or {}
    recovery = d.get("recovery") or {}
    for det in sorted(set(detectors) | set(recovery)):
        c = detectors.get(det) or {}
        r = recovery.get(det) or {}
        rows.append(
            f"| {det} | {_fmt(c.get('opened'), nd=0)} "
            f"| {_fmt(c.get('closed'), nd=0)} "
            f"| {_fmt(r.get('p50_s'), scale=1e-3, nd=0)} "
            f"| {_fmt(r.get('p99_s'), scale=1e-3, nd=0)} "
            f"| n={_fmt(r.get('n'), nd=0)} |"
        )
    rows.append(
        f"| **overhead** | — | — | — | — | health-on "
        f"{_fmt(d.get('writes_per_sec_health_on'), nd=0)} vs off "
        f"{_fmt(d.get('writes_per_sec_health_off'), nd=0)} w/s, "
        f"{_fmt(d.get('health_overhead_pct'))}%"
        + (
            f" ± {_fmt(d.get('health_overhead_sem_pct'))} SEM"
            if d.get("health_overhead_sem_pct") is not None else ""
        )
        + " (<5% asserted), "
        f"{_fmt(d.get('samples_total'), nd=0)} samples @ "
        f"{_fmt(d.get('sample_ms'), nd=0)}ms |"
    )
    return rows


def _telem_rows(detail):
    """Scale-telemetry rows (ISSUE 20): the device telemetry fold axis
    (``telem_axis`` in the artifact; ``bench_e2e.run_telem_axis``) —
    aggregate-sampler wall across a 64× group-count growth, the
    telem-on/off fold dispatch overhead on the fused multi-round shape,
    and the on-device top-K hit rate on planted worst-lag groups."""
    d = detail.get("telem_axis")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| telem axis | — | — | — | error: {str(d['error'])[:60]} |"
        ]
    sw = d.get("sampler_wall_ms") or {}
    gs, gb = d.get("groups_small"), d.get("groups_big")
    rows = [
        f"| sampler wall (aggregate) | {_fmt(sw.get('aggregate_small'), nd=4)} ms "
        f"| {_fmt(sw.get('aggregate_big'), nd=4)} ms "
        f"| {_fmt(sw.get('aggregate_big_over_small'), nd=2)}x "
        f"| drill-down walk {_fmt(sw.get('aggregate_walk_small'), nd=0)} / "
        f"{_fmt(sw.get('aggregate_walk_big'), nd=0)} rows; ≤2x asserted |",
        f"| sampler wall (full walk) | {_fmt(sw.get('full_small'), nd=2)} ms "
        f"| {_fmt(sw.get('full_big'), nd=2)} ms "
        f"| {_fmt(sw.get('full_big_over_small'), nd=1)}x "
        f"| the O(G) contrast the aggregate mode retires |",
        f"| fold dispatch overhead | — | — "
        f"| {_fmt(d.get('dispatch_overhead_pct'))}% "
        f"± {_fmt(d.get('dispatch_overhead_sem_pct'))} SEM "
        f"| fused {_fmt(d.get('rounds_per_block'), nd=0)}-round blocks "
        f"(fold once per block); <5%+2·SEM asserted; single-round "
        f"{_fmt(d.get('single_round_overhead_pct'))}% "
        f"± {_fmt(d.get('single_round_overhead_sem_pct'))} SEM "
        f"(informational) |",
        f"| top-K drill-down | — | — "
        f"| hit rate {_fmt(d.get('topk_hit_rate'), nd=2)} "
        f"| planted worst-lag groups over "
        f"{_fmt(d.get('topk_trials'), nd=0)} fresh trials; =1.0 asserted |",
        f"| **scale** | {_fmt(gs, nd=0)} groups | {_fmt(gb, nd=0)} groups "
        f"| {_fmt(d.get('scale'), nd=0)}x | — |",
    ]
    return rows


def _recovery_rows(detail):
    """Closed-loop recovery rows (ISSUE 17): per-detector MTTR p50/p99
    from the same-seed churn-soak A/B (``churn_soak`` in the artifact;
    ``bench_e2e.run_churn_soak``) — recovery plane OFF vs ON under the
    BlackWater schedule, plus the ON arm's actuation counts."""
    d = detail.get("churn_soak")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| churn soak | — | — | — | error: {str(d['error'])[:60]} |"
        ]
    rows = []
    for det, ab in sorted((d.get("mttr_p99") or {}).items()):
        speed = ab.get("speedup_x")
        rows.append(
            f"| {det} "
            f"| {_fmt(ab.get('off_p50_s'), nd=2)} → "
            f"{_fmt(ab.get('on_p50_s'), nd=2)} "
            f"| {_fmt(ab.get('off_p99_s'), nd=2)} → "
            f"{_fmt(ab.get('on_p99_s'), nd=2)} "
            f"| {_fmt(speed, nd=2)}x "
            f"| {'ON faster' if (speed or 0) > 1 else 'observe-only'} |"
        )
    acts = d.get("recovery_actions") or {}
    act_str = ", ".join(
        f"{k} ×{v}" for k, v in sorted(acts.items())
    ) or "none"
    on = d.get("on") or {}
    rows.append(
        f"| **verdict** | — | — | — | "
        f"{_fmt(d.get('groups'), nd=0)} groups seed {d.get('seed')}, "
        f"lin={'ok' if d.get('linearizable') else 'VIOLATED'}, "
        f"{_fmt(on.get('history_ops'), nd=0)} checked ops; "
        f"actions: {act_str} |"
    )
    return rows


def _devprof_program_rows(detail):
    """"Device programs" rows (ISSUE 15): the warm-set program registry
    from the bench devprof axis (``devprof_axis.programs``;
    ``DevProf.collect_programs``) — per-program XLA cost/memory
    analysis, one row per warmed variant."""
    d = detail.get("devprof_axis")
    if not isinstance(d, dict):
        return []
    if d.get("error"):
        return [
            f"| devprof axis | — | — | — | — | error: "
            f"{str(d['error'])[:60]} |"
        ]
    rows = []
    for p in d.get("programs") or []:
        if p.get("error"):
            rows.append(
                f"| `{p.get('variant')}` | — | — | — | — | error: "
                f"{str(p['error'])[:40]} |"
            )
            continue
        rows.append(
            f"| `{p.get('variant')}` "
            f"| {_fmt(p.get('flops'), scale=1e3)}k "
            f"| {_fmt(p.get('bytes_accessed'), scale=1024.0)} KiB "
            f"| {_fmt(p.get('temp_bytes'), scale=1024.0)} KiB "
            f"| {_fmt(p.get('compile_ms'))} "
            f"| out {_fmt(p.get('output_bytes'), scale=1024.0)} KiB |"
        )
    return rows


def _devprof_capacity_rows(detail):
    """"Device capacity" rows (ISSUE 15): the HBM ledger's per-plane
    residency plus the capacity model's verdict (prediction vs
    actually-allocated bytes, max-groups extrapolation at the reference
    16 GiB budget) and the estimator/overhead evidence."""
    d = detail.get("devprof_axis")
    if not isinstance(d, dict) or d.get("error"):
        return []
    cap = d.get("capacity") or {}
    rows = []
    for plane, nbytes in sorted((cap.get("planes") or {}).items()):
        rows.append(
            f"| {plane} | {_fmt(nbytes, scale=1024.0)} KiB | — | — | |"
        )
    if cap:
        rows.append(
            f"| **model** | {_fmt(cap.get('state_bytes'), scale=1024.0)} "
            f"KiB | {_fmt(cap.get('bytes_per_group'), nd=0)} B/group "
            f"| {_fmt(cap.get('max_groups_at_16gib'), scale=1e6, nd=1)}M "
            f"@16GiB | error {_fmt(cap.get('model_error_pct'), nd=2)}% "
            f"(<10% asserted, max |e| "
            f"{_fmt(cap.get('model_error_max_abs_pct'), nd=2)}%) |"
        )
    est = d.get("estimator") or {}
    if est:
        dm = est.get("device_ms") or {}
        rows.append(
            f"| **estimator** | — | — | — | device p50/p99 "
            f"{_fmt(dm.get('p50'), nd=2)}/{_fmt(dm.get('p99'), nd=2)} ms "
            f"({_fmt(est.get('sampled'), nd=0)} of "
            f"{_fmt(est.get('dispatches'), nd=0)} sampled), padding "
            f"waste {_fmt(est.get('padding_waste_ratio'), scale=0.01, nd=2)}% |"
        )
    if d.get("devprof_overhead_pct") is not None:
        rows.append(
            f"| **overhead** | — | — | — | profile-on "
            f"{_fmt(d.get('writes_per_sec_devprof_on'), nd=0)} vs off "
            f"{_fmt(d.get('writes_per_sec_devprof_off'), nd=0)} w/s, "
            f"{_fmt(d.get('devprof_overhead_pct'))}% ± "
            f"{_fmt(d.get('devprof_overhead_sem_pct'))} SEM "
            f"(<5% asserted) |"
        )
    return rows


def render(detail: dict, source: str) -> str:
    del source  # tables must be byte-identical whichever coherent
    # artifact (BENCH_DETAIL.json or its committed round record) renders
    lines = [
        BEGIN,
        "",
        "*Generated from the bench artifact (`make perf-ledger`) — edit "
        "the artifact, not this block.*",
    ]
    e2e_rows = [
        _e2e_row(label, detail[key])
        for label, key in (
            ("flagship (native SM, scalar)", "e2e"),
            ("python SM, scalar", "e2e_python_sm"),
            ("python SM, tpu engine", "e2e_tpu"),
            ("scale rung, tpu engine", "e2e_scale_tpu"),
            ("scale rung, scalar", "e2e_scale_scalar"),
        )
        if key in detail
    ]
    if e2e_rows:
        lines += [
            "",
            "### e2e A/B (identical placement; full stack, durable)",
            "",
            "| run | groups | writes/s | commit latency | ejects | sm/engine |",
            "|---|---|---|---|---|---|",
        ] + e2e_rows
    else:
        lines += ["", "*(no e2e sections in this capture)*"]
    lines += [
        "",
        "### Device ladder (staged ingress — NOT end-to-end)",
        "",
        "*reads/s is the device read plane's CONFIRMED ReadIndex rate "
        "(`kernels.read_confirm`, rung-4 mixed 9:1 phase); host-side "
        "watermark probes are listed in notes as `probe reads/s`.*",
        "",
        "| section | groups | writes/s | reads/s | rounds/dispatch | notes |",
        "|---|---|---|---|---|---|",
    ]
    if "groups" in detail:
        lines.append(_ladder_row(
            "pipelined kernel headline",
            {
                "groups": detail.get("groups"),
                "writes_per_sec": detail.get("headline_writes_per_sec"),
                "rounds_per_dispatch": detail.get("rounds_per_dispatch"),
                "platform": detail.get("platform"),
            },
            f"dispatch p99 {_fmt(detail.get('dispatch_p99_ms'))} ms",
        ))
    for label, key in (
        ("host_loop (real host staging)", "host_loop"),
        ("rung 4 (64k coordinator ingest)", "rung4"),
        ("rung 5 (100k + churn in-program)", "rung5"),
    ):
        if key in detail:
            lines.append(_ladder_row(label, detail[key], ""))
    if "idle_axis" in detail:
        lines.append(_idle_axis_row(detail["idle_axis"]))
    lat = detail.get("latency_mode")
    if isinstance(lat, dict) and not lat.get("error"):
        lines.append(
            f"| latency mode | {_fmt(lat.get('groups'), nd=0)} "
            f"| {_fmt(lat.get('writes_per_sec'), scale=1e6, nd=2)}M "
            f"| — | {lat.get('rounds_per_dispatch', '—')} "
            f"| dispatch p50 {_fmt(lat.get('dispatch_p50_ms'))} / "
            f"p99 {_fmt(lat.get('dispatch_p99_ms'))} ms |"
        )
    live_rows = _live_coord_rows(detail)
    if live_rows:
        lines += [
            "",
            "### Live coordinator (adaptive K-round batching, ISSUE 7)",
            "",
            "*The same live round — appends, follower acks, a K-tick "
            "backlog, scalar-guarded offload — through the WARMED "
            "coordinator (backlog fused into one multi-round dispatch) "
            "vs the UNWARMED single-round replay.  Warm-enable and "
            "compile-cache numbers from the same section "
            "(`live_coord` in the artifact).*",
            "",
            "| axis | fused w/s | single w/s | speedup | platform "
            "| notes |",
            "|---|---|---|---|---|---|",
        ] + live_rows
    host_rows = _host_plane_rows(detail)
    if host_rows:
        lines += [
            "",
            "### Host plane (compartmentalized ingress / group-commit "
            "WAL / executors, ISSUE 8)",
            "",
            "*Many-client/many-session axis: exactly-once sessions over "
            "a durable in-process 3-host cluster, compartments on/off "
            "A/B (`bench_e2e.run_sessions_ab`).  `slow` rows run the "
            "same stack on a SIMULATED serialized durability device — "
            "the regime where every group riding its own fsync is the "
            "bottleneck; `fast` rows are the real box disk, where the "
            "axis is per-write-Python-CPU bound and the tiers hold "
            "parity (spread ±15%).  Amortization = committer "
            "submissions per fsync cycle.*",
            "",
            "| axis | on w/s | off w/s | speedup | fsyncs/s on vs off "
            "| amortization | commit p50 on vs off |",
            "|---|---|---|---|---|---|---|",
        ] + host_rows
    hw_rows = _host_workers_rows(detail)
    if hw_rows:
        lines += [
            "",
            "### Host workers (multi-process host plane, ISSUE 12)",
            "",
            "*`host_workers` axis of `bench_e2e.run_sessions`: the same "
            "many-session durable cluster with the host-plane stages "
            "promoted to worker processes over shared-memory rings "
            "(`dragonboat_tpu/hostproc/`), N workers vs the in-process "
            "plane.  On a single-core box there is no parallelism to "
            "win — the axis asserts parity-within-noise and records the "
            "limitation; the ≥5x scaling target asserts only when "
            "`os.cpu_count()` provides the cores.*",
            "",
            "| axis | workers w/s | in-proc w/s | speedup "
            "| worker health | box |",
            "|---|---|---|---|---|---|",
        ] + hw_rows
    trace_rows = _trace_rows(detail)
    if trace_rows:
        lines += [
            "",
            "### Latency attribution (request tracing, ISSUE 9)",
            "",
            "*Per-stage share of a request's end-to-end latency, from "
            "the sampled cross-plane traces of the bench trace axis "
            "(`trace_axis` in the artifact; `bench_e2e.run_trace_axis`). "
            "A stage's latency is the time from the previous pipeline "
            "stamp to its own (propose → ingress → raft_step → wal "
            "[→ device_round] → apply → egress), so stage shares sum to "
            "~e2e; `n` counts stage observations (a stage a request's "
            "path skipped — e.g. device_round during leadership churn, "
            "when commits re-sync scalar-side — is absent, not zero).  "
            "Trace-on vs trace-off throughput is the MEAN pair-wise "
            "delta (± SEM) over an even number of adjacent-window PAIRS "
            "(one on + one off window each, order alternating per pair "
            "— 6 pairs = 12 windows in the committed capture) on the "
            "same live cluster — single-window weather on the 1-vCPU "
            "box is ±15%, which pairing + alternation cancels; the "
            "per-engine best-of w/s are shown for scale.*",
            "",
            "| path | stage | p50 ms | p99 ms | share of e2e | notes |",
            "|---|---|---|---|---|---|",
        ] + trace_rows
    rp_rows = _read_plane_rows(detail)
    if rp_rows:
        lines += [
            "",
            "### Read plane (leader-lease vs ReadIndex fallback, ISSUE 10)",
            "",
            "*Cross-domain rung (`crossdomain` in the artifact; "
            "`bench_e2e.run_crossdomain`): a live 3-host group under a "
            "9:1 mixed read/write load whose follower QUORUM sits one "
            "injected far link from the leader, so every ReadIndex "
            "confirmation and every commit pays the domain RTT while "
            "lease reads (`Config.read_lease`, dragonboat_tpu/lease.py) "
            "complete locally with zero confirmation rounds.  Asserted "
            "by the bench: lease read p99 single-digit ms, ≥90% lease "
            "hit ratio, mixed throughput within the noise band of the "
            "fallback run.  (The r07 device mixed-phase read-dispatch "
            "p99 this attacks was 1.08s — confirmation riding the "
            "write-round gate.)*",
            "",
            "| variant | read p50/p99 ms | write p50/p99 ms | mixed "
            "ops/s | ops | far link | notes |",
            "|---|---|---|---|---|---|---|",
        ] + rp_rows
    dv_rows = _devsm_rows(detail)
    if dv_rows:
        lines += [
            "",
            "### Device SM (device-resident KV apply plane, ISSUE 11)",
            "",
            "*Devsm rung (`devsm` in the artifact; `bench_e2e.run_devsm`):"
            " a live 3-host tpu-engine cluster under a 9:1 mixed KV load, "
            "`Config.device_kv` on vs off with the SAME "
            "`DeviceKVStateMachine` (the off variant is the host-apply "
            "oracle).  On the devsm path committed `(key_slot, value)` "
            "ops apply as tensor updates INSIDE the fused quorum "
            "dispatch (apply == commit on the device), reads serve "
            "straight from device state, and pending reads release at "
            "the device watermark — the `apply` trace stage leaves the "
            "read path, which is the share column collapsing.  Asserted "
            "by the bench: ≥50% of leader-host reads device-served, "
            "read-your-writes checked inline, apply share collapsed vs "
            "the host variant.*",
            "",
            "| variant | read p50/p99 ms | write p50/p99 ms | mixed "
            "ops/s | ops | apply share | notes |",
            "|---|---|---|---|---|---|---|",
        ] + dv_rows
    ca_rows = _commit_attr_rows(detail)
    if ca_rows:
        lines += [
            "",
            "### Commit attribution (replication-path tracing, ISSUE 14)",
            "",
            "*Per-peer quorum attribution from the cross-domain rung's "
            "attribution phase (`crossdomain.commit_attribution` in the "
            "artifact; `bench_e2e._xdom_commit_attribution`): sampled "
            "proposals carry a `ReplTrace` context across the wire, "
            "followers stamp append/fsync/ack, and the leader "
            "(`obs/replattr.py`) decomposes each commit's quorum close "
            "per peer — the ack times sorted ascending, the quorum-th "
            "smallest names the CLOSER (the same `kth_largest` rule "
            "`try_commit` runs; the leader self-acks at fan-out), "
            "unacked voters at close are LAGGARDS.  Stage deltas are "
            "clock-offset corrected (NTP-style ack pairing) so they sum "
            "to the measured RTT; the wire share is the number ROADMAP "
            "item 4's domain-local sub-quorum attacks.  Asserted by the "
            "bench: far-class peers are the only closers/laggards, the "
            "close pays the far RTT, wire share ≥50%, paired on/off "
            "overhead <5% + 2·SEM.*",
            "",
            "| peer (latency class) | ack RTT p50/p99 ms | quorum "
            "closes | laggard | notes |",
            "|---|---|---|---|---|",
        ] + ca_rows
    hier_rows = _hier_rows(detail)
    if hier_rows:
        lines += [
            "",
            "### Hierarchical commit (domain-local sub-quorums, "
            "ISSUE 18)",
            "",
            "*Hier rung (`hier` in the artifact; `bench_e2e.run_hier`): "
            "a live 4-host group in a 2+2 domain split — leader + one "
            "near follower, two followers one injected far link away — "
            "so the classic quorum (3/4) must cross the far link on "
            "every commit.  `Config.hier_commit` on vs off on identical "
            "topology and pure-write load: with the sub-quorum rule "
            "(`raft/hier.py`) the near-domain majority closes the "
            "commit at the near RTT and the far acks catch up "
            "asynchronously (CD-Raft / Fast Hierarchical Raft), with "
            "election intersection guaranteeing the next leader saw "
            "every sub-quorum commit.  Closer counts come from the "
            "replication-attribution plane, attributed against the "
            "rule that actually closed each commit.  Asserted by the "
            "bench: the closer table flips far→near, close p99 ≤ 0.5× "
            "the far RTT (vs ≥ it off), throughput no worse, and "
            "concurrent far-domain reads coalesce onto shared leader "
            "round trips (`FarReadBatcher`).*",
            "",
            "| variant | close p50/p99 ms | writes/s | closers "
            "near/far | notes |",
            "|---|---|---|---|---|",
        ] + hier_rows
    health_rows = _health_rows(detail)
    if health_rows:
        lines += [
            "",
            "### Cluster health (health plane, ISSUE 13)",
            "",
            "*Detector open/close counts and recovery-duration "
            "percentiles from the bench health axis (`health_axis` in "
            "the artifact; `bench_e2e.run_health_axis`): a live 3-host "
            "cluster under load with the HealthSampler A/B-toggled — "
            "scored as the MEAN pair-wise on/off delta ± SEM over "
            "alternating-order window pairs (the trace-axis weather "
            "discipline; <5% + 2·SEM asserted) — then a "
            "leadership-churn phase whose leader-flap open→close "
            "durations are the failover recovery times — the "
            "recorder-derived recovery p99s ROADMAP item 5 wants in "
            "the ledger.  `worker_flap` rows measure hostproc respawn, "
            "`devsm_rebind` rows device-plane rebind loops, when those "
            "planes run in the capture.*",
            "",
            "| detector | opened | closed | recovery p50 ms "
            "| recovery p99 ms | notes |",
            "|---|---|---|---|---|---|",
        ] + health_rows
    telem_rows = _telem_rows(detail)
    if telem_rows:
        lines += [
            "",
            "### Scale telemetry (device telemetry fold, ISSUE 20)",
            "",
            "*The device-plane telemetry axis (`telem_axis` in the "
            "artifact; `bench_e2e.run_telem_axis`): the fused quorum "
            "kernels reduce per-group health into one fixed-size "
            "aggregate per dispatch (commit-lag log₂ histogram, raft "
            "state counts, stalled count, slot occupancy, on-device "
            "top-K worst groups), so the aggregate-mode health sampler "
            "(`NodeHostConfig.health_aggregate`) walks top-K + open "
            "events instead of the group axis.  The sampler-wall rows "
            "are the O(1)-in-G evidence across a 64× group growth; the "
            "overhead row is the telem-on/off paired A/B on the fused "
            "multi-round dispatch shape (the coordinator's deployed "
            "shape — the fold runs once per scanned block); the top-K "
            "row is the drill-down correctness evidence.*",
            "",
            "| pillar | @ small | @ big | score | notes |",
            "|---|---|---|---|---|",
        ] + telem_rows
    rec_rows = _recovery_rows(detail)
    if rec_rows:
        lines += [
            "",
            "### Recovery (closed-loop recovery plane, ISSUE 17)",
            "",
            "*Per-detector MTTR from the same-seed churn-soak A/B "
            "(`churn_soak` in the artifact; `bench_e2e.run_churn_soak`): "
            "two identical BlackWater schedules — leader-flap storms, "
            "12s netsplit holds, SIGSTOP stalls, kill -9 restarts and "
            "membership recycles over witness-heavy quorums — with the "
            "recovery plane (`NodeHostConfig.auto_recover`, "
            "`obs/recovery.py`) OFF then ON.  MTTR is health-event open "
            "to close, fleet-merged with censored opens included; the "
            "gate is zero linearizability violations in both arms.  The "
            "OFF arm's quorum_at_risk MTTR is the netsplit hold (events "
            "close only when the partition heals); the ON arm's is the "
            "eviction latency.*",
            "",
            "| detector | MTTR p50 off→on (s) | MTTR p99 off→on (s) "
            "| p99 speedup | notes |",
            "|---|---|---|---|---|",
        ] + rec_rows
    dpp_rows = _devprof_program_rows(detail)
    if dpp_rows:
        lines += [
            "",
            "### Device programs (capacity & profiling plane, ISSUE 15)",
            "",
            "*The warm-set program registry from the bench devprof axis "
            "(`devprof_axis.programs`; `DevProf.collect_programs`): one "
            "row per warmed live-path variant (`warm_plan` is the single "
            "enumeration — the registry can't miss a program the live "
            "path runs), priced by the XLA executable's own "
            "`cost_analysis()`/`memory_analysis()`.  Compile walls are "
            "cache-hot where the persistent compilation cache holds the "
            "executable.  Asserted by the bench: non-zero cost/memory "
            "analysis for every program.*",
            "",
            "| program | flops | bytes accessed | peak temp "
            "| compile ms | notes |",
            "|---|---|---|---|---|---|",
        ] + dpp_rows
    dpc_rows = _devprof_capacity_rows(detail)
    if dpc_rows:
        lines += [
            "",
            "### Device capacity (HBM ledger & capacity model, ISSUE 15)",
            "",
            "*Per-plane device residency from the HBM ledger "
            "(`devprof_axis.capacity`; every artifact priced from the "
            "live arrays' own nbytes) against the capacity model's "
            "eval_shape prediction — the max-groups column extrapolates "
            "at a reference 16 GiB HBM budget and is the sizing input "
            "for ROADMAP items 2/3 (devsm scale-out, 1M+ groups on a "
            "mesh).  The estimator row is the sampled "
            "block_until_ready device-time evidence plus fused "
            "padding waste; the overhead row is the paired "
            "profile-on/off axis verdict.*",
            "",
            "| plane | resident | bytes/group | max groups | notes |",
            "|---|---|---|---|---|",
        ] + dpc_rows
    mesh_rows = _mesh_rows(detail)
    if mesh_rows:
        lines += [
            "",
            "### Mesh dispatch (per-shard streams, ISSUE 16)",
            "",
            "*The shard-count axis from the bench mesh rung "
            "(`mesh_axis.shards_axis`): one mesh-sharded engine "
            "(`ops/mesh.py`), fused steady-state writes/s aggregate and "
            "per shard, with the measured scaling factor per shard-count "
            "doubling.  The migration row is the live hot-group move "
            "evidence (count + wall); concurrency is the peak "
            "simultaneously in-flight shard dispatches — >1 is the "
            "no-global-mutex proof.  The multidev-wait column of the "
            "observability table is retired on mesh engines: per-shard "
            "dispatch streams hold no global dispatch lock, so "
            "`mu_wait_ms` is structurally zero.*",
            "",
            "| rung | writes/s | per-shard w/s | scaling "
            "| platform | notes |",
            "|---|---|---|---|---|---|",
        ] + mesh_rows
    obs_rows = _obs_rows(detail)
    if obs_rows:
        lines += [
            "",
            "### Observability (device-plane flight recorder)",
            "",
            "*Derived from the recorder span dump the obs axis ships in "
            "the artifact (`obs_axis.recorder.spans`); dispatch = host "
            "stage+launch wall, egress = blocking device→host wall, "
            "multidev wait = multi-device dispatch-lock acquisition "
            "(zero on single-device engines; structurally absent on "
            "mesh-sharded engines — see the mesh table).*",
            "",
            "| axis | spans | dispatch latency | egress latency "
            "| multidev wait ms | notes |",
            "|---|---|---|---|---|---|",
        ] + obs_rows
    lines += ["", END]
    return "\n".join(lines)


def _default_artifact(root: str) -> str:
    """The working-tree BENCH_DETAIL.json (written by the bench run,
    deliberately untracked) is the only artifact of record."""
    return os.path.join(root, "BENCH_DETAIL.json")


def _unwrap(rec: dict) -> dict:
    """Accept either artifact shape: a raw bench detail dict, or a driver
    round record ({"parsed": {"detail": ...}, ...}) — the slim round
    records carry the same section fields."""
    if "parsed" in rec and isinstance(rec["parsed"], dict):
        parsed = rec["parsed"]
        detail = parsed.get("detail")
        if isinstance(detail, dict) and "see" not in detail:
            out = dict(detail)
            out.setdefault("headline_writes_per_sec", parsed.get("value"))
            return out
        return parsed
    return rec


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--detail", default=None,
        help="bench artifact to derive the tables from (default: "
        "BENCH_DETAIL.json)",
    )
    ap.add_argument("--perf", default=os.path.join(root, "PERF.md"))
    ap.add_argument(
        "--check", action="store_true",
        help="exit 1 if PERF.md is stale instead of rewriting it",
    )
    args = ap.parse_args()
    if args.detail is None:
        args.detail = _default_artifact(root)

    try:
        with open(args.detail) as f:
            detail = _unwrap(json.load(f))
    except (OSError, ValueError) as e:
        print(f"perf_ledger: cannot read {args.detail}: {e}", file=sys.stderr)
        return 1
    try:
        with open(args.perf) as f:
            perf = f.read()
    except OSError as e:
        print(f"perf_ledger: cannot read {args.perf}: {e}", file=sys.stderr)
        return 1

    if BEGIN not in perf or END not in perf:
        print(
            f"perf_ledger: marker block not found in {args.perf} "
            f"(need {BEGIN!r} … {END!r})",
            file=sys.stderr,
        )
        return 1
    head, rest = perf.split(BEGIN, 1)
    _, tail = rest.split(END, 1)
    block = render(detail, os.path.basename(args.detail))
    out = head + block + tail
    if out == perf:
        print("perf_ledger: up to date")
        return 0
    if args.check:
        print("perf_ledger: PERF.md is stale (run `make perf-ledger`)",
              file=sys.stderr)
        return 1
    with open(args.perf, "w") as f:
        f.write(out)
    print(f"perf_ledger: regenerated ledger block in {args.perf}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
