"""From a profiler trace and the tracer's stamps to numbers.

The yardstick: later PRs may not edit this file, so every PR computes busy
and idle time, kernel time and the attribution of idle gaps the same way.
``tests/test_reduce.py`` checks it against a small recorded trace.

A trace, here, is plain data, ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, dur_ns], ...]}]}]}``: what ``load_xplane`` makes
of the profiler's ``.xplane.pb`` and what the recorded test trace holds.
"""
from __future__ import annotations

import bisect
import glob
import os

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:"
#: lines of a device plane, finest first: busy time is the union of single
#: operations where the trace has them, else of whole programs
OP_LINES = ("XLA Ops", "XLA Modules")
MODULE_LINE = "XLA Modules"
#: spans the benchmark itself writes on the profiler's clock
WINDOW_MARK = "bench:traced_window"
DISPATCH_SPAN = "bench:dispatch"


def load_xplane(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---- interval arithmetic (half-open [start, end), integers) ----------------


def union(intervals) -> list:
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def complement(merged, lo: int, hi: int) -> list:
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def overlap(merged, s: int, e: int) -> int:
    """Length of [s, e) inside a merged, sorted interval list."""
    i = max(bisect.bisect_right(merged, [s, float("inf")]) - 1, 0)
    got = 0
    while i < len(merged) and merged[i][0] < e:
        got += max(min(merged[i][1], e) - max(merged[i][0], s), 0)
        i += 1
    return got


# ---- the reduction ---------------------------------------------------------


def kernel_name(event_name: str) -> str:
    """``jit_quorum_step_impl(1234)`` -> ``quorum_step_impl``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def self_times(events) -> list:
    """[(name, start, end, self_ns)] for the events of one thread's line,
    where nested events are taken out of the event that holds them."""
    out, stack = [], []
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        e = s + d
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, d])
    out += [tuple(x) for x in stack]
    return out


def reduce_trace(trace: dict, kernels=()) -> dict:
    """Busy and idle time of the device, time by device operation, the
    kernels' dispatches, the benchmark's dispatch spans, and the idle gaps
    by what the host was doing.  Times in seconds; ``None`` where the trace
    holds no device plane (a CPU rehearsal)."""
    dev = [p for p in trace["planes"] if p["name"].startswith(DEVICE_PLANE)]
    host = [p for p in trace["planes"] if p["name"].startswith(HOST_PLANE)]
    host_events = [ev for p in host for ln in p["lines"] for ev in ln["events"]]
    marks = [ev for ev in host_events if ev[0] == WINDOW_MARK]
    if marks:
        lo, hi = marks[0][1], marks[0][1] + marks[0][2]
    else:
        every = host_events + [
            ev for p in dev for ln in p["lines"] for ev in ln["events"]
        ]
        if not every:
            return {"window_s": 0.0, "busy_s": None}
        lo = min(ev[1] for ev in every)
        hi = max(ev[1] + ev[2] for ev in every)
    out = {"window_s": (hi - lo) / 1e9, "busy_s": None, "device_ops": [],
           "kernel_s": {}, "kernel_n": {}, "idle_gaps": [], "dispatch_s": []}
    out["dispatch_s"] = sorted(
        ev[2] / 1e9 for ev in host_events
        if ev[0] == DISPATCH_SPAN and lo <= ev[1] < hi
    )
    if not dev:
        return out

    busy_total, by_name, per_chip_busy = 0, {}, []
    for plane in dev:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = next((lines[n] for n in OP_LINES if lines.get(n)), [])
        merged = clip(union([s, s + d] for _n, s, d in ops), lo, hi)
        per_chip_busy.append(merged)
        busy_total += total(merged)
        for name, s, d in lines.get(MODULE_LINE, []):
            if lo <= s < hi:
                k = kernel_name(name)
                by_name[k] = by_name.get(k, 0) + d
                if k in kernels:
                    out["kernel_s"][k] = out["kernel_s"].get(k, 0.0) + d / 1e9
                    out["kernel_n"][k] = out["kernel_n"].get(k, 0) + 1
    out["busy_s"] = busy_total / 1e9 / len(dev)
    out["device_ops"] = sorted(
        ([k, v / 1e9] for k, v in by_name.items()), key=lambda kv: -kv[1]
    )

    # idle gaps of the first chip, by the host's self time inside them
    busy = per_chip_busy[0]
    gaps = complement(busy, lo, hi)
    by_host, covered = {}, []
    for plane in host:
        for ln in plane["lines"]:
            spans = [ev for ev in ln["events"]
                     if ev[0] != WINDOW_MARK and ev[1] < hi
                     and ev[1] + ev[2] > lo]
            for name, s, e, self_ns in self_times(spans):
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                covered.append([s, e])
                idle_part = (e - s) - overlap(busy, s, e)
                share = idle_part / (e - s)
                key = "host:" + name.strip()
                by_host[key] = by_host.get(key, 0.0) + self_ns * share
    seen = union(covered)
    untraced = sum((e - s) - overlap(seen, s, e) for s, e in gaps)
    by_host["host:no_event_traced"] = float(untraced)
    out["idle_gaps"] = sorted(
        ([k, v / 1e9] for k, v in by_host.items() if v > 0),
        key=lambda kv: -kv[1],
    )
    out["idle_s"] = total(gaps) / 1e9
    return out


def stage_p50_ms(traces) -> dict:
    """stage -> (median milliseconds, samples) over the tracer's completed
    sampled requests of every host: the time from the stamp before to this
    stage's stamp (``obs/trace.py compute_stage_stats``, nearest rank)."""
    per = {}
    for t in traces:
        if not t.done:
            continue
        evs = sorted(t.events, key=lambda ev: ev[1])
        prev = evs[0][1]
        for stage, ts, _thread in evs[1:]:
            per.setdefault(stage, []).append(max(0.0, ts - prev))
            prev = ts
    out = {}
    for stage, vals in per.items():
        vals.sort()
        i = min(len(vals) - 1, int(round(0.5 * (len(vals) - 1))))
        out[stage] = (vals[i] * 1e3, len(vals))
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values (no chunks, no trimming)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of nothing")
    return vals[min(len(vals) - 1, int(round(q / 100.0 * (len(vals) - 1))))]
