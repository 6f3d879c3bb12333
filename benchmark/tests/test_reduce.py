"""The reduction from a trace to numbers, against a small recorded trace.

``data/recorded_trace.json`` is 300 ms cut from a traced run of
``upstream48x3.write_closed`` on the v5e (PR 25).  The reduction's busy and
idle time, per-kernel time and gap attribution are recomputed here the slow
way, by painting every nanosecond-interval onto a microsecond grid.
"""
import json
import os

import numpy as np
import pytest

from benchmark import reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KERNELS = ("quorum_step_impl", "quorum_multiround_impl")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        return json.load(f)


def paint(events, lo, hi):
    """Boolean microsecond grid of [lo, hi) covered by the events."""
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for _name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    return grid


def test_busy_idle_and_kernel_time_of_the_recorded_trace(recorded):
    got = reduce.reduce_trace(recorded, KERNELS)
    dev = [p for p in recorded["planes"]
           if p["name"].startswith(reduce.DEVICE_PLANE)]
    assert len(dev) == 1
    every = [ev for p in recorded["planes"] for ln in p["lines"]
             for ev in ln["events"]]
    lo = min(ev[1] for ev in every)
    hi = max(ev[1] + ev[2] for ev in every)
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)

    lines = {ln["name"]: ln["events"] for ln in dev[0]["lines"]}
    ops = lines.get("XLA Ops") or lines["XLA Modules"]
    busy_grid = paint(ops, lo, hi)
    # the grid rounds every interval outwards to whole microseconds
    assert got["busy_s"] <= busy_grid.sum() / 1e6
    assert got["busy_s"] >= busy_grid.sum() / 1e6 - 2e-6 * len(ops)
    assert got["idle_s"] == pytest.approx(got["window_s"] - got["busy_s"])
    assert 0 < got["busy_s"] < 0.05 * got["window_s"]  # a host-bound system

    # per-kernel device time and dispatch counts, summed by hand
    want_s, want_n = {}, {}
    for name, _s, d in lines["XLA Modules"]:
        k = name.split("(")[0].removeprefix("jit_")
        if k in KERNELS:
            want_s[k] = want_s.get(k, 0) + d
            want_n[k] = want_n.get(k, 0) + 1
    assert want_n and got["kernel_n"] == want_n
    for k in want_s:
        assert got["kernel_s"][k] == pytest.approx(want_s[k] / 1e9)
    assert [k for k, _ in got["device_ops"]][0] in KERNELS

    # the benchmark's own dispatch spans are found on the host plane
    n_spans = sum(1 for ev in every if ev[0] == reduce.DISPATCH_SPAN)
    assert len(got["dispatch_s"]) == n_spans > 0


def test_gap_attribution_of_the_recorded_trace(recorded):
    got = reduce.reduce_trace(recorded, KERNELS)
    gaps = dict(got["idle_gaps"])
    host = [ln["events"] for p in recorded["planes"]
            if p["name"].startswith(reduce.HOST_PLANE) for ln in p["lines"]]
    every = [ev for p in recorded["planes"] for ln in p["lines"]
             for ev in ln["events"]]
    lo = min(ev[1] for ev in every)
    hi = max(ev[1] + ev[2] for ev in every)
    # where no host thread had any event open, the gap is "not traced"
    covered = np.zeros((hi - lo) // 1000 + 1, bool)
    for events in host:
        covered |= paint(events, lo, hi)
    untraced = (~covered).sum() / 1e6
    assert gaps["host:no_event_traced"] == pytest.approx(untraced, abs=2e-3)
    # self times add up: per thread, no more than the thread's covered time
    named = sum(v for k, v in gaps.items() if k != "host:no_event_traced")
    per_thread = sum(paint(events, lo, hi).sum() / 1e6 for events in host)
    assert 0 < named <= per_thread + 1e-3
    assert got["idle_gaps"] == sorted(got["idle_gaps"], key=lambda kv: -kv[1])


def test_self_time_takes_nested_events_out():
    line = [["outer", 0, 100], ["inner", 10, 30], ["leaf", 15, 5],
            ["inner2", 50, 20], ["next", 200, 10]]
    got = {name: self_ns for name, _s, _e, self_ns in reduce.self_times(line)}
    assert got == {"outer": 50, "inner": 25, "leaf": 5, "inner2": 20,
                   "next": 10}


def test_synthetic_trace_end_to_end():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_quorum_step_impl(1)", 1000, 200],
                ["jit__gather_rows(2)", 2000, 100]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 1000, 50], ["fusion.2", 1100, 100],
                ["gather.3", 2000, 100]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "round-1", "events": [
                [reduce.DISPATCH_SPAN, 500, 2000], ["shard_args", 600, 300],
                ["DevicePut", 700, 100]]},
            {"name": "main", "events": [[reduce.WINDOW_MARK, 0, 4000]]}]},
    ]}
    got = reduce.reduce_trace(trace, KERNELS)
    assert got["window_s"] == pytest.approx(4000e-9)
    assert got["busy_s"] == pytest.approx(250e-9)
    assert got["kernel_s"] == {"quorum_step_impl": pytest.approx(200e-9)}
    assert got["kernel_n"] == {"quorum_step_impl": 1}
    assert got["dispatch_s"] == [pytest.approx(2000e-9)]
    gaps = dict(got["idle_gaps"])
    assert gaps["host:shard_args"] == pytest.approx(200e-9)
    assert gaps["host:DevicePut"] == pytest.approx(100e-9)
    # 2000 ns of span, 300 inside shard_args, 250 of the rest under device ops
    assert gaps["host:bench:dispatch"] == pytest.approx(
        1700e-9 * (2000 - 250) / 2000)
    assert gaps["host:no_event_traced"] == pytest.approx(2000e-9)


def test_no_device_plane_gives_no_device_numbers():
    trace = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "t", "events": [[reduce.DISPATCH_SPAN, 0, 10]]}]}]}
    got = reduce.reduce_trace(trace, KERNELS)
    assert got["busy_s"] is None and got["dispatch_s"] == [1e-8]


def test_percentile_is_over_all_values():
    assert reduce.percentile([5, 1, 3, 2, 4], 50) == 3
    assert reduce.percentile(range(101), 95) == 95
    with pytest.raises(ValueError):
        reduce.percentile([], 50)
