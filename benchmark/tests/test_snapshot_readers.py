"""The seven ``snapshot_*`` readers (ISSUE 37) on recorded spans and
counters (``data/snapshot_plane.json``): the window's committed periodic
saves, the counts of the window's whole seconds, the earlier line, and
``None`` wherever the program recorded nothing: an older program, the plain
reference in its place, a run in which no group snapshots (the five older
cells' lines do not change)."""
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import run as harness
from benchmark.layers import snapshot_plane as sp

HERE = os.path.dirname(os.path.abspath(__file__))
LAYERS = os.path.join(os.path.dirname(HERE), "layers")
T0, T_END = 100.0, 148.0

EXPECTED = {
    "snapshot_save_ms": 174.6657,      # of 7 saves inside the window
    "snapshot_sm_save_ms": 88.6002,
    "snapshot_queue_ms": 6.4189,
    "snapshot_compact_ms": 21.0494,
    "snapshot_lag_x": 1.3,             # 13 entries at snapshot_entries 10
    "snapshot_pool_busy_pct": 100.0 * 1.5 / (16 * 48.0),
    "snapshot_installs": 0.0,          # the three sent fall after it
}


def reader(family):
    return harness.load_module(os.path.join(LAYERS, family + ".py"),
                               "test_layer_" + family)


def recorded():
    with open(os.path.join(HERE, "data", "snapshot_plane.json")) as f:
        return json.load(f)


class Plane:
    """A host's replica instruments as recorded: ``workers`` and counts by
    the whole second."""

    def __init__(self, host):
        self.workers = host["workers"]
        self.secs = {int(s): c for s, c in host["by_second"].items()}

    def window(self, lo, hi):
        out = {}
        for sec, counts in self.secs.items():
            if lo <= sec + 0.5 < hi:
                for name, n in counts.items():
                    out[name] = out.get(name, 0) + n
        return out


def ctx(spans, planes, trace=None):
    c = harness.Ctx(outcome=SimpleNamespace(t0=T0, t_end=T_END),
                    seconds=T_END - T0, trace=trace)
    sp.select(c, spans=lambda: spans, planes=lambda: planes)
    return c


def full(trace=None):
    rec = recorded()
    return ctx(rec["spans"], [Plane(h) for h in rec["hosts"]], trace)


@pytest.mark.parametrize("family", sorted(EXPECTED))
def test_reader_takes_the_windows_saves_and_counts(family):
    assert reader(family).read(full()) == pytest.approx(EXPECTED[family])


@pytest.mark.parametrize("family", sorted(EXPECTED))
@pytest.mark.parametrize("program", [
    "no_ring_no_counters", "counters_but_no_snapshot", "spans_outside"])
def test_reader_returns_none_where_nothing_snapshots(family, program):
    rec = recorded()
    if program == "no_ring_no_counters":  # the parent, the plain reference
        c = ctx(None, None)
    elif program == "counters_but_no_snapshot":  # an older cell, traced
        quiet = Plane({"workers": 8, "by_second": {
            "120": {"checkq_windows": 0}}})
        rounds = [{"kind": "coord_round", "t0": 120.0, "t1": 120.1,
                   "wall_ms": 100.0}]
        c = ctx(rounds, [quiet, quiet])
    else:
        outside = [s for s in rec["spans"] if not T0 <= s["t0"] < T_END]
        c = ctx(outside, [])
    assert reader(family).read(c) is None


def test_selection_is_the_committed_periodic_saves_of_the_window():
    saves, counts = sp.select(full())
    assert len(saves) == 7
    assert all(s["saved"] and s["save_kind"] == "periodic" for s in saves)
    assert all(T0 <= s["t0"] < T_END for s in saves)
    assert counts == {
        "workers": 16, "saves": 9, "compactions": 9, "pool_busy_s": 1.5,
        "saves_refused": 3, "checkq_windows": 12}
    c = full()  # cached: every reader of a run sees one selection
    assert sp.select(c, spans=lambda: [], planes=lambda: []) is sp.select(c)


def test_what_was_found_goes_on_one_earlier_line(capsys):
    trace = {"idle_gaps": [["host:dbtpu:snapshot_save", 3.25],
                           ["host:wal_sync", 2.0],
                           ["host:dbtpu:compact", 0.5]]}
    c = full(trace)
    for family in EXPECTED:
        reader(family).read(c)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines == [{
        "event": "snapshot_plane", "spans_in_ring": 7, "checkq_windows": 12,
        "compactions": 9, "pool_busy_s": 1.5, "saves": 9,
        "saves_refused": 3, "workers": 16, "saves_per_s": 0.19,
        "image_bytes_p50_max": [1169, 1297],
        "idle_gaps_s": {"host:dbtpu:snapshot_save": 3.25,
                        "host:dbtpu:compact": 0.5}}]
    ctx(None, None)  # nothing found: no line
    assert capsys.readouterr().out == ""


def test_an_install_inside_the_window_is_counted():
    rec = recorded()
    rec["hosts"][1]["by_second"]["130"]["installs_sent"] = 2
    c = ctx(rec["spans"], [Plane(h) for h in rec["hosts"]])
    assert reader("snapshot_installs").read(c) == 2.0


def test_the_program_side_accessors_exist_and_are_empty_when_idle():
    """What ``ring`` and ``live`` read in the program: the default
    recorder's spans and the live replica instruments (none while no
    NodeHost with its tracer or metrics on is running)."""
    from dragonboat_tpu.obs import instruments

    assert isinstance(sp.ring(), list)
    assert sp.live() == instruments.replica_obs_live()
    obs = instruments.ReplicaObs(host="t", workers=8)
    try:
        assert obs in sp.live()
        obs.pool_task(10.75, 12.5, 0)  # each second gets its own part
        obs.save_refused()
        assert obs.window(0.0, 11.5) == {"pool_busy_s": 0.25}
        assert obs.window(11.0, 12.0) == {"pool_busy_s": 1.0}
        assert obs.window(0.0, float("inf")) == {
            "pool_busy_s": 1.75, "saves_refused": 1}
    finally:
        obs.close()
    assert obs not in sp.live()
