"""The readers of the program's own spans and counters (``layers/round_*``,
``engine_*``, ``rounds_per_s``, ``compile_ms_in_window``, ``read_*_pct``,
``program_timeouts_per_kop``), each driven with a hand-made ring: the
window's selection, the line a wrapped ring earns, ``None`` where the
program recorded nothing."""
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import run as harness
from benchmark.layers import program_spans as ps

LAYERS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "layers")
T0, T_END = 100.0, 148.0


def reader(family):
    return harness.load_module(os.path.join(LAYERS, family + ".py"),
                               "test_layer_" + family)


def ctx(spans=None, written=None, capacity=16384):
    c = harness.Ctx(outcome=SimpleNamespace(t0=T0, t_end=T_END),
                    seconds=T_END - T0)
    if spans is not None:
        ps.select(c, source=lambda: (
            spans, len(spans) if written is None else written, capacity))
    return c


def round_span(t0, host="h1", wall=100.0, **kw):
    s = {"kind": "coord_round", "t0": t0, "t1": t0 + wall / 1e3,
         "host": host, "parent": None, "wall_ms": wall, "wait_ms": 50.0,
         "drain_ms": 5.0, "fanout_ms": 8.0, "read_acks": 0,
         "reads_staged": 0, "reads_refused": 0,
         "read_fallback_slot_overflow": 0, "read_fallback_after_confirm": 0,
         "read_fallback_purged": 0}
    s.update(kw)
    return s


def dispatch_span(t0, kind="dispatch", **kw):
    s = {"kind": kind, "t0": t0, "t1": t0 + 0.09, "host": "h1",
         "parent": 0, "dispatch_ms": 80.0, "row_sync_ms": 4.0,
         "stage_ms": 30.0, "transfer_ms": 25.0, "launch_ms": 1.0,
         "egress_ms": 10.0, "egress_wait_ms": 7.0,
         "decode_ms": 3.0, "step_ms": 100.0}
    s.update(kw)
    return s


def ring():
    """Three rounds and dispatches inside the window on two hosts, one of
    each before it and after it, one round still open (no ``wall_ms``)."""
    open_round = round_span(120.0)
    del open_round["wall_ms"]
    open_round["wait_ms"] = None
    inflight = dispatch_span(121.0)
    del inflight["egress_ms"]
    inflight["egress_wait_ms"] = inflight["decode_ms"] = None
    return [
        round_span(99.0, wall=999.0, wait_ms=999.0, fanout_ms=999.0),
        dispatch_span(99.5, stage_ms=999.0, transfer_ms=999.0),
        round_span(100.0, wall=100.0, wait_ms=40.0, fanout_ms=6.0,
                   read_acks=30, read_fallback_after_confirm=50,
                   read_fallback_slot_overflow=15, reads_staged=36,
                   reads_refused=4),
        dispatch_span(100.01, stage_ms=20.0, transfer_ms=20.0,
                      launch_ms=1.0, step_ms=73.0, egress_wait_ms=5.0),
        round_span(110.0, host="h2", wall=120.0, wait_ms=60.0,
                   fanout_ms=10.0, read_acks=10,
                   read_fallback_purged=5),
        dispatch_span(110.01, kind="fused", stage_ms=40.0,
                      transfer_ms=30.0, launch_ms=3.0, step_ms=119.0,
                      egress_wait_ms=9.0),
        open_round,
        inflight,
        round_span(147.9, wall=110.0, wait_ms=50.0, fanout_ms=8.0),
        dispatch_span(147.91, stage_ms=30.0, transfer_ms=25.0,
                      launch_ms=2.0, step_ms=101.0, egress_wait_ms=7.0),
        round_span(148.0, wall=888.0, wait_ms=888.0, fanout_ms=888.0),
        dispatch_span(148.5, stage_ms=888.0, transfer_ms=888.0),
    ]


EXPECTED = {
    "round_wait_ms": 50.0,            # 40, 60, 50 (not the open round)
    "round_wall_ms": 110.0,           # 100, 120, 110 from t1 - t0
    "round_fanout_ms": 8.0,           # 6, 10, 8
    "rounds_per_s": 3 / 48.0 / 2,     # three finished rounds, two hosts
    "engine_stage_ms": 34.0,          # 24, 44, 34 with the row syncs
    "engine_transfer_ms": 25.0,       # 20, 30, 25
    "engine_launch_ms": 2.0,          # 1, 3, 2 (not the in-flight step)
    "engine_other_ms": 30.0,          # step_ms less the phases: 20, 30, 30
    "engine_egress_ms": 10.0,         # 8, 12, 10
    "read_fallback_pct": 100.0 * 70 / 110,
    "read_slot_overflow_pct": 10.0,
}


@pytest.mark.parametrize("family", sorted(EXPECTED))
def test_reader_takes_the_windows_spans(family):
    got = reader(family).read(ctx(ring()))
    assert got == pytest.approx(EXPECTED[family]), family


@pytest.mark.parametrize("family", sorted(EXPECTED))
def test_reader_returns_none_without_spans(family):
    """The plain reference in the program's place, or an older program
    whose records carry no interval: nothing to read."""
    assert reader(family).read(ctx([])) is None
    legacy = [{"kind": "coord_round", "ts": 1.0, "wall_ms": 5.0},
              {"kind": "dispatch", "ts": 1.0, "dispatch_ms": 5.0}]
    assert reader(family).read(ctx(legacy)) is None
    only_outside = [s for s in ring() if not T0 <= s["t0"] < T_END]
    assert reader(family).read(ctx(only_outside)) is None


def test_selection_is_cached_per_run_and_split_by_kind():
    c = ctx(ring())
    assert len(ps.spans(c, ps.ROUND)) == 3    # the open round: not read
    assert len(ps.spans(c, ps.DISPATCH)) == 3  # nor the in-flight step
    assert {s["kind"] for s in ps.spans(c, ps.DISPATCH)} == {
        "dispatch", "fused"}
    # a later reader of the same run sees the same selection
    assert ps.select(c, source=lambda: ([], 0, 1)) is ps.select(c)


def test_wrapped_ring_reads_what_is_covered_and_says_so(capsys):
    spans = [s for s in ring() if s["t0"] >= 110.0]  # the start is gone
    got = reader("round_wait_ms").read(
        ctx(spans, written=20000, capacity=len(spans)))
    assert got == pytest.approx(50.0)  # of 60 and 50, nearest rank
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines == [{"event": "span_ring_wrapped", "capacity": len(spans),
                      "written": 20000, "covered_from_s": 10.0,
                      "window_s": 48.0}]
    # a ring that wrapped but still reaches back before the window: silent
    reader("round_wait_ms").read(ctx(ring(), written=20000, capacity=12))
    assert capsys.readouterr().out == ""


def test_read_echo_causes_go_on_an_earlier_line(capsys):
    reader("read_fallback_pct").read(ctx(ring()))
    assert json.loads(capsys.readouterr().out) == {
        "event": "read_echoes", "device": 40,
        "scalar": {"slot_overflow": 15, "after_confirm": 50, "purged": 5}}
    # spans but no echo at all (a writes-only window): nothing to read
    quiet = [round_span(101.0), dispatch_span(101.1)]
    assert reader("read_fallback_pct").read(ctx(quiet)) is None
    assert reader("read_slot_overflow_pct").read(ctx(quiet)) is None


def test_compile_ms_in_window_sums_the_logs_entries_and_names_them(capsys):
    log = [(99.0, 99.5, "jit_before", "warm", "miss"),
           (101.0, 101.25, "jit_quorum_step_impl", "tpuquorum", "hit"),
           (140.0, 140.5, "jit__gather_rows", "tpuquorum", "miss"),
           (148.0, 149.0, "jit_after", "tpuquorum", "miss")]
    mod = reader("compile_ms_in_window")
    assert mod.read(ctx(), log=lambda: log) == pytest.approx(750.0)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [(ln["program"], ln["thread"], ln["cache"], ln["ms"])
            for ln in lines] == [
        ("jit_quorum_step_impl", "tpuquorum", "hit", 250.0),
        ("jit__gather_rows", "tpuquorum", "miss", 500.0)]
    assert mod.read(ctx(), log=lambda: []) == 0.0  # it has to read 0


def test_program_timeouts_per_kop_reads_every_live_tracer():
    def tracer(events, by_second):
        return SimpleNamespace(outcomes=lambda: {
            "counts": {}, "events": events, "by_second": by_second})

    a = tracer(
        [(99.0, "read", "TIMEOUT"), (101.0, "read", "TIMEOUT"),
         (102.0, "propose", "TIMEOUT"), (103.0, "read", "DROPPED"),
         (149.0, "read", "TIMEOUT")],
        {99: {("read", "COMPLETED"): 500},
         101: {("read", "COMPLETED"): 90, ("read", "TIMEOUT"): 1,
               ("propose", "COMPLETED"): 300},
         103: {("read", "DROPPED"): 1}},
    )
    b = tracer([(120.0, "read", "TIMEOUT")],
               {120: {("read", "TIMEOUT"): 1, ("read", "COMPLETED"): 7},
                148: {("read", "COMPLETED"): 400}})
    mod = reader("program_timeouts_per_kop")
    # two timeouts in the window over 90 + 1 + 1 + 1 + 7 read attempts
    assert mod.read(ctx(), tracers=[a, b]) == pytest.approx(2000.0 / 100)
    assert mod.read(ctx(), tracers=[]) is None
    assert mod.read(ctx(), tracers=[tracer([], {})]) is None
