"""The nine ``read_*.read`` readers and their shared selection
(``layers/read_legs.py``, ISSUE 39) on a hand-made ring and trace list: the
numbers, the earlier line whose means add up, and ``None`` from every reader
on a program that writes no ``read_ctx`` span and follows no context (the
parent commit, the plain reference in its place)."""
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import run as harness
from benchmark.layers import read_legs as rl

HERE = os.path.dirname(os.path.abspath(__file__))
LAYERS = os.path.join(os.path.dirname(HERE), "layers")
T0, T_END = 100.0, 148.0
FAMILIES = ("read_submit_wait_ms", "read_forward_ms", "read_echo_trip_ms",
            "read_echo_wait_ms", "read_confirm_ms", "read_release_ms",
            "read_answer_ms", "read_leader_ms", "read_rounds_per_ctx")


def reader(family):
    return harness.load_module(os.path.join(LAYERS, family + ".py"),
                               "test_layer_" + family)


def span(cid, low, a_s, legs, origin="remote", path="device", rounds=2,
         **more):
    """A released ``read_ctx`` span accepted at ``a_s`` with the chain
    ``legs`` (ms)."""
    leader = sum(legs)
    s = {"kind": "read_ctx", "host": "h1:1", "cluster_id": cid, "low": low,
         "high": 1, "t0": a_s, "t1": a_s + leader / 1e3, "origin": origin,
         "path": path, "tid": low, "trace_origin": "h2:1", "echoes": 1,
         "leader_ms": leader, "rounds": rounds, "stage_wait_ms": 1.0,
         "first_echo_ms": legs[0]}
    s.update(zip(rl.CHAIN, legs))
    s.update(more)
    return s


def trace(cid, low, t0_s, stamps_ms, origin="forwarded", kind="read",
          outcome="completed", followed=True):
    """A finished sampled request: ``stamps_ms`` after ``t0_s`` for
    ingress, raft_step, read_confirm, apply, egress."""
    names = ("ingress", "raft_step", "read_confirm", "apply", "egress")
    t = SimpleNamespace(
        kind=kind, done=True, outcome=outcome, t0=t0_s, cluster_id=cid,
        events=[["propose", t0_s, "gen"]] + [
            [n, t0_s + ms / 1e3, "w"] for n, ms in zip(names, stamps_ms)])
    if followed:
        t.read_ctx = (low, 1)
        t.read_origin = origin
    return t


#: three forwarded reads and one local inside the window, with their spans;
#: a forwarded read whose context was dropped at the leader; a write; a read
#: submitted before the window
def recorded():
    spans = [
        # accepted 3 ms after the requester's raft_step (1 ms)
        span(7, 1, 110.004, (4.0, 6.0, 10.0, 100.0)),
        span(7, 2, 120.006, (6.0, 8.0, 12.0, 60.0), rounds=3),
        span(8, 3, 130.005, (5.0, 7.0, 11.0, 80.0)),
        span(8, 4, 140.0015, (2.0, 3.0, 4.0, 50.0), origin="local",
             rounds=1),
        {"kind": "read_ctx", "host": "h1:1", "cluster_id": 9, "low": 5,
         "high": 1, "t0": 141.0, "t1": 141.5, "origin": "remote",
         "path": "dropped", "tid": 5, "trace_origin": "h2:1", "echoes": 0},
        span(7, 6, 99.0, (1.0, 1.0, 1.0, 1.0)),  # before the window
        {"kind": "coord_round", "t0": 111.0, "t1": 111.1, "wall_ms": 100.0},
        None,
    ]
    traces = [
        # ingress 0.5, raft_step 1.0; answered 2 ms after the release
        trace(7, 1, 110.0, (0.5, 1.0, 126.0, 126.5, 127.0)),
        trace(7, 2, 120.0, (0.5, 3.0, 95.0, 95.2, 96.0)),
        trace(8, 3, 130.0, (0.5, 2.0, 112.0, 112.5, 113.0)),
        trace(8, 4, 140.0, (0.5, 1.0, 62.5, 62.7, 63.0), origin="local"),
        trace(9, 5, 141.0, (0.5, 1.0, 900.0, 900.5, 901.0)),  # via a retry
        trace(7, 9, 142.0, (0.5, 1.0, 20.0, 20.5, 21.0), kind="write"),
        trace(7, 6, 98.9, (0.5, 1.0, 105.0, 105.5, 106.0)),
        trace(7, 8, 143.0, (0.5, 1.0, 5.0, 5.5, 6.0), outcome="timeout"),
    ]
    return spans, traces


def ctx(spans, traces, capsys=None):
    c = harness.Ctx(
        outcome=SimpleNamespace(t0=T0, t_end=T_END,
                                lat={harness.READ: [0.1, 0.2]}),
        seconds=T_END - T0)
    rl.select(c, spans=lambda: spans, traces=lambda: traces)
    return c


EXPECTED = {  # nearest rank: the third of four, the second of three
    # every followed read of the window: 0.5, 2.5, 1.5, 0.5, 0.5
    "read_submit_wait_ms": 0.5,
    # forwarded and joined: 3.0, 3.0, 3.0
    "read_forward_ms": 3.0,
    # the window's four released spans
    "read_echo_trip_ms": 5.0,      # 2, 4, 5, 6
    "read_echo_wait_ms": 7.0,
    "read_confirm_ms": 11.0,
    "read_release_ms": 80.0,       # 50, 60, 80, 100
    "read_answer_ms": 3.0,         # 2.0, 3.0, 4.0
    "read_leader_ms": 103.0,       # 59, 86, 103, 120
    "read_rounds_per_ctx": 2,
}


@pytest.mark.parametrize("family", FAMILIES)
def test_reader_reads_the_windows_contexts(family):
    assert reader(family).read(ctx(*recorded())) == pytest.approx(
        EXPECTED[family], abs=1e-6)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("program", ["parent", "no_ring", "outside"])
def test_reader_returns_none_where_no_context_is_followed(family, program,
                                                          capsys):
    spans, traces = recorded()
    if program == "parent":   # stamps, but no read_ctx and no span
        spans = [s for s in spans if s is None or s["kind"] != "read_ctx"]
        traces = [trace(7, 1, 110.0, (0.5, 1.0, 126.0, 126.5, 127.0),
                        followed=False)]
    elif program == "no_ring":  # the plain reference in the program's place
        spans, traces = None, None
    else:                     # everything lies outside the window
        spans = [s for s in spans if s and s.get("low") == 6]
        traces = [t for t in traces if t.t0 < T0]
    assert reader(family).read(ctx(spans, traces)) is None
    assert "read_legs" not in capsys.readouterr().out


def test_the_earlier_lines_means_add_up(capsys):
    ctx(*recorded())
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["event"] for l in lines] == ["read_legs"]
    line = lines[0]
    assert line["spans_in_window"] == 4 and line["sampled_reads"] == 5
    assert line["paths"] == {"device": 4}
    fwd = line["forwarded/device"]
    assert fwd["n"] == 3
    assert fwd["whole_ms"] == pytest.approx((127.0 + 96.0 + 113.0) / 3)
    assert fwd["release_ms"] == pytest.approx(80.0)
    assert sum(fwd[k] for k in rl.LEGS) == pytest.approx(fwd["whole_ms"],
                                                         abs=1e-3)
    assert line["unnamed_pct"] == pytest.approx(0.0, abs=0.01)
    assert line["chain_complete_pct"] == 100.0
    # three of the four forwarded reads found their span: the fourth's
    # context was dropped at the leader and a retry answered it
    assert line["joined_pct_forwarded"] == 75.0
    assert line["joined_pct_local"] == 100.0
    assert line["local/device"]["n"] == 1
    assert line["stage_wait_ms_p50"] == 1.0
    assert line["generator_read_mean_ms"] == pytest.approx(150.0)


def test_a_leg_whose_ends_were_not_seen_is_unnamed(capsys):
    spans, traces = recorded()
    for s in spans:
        if s and s.get("low") == 1:  # released by a later context's quorum
            del s["echo_wait_ms"], s["confirm_ms"]
    c = ctx(spans, traces)
    line = json.loads(capsys.readouterr().out.splitlines()[0])
    assert line["chain_complete_pct"] == pytest.approx(66.67, abs=0.01)
    assert line["unnamed_pct"] == pytest.approx(
        100.0 * 16.0 / (127.0 + 96.0 + 113.0), abs=0.01)
    # the medians take the spans that carry the leg
    assert reader("read_confirm_ms").read(c) == 11.0  # of 4, 11, 12
