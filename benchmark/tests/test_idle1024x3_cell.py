"""``idle1024x3.sparse11`` (ISSUE 44) resolves from the names in
``BENCHMARK.json`` alone: its configuration (``ladder1024x3`` plus
``Config.quiesce`` and a 100 ms tick), the reference beside it with the
same six exact limits, its traffic file, the end-to-end metrics it
reports, a reader for every per-layer metric it inherits or brings; and
the six readers it brings, over hand-made spans, requests and counters.
Adds nothing and runs nothing.  (The two controls fail under this cell's
traffic at a small size in ``test_control.py``, which takes its cells from
``BENCHMARK.json``.)"""
import json
import math
import os
import shutil
from types import SimpleNamespace

import pytest

from benchmark import cluster as cl, run as harness
from benchmark.generator import READ, WRITE, open_schedule
from benchmark.layers import quiesce_plane as qp

HERE = os.path.dirname(os.path.abspath(__file__))
LAYERS = os.path.join(os.path.dirname(HERE), "layers")
CELL = "idle1024x3.sparse11"
PLAIN = "ladder1024x3.mixed91"
NEW = ("quiesced_rows_pct.lat", "woke_ops_pct.lat", "wake_ms.lat",
       "wake_read_ms.read", "wake_elections_per_kop.lat",
       "scalar_ticks_per_s.lat")
#: the keys in which the configuration differs from ``ladder1024x3``
DIFFERS = {"name", "source", "deployment", "group_config", "guarantees",
           "assumed", "reduced", "reference"}
T0, T_END = 100.0, 148.0


@pytest.fixture(params=["as_committed", "with_later_additions"])
def root(request, tmp_path):
    """The repo, and a copy to which a later PR has added a cell and a
    per-layer metric as entries only."""
    if request.param == "as_committed":
        return harness.ROOT
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(harness.ROOT, "benchmark", sub),
                        tmp_path / "benchmark" / sub)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    bench["workloads"].append({
        "name": "idle1024x3.write_closed", "config": "idle1024x3",
        "traffic": "write_closed_x1", "chips": 1, "why": "a later cell"})
    bench["per_layer"].append({
        "name": "gen_late_ms.read", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "generator", "moves": "read_p50_ms"})
    for m in bench["end_to_end"]:
        if "ladder1024x3.write_closed" in m.get("workloads", ()):
            m["workloads"].append("idle1024x3.write_closed")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_the_cell_resolves_by_name(root):
    cell = harness.Cell(CELL, root=root)
    old = harness.Cell(PLAIN, root=root)
    assert (cell.entry["chips"], cell.entry["traffic"]) == (1, "sparse11")
    assert 1 <= len(cell.entry["why"]) <= 200
    t = cell.traffic
    assert set(t) == set(old.traffic)  # the generator's keys, no new one
    assert (t["loop"], t["rate_ops_per_s"], t["read_share"],
            t["keys_per_group"], t["read_host"], t["read_newest_share"],
            t["attempt_timeout_s"], t["deadline_s"], t["warmup_s"]) == (
        "open", 25.0, 0.5, 4, "any", 0.5, 5.0, 30.0, 12.0)
    # the arithmetic of the rate: groups / (4 x threshold_s), rounded down
    conf = cell.config
    threshold_s = 10 * conf["assumed"]["election_rtt"] * (
        conf["assumed"]["rtt_millisecond"] / 1000.0)
    assert threshold_s == 10.0 and t["warmup_s"] > threshold_s
    assert t["rate_ops_per_s"] == math.floor(
        conf["groups"] / (4 * threshold_s))
    gap_s = conf["groups"] / t["rate_ops_per_s"]
    assert 0.77 < math.exp(-threshold_s / gap_s) < 0.79
    # the reference: ladder1024x3's six limits, every one exact
    assert cell.reference.LIMITS == old.reference.LIMITS
    assert set(cell.reference.LIMITS.values()) == {0}
    assert len(cell.reference.LIMITS) == 6
    ref = cell.reference.cluster(cell.config, 1)
    assert len(ref.cids) == 1024 and ref.replicas == 3
    e2e = [m["name"] for m in cell.metrics("end_to_end")]
    assert e2e == ["write_p50_ms", "read_p50_ms", "setup_s"]


def test_every_seed_gets_the_same_work(root):
    cell = harness.Cell(CELL, root=root)
    cids = list(range(1, 1025))
    seen = None
    for seed in (1, 2**31 + 7):
        sched = open_schedule(cell.traffic, cids, seed, 48.0, 12.0)
        window = [s for s in sched if s[0] >= 0]
        counts = (len(sched), len(window),
                  sum(1 for s in window if s[1] == WRITE),
                  sum(1 for s in window if s[1] == READ))
        assert counts == (1500, 1200, 600, 600)
        assert seen in (None, counts)
        seen = counts


def test_the_configuration_is_ladder1024x3_plus_quiesce(root):
    conf = harness.Cell(CELL, root=root).config
    old = harness.Cell(PLAIN, root=root).config
    assert set(conf) - set(old) == {"group_config"}
    assert {k for k in old if conf[k] != old[k]} == DIFFERS - {"group_config"}
    assert conf["group_config"] == {"quiesce": True}
    assert (conf["groups"], conf["replicas"], conf["payload_bytes"],
            conf["fsync"]) == (1024, 3, 16, True)
    g, og = conf["guarantees"], old["guarantees"]
    assert {k: g[k] for k in og} == og  # the four, word for word
    assert set(g) - set(og) == {"quiesce"}
    assert "lose no acknowledged write" in g["quiesce"]
    a, oa = conf["assumed"], old["assumed"]
    assert set(a) - set(oa) == {"quiesce", "quiesce_threshold", "idle_set",
                                "mix"}
    assert {k for k in oa if a[k] != oa[k]} == {"rtt_millisecond",
                                                "failure_detection"}
    assert (a["rtt_millisecond"], a["election_rtt"], a["heartbeat_rtt"],
            a["engine_block_groups"]) == (100, 10, 1, 1024)
    assert "100 ticks = 10 s" in a["quiesce_threshold"]
    assert "from the wake" in a["failure_detection"]
    assert "as recalled" in conf["source"] and len(conf["source"]) <= 200
    assert "Config.Quiesce" in conf["source"]
    assert set(conf["reduced"]) == {"servers", "groups"}
    assert conf["reduced"]["servers"] == old["reduced"]["servers"]
    assert "16,384 -> 1,024" in conf["reduced"]["groups"]
    entry = next(c for c in harness.load_json(root, "BENCHMARK.json")[
        "configs"] if c["name"] == "idle1024x3")
    assert entry["reduced"] == list(conf["reduced"])
    assert entry["source"] == conf["source"]
    assert entry["file"] == "benchmark/configs/idle1024x3.json"


def test_every_replicas_config_carries_quiesce(root):
    from dragonboat_tpu import Config

    conf = harness.Cell(CELL, root=root).config
    settings = cl.group_config(conf)
    assert settings == {"election_rtt": 10, "heartbeat_rtt": 1,
                        "quiesce": True}
    c = Config(cluster_id=1, node_id=1, **settings)
    c.validate()
    assert c.quiesce and not c.read_lease and not c.check_quorum
    with pytest.raises(Exception):  # the lease stays refused beside it
        Config(cluster_id=1, node_id=1, check_quorum=True, read_lease=True,
               **settings).validate()
    assert cl.group_config(harness.Cell(PLAIN, root=root).config) == {
        "election_rtt": 10, "heartbeat_rtt": 1}


def test_the_metrics_reported(root):
    cell = harness.Cell(CELL, root=root)
    old = harness.Cell(PLAIN, root=root)
    names = {m["name"] for m, mod in cell.readers() if callable(mod.read)}
    inherited = {m["name"] for m in old.metrics("per_layer")}
    # everything the all-awake cell at this scale reports, and the six
    assert names == inherited | set(NEW)
    assert not inherited & set(NEW)
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    for n in NEW:
        m = by_name[n]
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("read_p50_ms" if n.endswith(".read")
                              else "write_p50_ms")
        assert os.path.exists(os.path.join(
            root, "benchmark", "layers", n.split(".", 1)[0] + ".py"))
    assert by_name["quiesced_rows_pct.lat"]["layer"] == "coordinator round"
    assert by_name["scalar_ticks_per_s.lat"]["source"] == "program_counter"
    # no share of a roofline or of a peak comes with the cell: no new kernel
    assert not any("roofline" in n or "mfu" in n for n in NEW)
    # no other configuration's cell reports the six
    for w in cell.bench["workloads"]:
        if w["config"] != "idle1024x3":
            other = {m["name"] for m in harness.Cell(
                w["name"], root=root).metrics("per_layer")}
            assert not other & set(NEW), w["name"]
    # the lease cell's three stay the lease cell's
    for n in ("lease_read_pct.read", "lease_remaining_ticks.read",
              "hb_block_pct.read"):
        assert n not in names


def test_the_additions_edit_no_entry_that_was_there(root):
    bench = harness.Cell(CELL, root=root).bench
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:7] == [
        "upstream48x3.write_closed", "upstream48x3.mixed91",
        "ladder1024x3.write_closed", "ladder512x5.mixed91",
        "ladder1024x3.mixed91", "upstream48x3snap.write_closed",
        "upstream48x3lease.lease91"]
    assert [c["name"] for c in bench["configs"]][:5] == [
        "upstream48x3", "ladder1024x3", "ladder512x5", "upstream48x3snap",
        "upstream48x3lease"]
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert bounds == {"ops_per_s": 0.15, "write_p50_ms": 0.25,
                      "read_p50_ms": 0.25, "setup_s": 0.25}
    for name in ("write_p50_ms", "read_p50_ms"):
        m = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert m["workloads"][:5] == [
            "upstream48x3.mixed91", "ladder512x5.mixed91",
            "ladder1024x3.mixed91", "upstream48x3lease.lease91", CELL]
    assert bench["run_seconds"] == 48
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    for w in bench["workloads"] + bench["configs"]:
        assert 1 <= len(w["why"]) <= 200 and len(w.get("source", "x")) <= 200
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536


# ---- the six readers, over hand-made spans, requests and counters ---------


def reader(family):
    return harness.load_module(os.path.join(LAYERS, family + ".py"),
                               "test_layer_" + family)


def wake(op, t0, ms, woke, elected=False, outcome="completed"):
    return {"kind": "quiesce_wake", "host": "h1:1", "t0": t0,
            "t1": t0 + ms / 1e3, "wake_ms": ms, "op": op, "woke": woke,
            "elected": elected, "cluster_id": 7, "outcome": outcome}


def rounds(*asleep, rows=1024):
    return [{"kind": "coord_round", "host": "h1:1", "t0": T0 + 1 + i,
             "t1": T0 + 1.01 + i, "wall_ms": 10.0, "rows": rows,
             "rows_quiesced": n, "quiesce_enters": 0, "quiesce_wakes": 0}
            for i, n in enumerate(asleep)]


def request(t0):
    return SimpleNamespace(t0=t0, woke=None)


def ctx(spans, requests):
    c = SimpleNamespace(
        outcome=SimpleNamespace(t0=T0, t_end=T_END), seconds=T_END - T0,
        percentile=harness.reduce.percentile)
    by_kind = {}
    for s in spans or ():
        if s is not None and s["kind"] == "coord_round":
            by_kind.setdefault("coord_round", []).append(s)
    c._program_spans = by_kind
    c._quiesce_plane = None
    got = qp.select(c, spans=lambda: spans, requests=lambda: requests)
    c._quiesce_plane = got
    return c


def window():
    """Ten sampled operations in the window, eight of which found their
    group asleep (five writes, three reads; one read's wake elected), one
    wake and one request before the window."""
    spans = [wake("write", T0 + 1, 30.0, "leader"),
             wake("write", T0 + 2, 50.0, "leader"),
             wake("write", T0 + 3, 40.0, "leader"),
             wake("write", T0 + 4, 90.0, "leader"),
             wake("write", T0 + 5, 20.0, "leader"),
             wake("read", T0 + 6, 120.0, "follower"),
             wake("read", T0 + 7, 80.0, "leader"),
             wake("read", T0 + 8, 900.0, "follower", elected=True),
             wake("write", T0 - 1, 10.0, "leader"),
             None] + rounds(700, 800, 900)
    requests = [request(T0 + i + 0.5) for i in range(10)] + [request(T0 - 2)]
    return spans, requests


def test_the_wake_readers(capsys):
    c = ctx(*window())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == {"event": "quiesce_plane", "sampled_ops": 10, "wakes": 8,
                    "by_op_and_role": {"write:leader": 5, "read:follower": 2,
                                       "read:leader": 1},
                    "elected": 1, "not_completed": 0}
    assert reader("woke_ops_pct").read(c) == pytest.approx(80.0)
    assert reader("wake_ms").read(c) == 40.0          # 20 30 40 50 90
    assert reader("wake_read_ms").read(c) == 120.0    # 80 120 900
    assert reader("wake_elections_per_kop").read(c) == pytest.approx(100.0)
    assert reader("quiesced_rows_pct").read(c) == pytest.approx(
        100.0 * 800 / 1024)


def test_scalar_ticks_are_the_windows_seconds(monkeypatch):
    class Plane:
        def __init__(self, by_sec):
            self.by_sec = by_sec

        def window(self, lo, hi):
            return {"scalar_ticks": sum(
                n for s, n in self.by_sec.items() if lo <= s + 0.5 < hi)}

    planes = [Plane({99: 7, 100: 480, 147: 480, 148: 9}), Plane({120: 480})]
    monkeypatch.setattr(qp, "replicas", lambda: planes)
    c = ctx([], [])
    assert reader("scalar_ticks_per_s").read(c) == pytest.approx(1440 / 48)
    monkeypatch.setattr(qp, "replicas", lambda: [Plane({})])
    assert reader("scalar_ticks_per_s").read(c) == 0.0


@pytest.mark.parametrize("family", [n.split(".", 1)[0] for n in NEW])
@pytest.mark.parametrize("program", ["parent", "no_ring"])
def test_readers_return_none_where_nothing_says_sleep(family, program,
                                                      monkeypatch):
    """The parent on this cell's files: rounds without ``rows_quiesced``,
    no ``quiesce_wake`` span, requests that note no wake, no tick counter."""
    monkeypatch.setattr(qp, "replicas", lambda: None)
    if program == "parent":
        spans = [{"kind": "coord_round", "host": "h1:1", "t0": T0 + 1,
                  "t1": T0 + 1.01, "wall_ms": 10.0, "rows": 1024}]
        c = ctx(spans, None)
    else:
        c = ctx(None, None)
    assert reader(family).read(c) is None


def test_the_program_says_what_the_readers_ask():
    """The names the readers look for are the program's."""
    from dragonboat_tpu.obs import instruments, trace

    assert "woke" in trace.Trace.__slots__
    assert hasattr(instruments.ReplicaObs, "scalar_ticks")
    assert hasattr(instruments.CoordObs, "quiesce_wake")
    assert qp.sampled() is not None and qp.replicas() is not None
