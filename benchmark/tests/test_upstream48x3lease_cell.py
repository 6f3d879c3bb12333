"""``upstream48x3lease.lease91`` (ISSUE 41) resolves from the names in
``BENCHMARK.json`` alone: its configuration (``upstream48x3`` plus the lease
block), the reference beside it, its traffic file (``mixed91.json`` with the
reads at the leader and the cell's own rate), the end-to-end metrics it
reports, a reader for every per-layer metric it inherits or brings; and the
two readers it brings, over hand-made spans.  Adds nothing and runs
nothing."""
import json
import os
import shutil

import pytest

from benchmark import cluster as cl, run as harness
from benchmark.layers import read_legs as rl
from test_read_legs import T0, ctx, reader, recorded, span, trace

CELL = "upstream48x3lease.lease91"
PLAIN = "upstream48x3.mixed91"
NEW = ("lease_read_pct.read", "lease_remaining_ticks.read",
       "hb_block_pct.read")
SETTINGS = {"check_quorum": True, "read_lease": True}
#: the keys in which the configuration may differ from ``upstream48x3``
DIFFERS = {"name", "source", "group_config", "guarantees", "assumed",
           "reference"}
#: accepted ``.read`` metrics whose readers find nothing where every read is
#: answered under a lease at its leader (no echo, no round, no forwarding;
#: ``read_leader_ms`` finds nothing on a parent that writes no span for a
#: leased read, and 0.02 ms on the change: the ``read_legs`` line has it):
#: they list the three cells that had them
SILENT = ("read_fallback_pct.read", "read_slot_overflow_pct.read",
          "read_forwarded_pct.read", "read_forward_ms.read",
          "read_echo_trip_ms.read", "read_echo_wait_ms.read",
          "read_confirm_ms.read", "read_release_ms.read",
          "read_answer_ms.read", "read_leader_ms.read",
          "read_rounds_per_ctx.read")
OLDER = ["upstream48x3.mixed91", "ladder512x5.mixed91",
         "ladder1024x3.mixed91"]


@pytest.fixture(params=["as_committed", "with_later_additions"])
def root(request, tmp_path):
    """The repo, and a copy to which a later PR has added a cell and a
    per-layer metric as entries only: these tests hold this cell, and pass
    whatever is appended beside it."""
    if request.param == "as_committed":
        return harness.ROOT
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(harness.ROOT, "benchmark", sub),
                        tmp_path / "benchmark" / sub)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    bench["workloads"].append({
        "name": "upstream48x3lease.write_closed",
        "config": "upstream48x3lease", "traffic": "write_closed",
        "chips": 1, "why": "a later cell"})
    bench["per_layer"].append({
        "name": "gen_late_ms.read", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "generator", "moves": "read_p50_ms"})
    for m in bench["end_to_end"]:
        if "upstream48x3.write_closed" in m.get("workloads", ()):
            m["workloads"].append("upstream48x3lease.write_closed")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_the_cell_resolves_by_name(root):
    cell = harness.Cell(CELL, root=root)
    old = harness.Cell(PLAIN, root=root)
    assert (cell.entry["chips"], cell.entry["traffic"]) == (1, "lease91")
    # mixed91.json with two keys changed (and the sentence that says why)
    changed = {k for k in old.traffic if cell.traffic[k] != old.traffic[k]}
    assert set(cell.traffic) == set(old.traffic)
    assert changed == {"read_host", "rate_ops_per_s", "why"}
    assert cell.traffic["read_host"] == "leader"
    rate = cell.traffic["rate_ops_per_s"]
    assert rate > 0 and rate % 50 == 0  # of the knee, down to a multiple
    assert f"{int(rate):d} ops/s" in cell.entry["why"]
    # the reference: every limit exact, upstream48x3's own size
    assert set(cell.reference.LIMITS.values()) == {0}
    assert cell.reference.LIMITS == old.reference.LIMITS
    assert len(cell.reference.LIMITS) == 6
    ref = cell.reference.cluster(cell.config, 1)
    assert len(ref.cids) == 48 and ref.replicas == 3
    e2e = [m["name"] for m in cell.metrics("end_to_end")]
    assert e2e == ["write_p50_ms", "read_p50_ms", "setup_s"]


def test_the_configuration_is_upstream48x3_plus_the_block(root):
    conf = harness.Cell(CELL, root=root).config
    old = harness.Cell(PLAIN, root=root).config
    snap = harness.load_json(root, "benchmark", "configs",
                             "upstream48x3snap.json")
    assert set(conf) - set(old) == {"group_config"}
    assert {k for k in old if conf[k] != old[k]} == DIFFERS - {"group_config"}
    assert conf["group_config"] == SETTINGS
    # upstream48x3's guarantees with ``read`` restated, and CheckQuorum's
    # sentence as upstream48x3snap has it
    g, og = conf["guarantees"], old["guarantees"]
    assert {k: g[k] for k in og if k != "read"} == {
        k: og[k] for k in og if k != "read"}
    assert set(g) - set(og) == {"check_quorum"}
    assert g["check_quorum"] == snap["guarantees"]["check_quorum"]
    assert g["read"].startswith("linearizable: answered by the leader under "
                                "a lease")
    assert "ReadIndex whenever the lease is not valid" in g["read"]
    assert "wall time" in g["read"]
    # the clocks, the engine and the rest of 'assumed' are upstream48x3's
    assert {k: conf["assumed"][k] for k in old["assumed"]} == old["assumed"]
    assert set(conf["assumed"]) - set(old["assumed"]) == {
        "read_lease", "check_quorum", "lease", "clocks"}
    assert "as recalled" in conf["assumed"]["read_lease"]
    assert "8 ticks" in conf["assumed"]["lease"]
    assert "as recalled" in conf["source"] and len(conf["source"]) <= 200
    assert "ReadOnlyLeaseBased" in conf["source"]
    assert "6.4.1" in conf["source"]
    # reduced: upstream48x3's cut and nothing else
    assert conf["reduced"] == old["reduced"]
    assert set(conf["reduced"]) == {"servers"}
    entry = next(c for c in harness.load_json(root, "BENCHMARK.json")[
        "configs"] if c["name"] == "upstream48x3lease")
    assert entry["reduced"] == list(conf["reduced"])
    assert entry["source"] == conf["source"]
    assert entry["file"] == "benchmark/configs/upstream48x3lease.json"


def test_every_replicas_config_carries_the_lease_and_check_quorum(root):
    from dragonboat_tpu import Config

    conf = harness.Cell(CELL, root=root).config
    settings = cl.group_config(conf)
    built = [Config(cluster_id=cid, node_id=i, **settings)
             for cid in range(1, conf["groups"] + 1)
             for i in range(1, conf["replicas"] + 1)]
    assert len(built) == 144
    for c in built:
        c.validate()
        assert (c.read_lease, c.check_quorum, c.election_rtt,
                c.heartbeat_rtt) == (True, True, 10, 1)
    # the wall guard is no field a configuration could leave off
    from dragonboat_tpu import NodeHostConfig

    assert not hasattr(NodeHostConfig(), "lease_wall_guard")
    # and upstream48x3's own cells still build what they built
    assert cl.group_config(harness.Cell(PLAIN, root=root).config) == {
        "election_rtt": 10, "heartbeat_rtt": 1}


def test_the_metrics_reported(root):
    cell = harness.Cell(CELL, root=root)
    old = harness.Cell(PLAIN, root=root)
    names = {m["name"] for m, mod in cell.readers() if callable(mod.read)}
    inherited = {m["name"] for m in old.metrics("per_layer")}
    assert names == (inherited - set(SILENT)) | set(NEW)
    assert not inherited & set(NEW)  # the older cells' lines do not change
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    for n in NEW:
        m = by_name[n]
        assert m["workloads"] == [CELL] or CELL in m["workloads"]
        assert m["moves"] == "read_p50_ms" and m["better"] == "higher"
        family = n.split(".", 1)[0]
        assert os.path.exists(os.path.join(
            root, "benchmark", "layers", family + ".py"))
    assert by_name["lease_read_pct.read"]["layer"] == "raft step, WAL, apply"
    assert by_name["hb_block_pct.read"]["layer"] == "coordinator round"
    assert by_name["hb_block_pct.read"]["source"] == "program_counter"
    for n in ("quorum_step_roofline.lat", "device_idle_pct.lat",
              "compiles_in_window.lat", "leader_changes.lat",
              "read_submit_wait_ms.read",
              "program_timeouts_per_kop.read", "read_p95_ms.obs",
              "write_p95_ms.obs"):
        assert n in names
    # what falls silent here is read where it was read, and nowhere else
    for n in SILENT:
        assert by_name[n]["workloads"][:3] == OLDER
        assert n in inherited
    # no other cell reports the three
    for w in cell.bench["workloads"]:
        if w["config"] != "upstream48x3lease":
            other = {m["name"] for m in harness.Cell(
                w["name"], root=root).metrics("per_layer")}
            assert not other & set(NEW), w["name"]


def test_the_additions_edit_no_entry_that_was_there(root):
    """Membership, not position or count: the six older cells, the four
    older configurations and the bounds are there as they were, and
    whatever a later PR appends passes."""
    bench = harness.Cell(CELL, root=root).bench
    cells = {w["name"]: w for w in bench["workloads"]}
    for name, config, traffic in (
            ("upstream48x3.write_closed", "upstream48x3", "write_closed"),
            ("upstream48x3.mixed91", "upstream48x3", "mixed91"),
            ("ladder1024x3.write_closed", "ladder1024x3", "write_closed_x1"),
            ("ladder512x5.mixed91", "ladder512x5", "mixed91_x5"),
            ("ladder1024x3.mixed91", "ladder1024x3", "mixed91_g1024"),
            ("upstream48x3snap.write_closed", "upstream48x3snap",
             "write_closed")):
        assert (cells[name]["config"], cells[name]["traffic"],
                cells[name]["chips"]) == (config, traffic, 1)
    assert [c["name"] for c in bench["configs"]][:4] == [
        "upstream48x3", "ladder1024x3", "ladder512x5", "upstream48x3snap"]
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert bounds == {"ops_per_s": 0.15, "write_p50_ms": 0.25,
                      "read_p50_ms": 0.25, "setup_s": 0.25}
    for name in ("write_p50_ms", "read_p50_ms"):
        m = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert m["workloads"][:3] == OLDER and CELL in m["workloads"]
    ops = next(m for m in bench["end_to_end"] if m["name"] == "ops_per_s")
    assert CELL not in ops["workloads"]
    assert bench["run_seconds"] == 48
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    for w in bench["workloads"] + bench["configs"]:
        assert 1 <= len(w["why"]) <= 200 and len(w.get("source", "x")) <= 200
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536
    # the other mixed91 traffic files are what they were
    assert harness.load_json(root, "benchmark", "traffic", "mixed91.json")[
        "read_host"] == "any"


# ---- the two readers, over hand-made spans --------------------------------


def leased(cid, low, a_s, remaining, origin="local"):
    """A ``read_ctx`` span of a read answered under the lease: the accept,
    the answer 20 us later, what ``lease.check`` returned, no chain."""
    return {"kind": "read_ctx", "host": "h1:1", "cluster_id": cid,
            "low": low, "high": 1, "t0": a_s, "t1": a_s + 2e-5,
            "origin": origin, "path": "lease", "tid": low,
            "trace_origin": "h1:1", "echoes": 0, "leader_ms": 0.02,
            "remaining_ticks": remaining}


def lease_window():
    """Five leased reads and one that found the lease not valid inside the
    window, one leased read before it."""
    spans = [leased(7, 1, T0 + 1, 8), leased(7, 2, T0 + 2, 7),
             leased(8, 3, T0 + 3, 8), leased(9, 4, T0 + 4, 3),
             leased(9, 5, T0 + 5, 8),
             span(8, 6, T0 + 6, (4.0, 6.0, 10.0, 1.0), origin="local",
                  lease_fallback=True),
             leased(7, 9, T0 - 1, 1)]
    traces = [trace(cid, low, T0 + i + 0.9995, (0.1, 0.5, 0.6, 0.7, 0.8),
                    origin="local")
              for i, (cid, low) in enumerate(
                  ((7, 1), (7, 2), (8, 3), (9, 4), (9, 5), (8, 6)))]
    return spans, traces


def test_lease_read_pct_is_the_share_of_the_windows_spans(capsys):
    c = ctx(*lease_window())
    capsys.readouterr()
    assert reader("lease_read_pct").read(c) == pytest.approx(100.0 * 5 / 6)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == {"event": "lease_reads", "leased": 5,
                    "not_leased": {"device:lease_fallback": 1}}


def test_lease_remaining_ticks_is_the_leased_spans_median():
    # 3, 7, 8, 8, 8: nearest rank, the third of five
    assert reader("lease_remaining_ticks").read(ctx(*lease_window())) == 8


def test_the_join_finds_every_leased_read_its_span(capsys):
    ctx(*lease_window())
    line = json.loads(capsys.readouterr().out.splitlines()[0])
    assert line["event"] == "read_legs"
    assert line["joined_pct_local"] == 100.0
    assert line["paths"] == {"lease": 5, "device": 1}
    assert line["local/lease"]["n"] == 5
    assert line["local/lease"]["leader_ms"] == pytest.approx(0.02)
    assert not set(rl.CHAIN) & set(line["local/lease"])


@pytest.mark.parametrize("family", ["lease_read_pct",
                                    "lease_remaining_ticks"])
@pytest.mark.parametrize("program", ["parent", "no_lease_group", "no_ring"])
def test_lease_readers_return_none_where_nothing_says_lease(family, program):
    if program == "parent":
        # the parent on this cell: a leased read leaves no span, a read
        # that fell back leaves today's, which does not say why
        spans, traces = lease_window()
        spans = [dict(s, lease_fallback=None) for s in spans
                 if s.get("path") != "lease"]
    elif program == "no_lease_group":
        spans, traces = recorded()  # ReadIndex contexts of a plain group
    else:
        spans, traces = None, None
    assert reader(family).read(ctx(spans, traces)) is None
