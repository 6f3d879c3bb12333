"""``upstream48x3snap.write_closed`` (ISSUE 37) resolves from the names in
``BENCHMARK.json`` alone: its configuration with the documented group
settings, the reference beside it, ``upstream48x3.write_closed``'s traffic
file, the end-to-end metrics it reports, a reader for every per-layer
metric it inherits or brings.  Adds nothing and runs nothing."""
import json
import os
import shutil

import pytest

from benchmark import cluster as cl, run as harness

CELL = "upstream48x3snap.write_closed"
NEW = ("snapshot_save_ms.tput", "snapshot_sm_save_ms.tput",
       "snapshot_queue_ms.tput", "snapshot_compact_ms.tput",
       "snapshot_pool_busy_pct.tput", "snapshot_lag_x.tput",
       "snapshot_installs.tput")
SETTINGS = {"check_quorum": True, "snapshot_entries": 200,
            "compaction_overhead": 5}
#: the keys in which the configuration may differ from ``upstream48x3``
DIFFERS = {"name", "source", "group_config", "guarantees", "assumed",
           "reduced", "reference"}


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
        "name": "upstream48x3snap.mixed91", "config": "upstream48x3snap",
        "traffic": "mixed91", "chips": 1, "why": "a later cell"})
    bench["per_layer"].append({
        "name": "gen_late_ms.read", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "generator", "moves": "read_p50_ms"})
    for m in bench["end_to_end"]:
        if "upstream48x3.mixed91" in m.get("workloads", ()):
            m["workloads"].append("upstream48x3snap.mixed91")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_the_cell_resolves_by_name(root):
    cell = harness.Cell(CELL, root=root)
    old = harness.Cell("upstream48x3.write_closed", root=root)
    assert cell.entry["chips"] == 1
    assert cell.entry["traffic"] == old.entry["traffic"] == "write_closed"
    assert cell.traffic == old.traffic  # the one file, as it is
    assert (cell.traffic["loop"], cell.traffic["inflight_per_group"],
            cell.traffic["read_share"]) == ("closed", 8, 0.0)
    assert "keys_per_group" not in cell.traffic  # fresh keys
    # the reference: every limit exact, upstream48x3's own size
    assert set(cell.reference.LIMITS.values()) == {0}
    assert cell.reference.LIMITS == old.reference.LIMITS
    ref = cell.reference.cluster(cell.config, 1)
    assert len(ref.cids) == 48 and ref.replicas == 3
    e2e = [m["name"] for m in cell.metrics("end_to_end")]
    assert e2e == ["ops_per_s", "setup_s"]


def test_the_configuration_is_upstream48x3_plus_the_settings(root):
    conf = harness.Cell(CELL, root=root).config
    old = harness.Cell("upstream48x3.write_closed", root=root).config
    assert set(conf) - set(old) == {"group_config"}
    assert {k for k in old if conf[k] != old[k]} == DIFFERS - {"group_config"}
    assert conf["group_config"] == SETTINGS
    # upstream48x3's four guarantees word for word, and the two added
    assert {k: conf["guarantees"][k] for k in old["guarantees"]} == old[
        "guarantees"]
    added = set(conf["guarantees"]) - set(old["guarantees"])
    assert added == {"check_quorum", "snapshot"}
    assert "steps down (CheckQuorum)" in conf["guarantees"]["check_quorum"]
    assert "holds every acknowledged write" in conf["guarantees"]["snapshot"]
    # the clocks, the engine and the rest of 'assumed' are upstream48x3's;
    # the three settings are listed there as recalled
    assert {k: conf["assumed"][k] for k in old["assumed"]} == old["assumed"]
    assert set(conf["assumed"]) - set(old["assumed"]) == set(SETTINGS)
    assert all("as recalled" in conf["assumed"][k] for k in SETTINGS)
    assert "as recalled" in conf["source"] and len(conf["source"]) <= 200
    assert "helloworld/main.go" in conf["source"]
    assert "config/config.go:98-118" in conf["source"]
    # reduced: upstream48x3's cut and the cadence, with its rule
    assert conf["reduced"]["servers"] == old["reduced"]["servers"]
    assert set(conf["reduced"]) == {"servers", "snapshot_entries"}
    assert "10 -> 200" in conf["reduced"]["snapshot_entries"]
    entry = next(c for c in harness.load_json(root, "BENCHMARK.json")[
        "configs"] if c["name"] == "upstream48x3snap")
    assert entry["reduced"] == list(conf["reduced"])
    assert entry["source"] == conf["source"]
    assert entry["file"] == "benchmark/configs/upstream48x3snap.json"


def test_every_replicas_config_carries_the_three_values(root):
    from dragonboat_tpu import Config

    conf = harness.Cell(CELL, root=root).config
    settings = cl.group_config(conf)
    built = [Config(cluster_id=cid, node_id=i, **settings)
             for cid in range(1, conf["groups"] + 1)
             for i in range(1, conf["replicas"] + 1)]
    assert len(built) == 144
    for c in built:
        c.validate()
        assert (c.check_quorum, c.snapshot_entries, c.compaction_overhead,
                c.election_rtt, c.heartbeat_rtt) == (True, 200, 5, 10, 1)
    # and upstream48x3's own cells still build what they built
    plain = cl.group_config(harness.Cell(
        "upstream48x3.write_closed", root=root).config)
    assert plain == {"election_rtt": 10, "heartbeat_rtt": 1}


def test_the_metrics_reported(root):
    cell = harness.Cell(CELL, root=root)
    old = harness.Cell("upstream48x3.write_closed", root=root)
    names = {m["name"] for m, mod in cell.readers() if callable(mod.read)}
    inherited = {m["name"] for m in old.metrics("per_layer")}
    assert all(n.endswith(".tput") for n in names)
    assert names == inherited | set(NEW)
    assert not inherited & set(NEW)  # the older cell's line does not change
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    for n in NEW:
        m = by_name[n]
        assert m["workloads"] == [CELL] or CELL in m["workloads"]
        assert (m["layer"], m["moves"]) == ("snapshot, compaction",
                                            "ops_per_s")
        assert m["better"] == "lower"
        family = n.split(".", 1)[0]
        assert os.path.exists(os.path.join(
            root, "benchmark", "layers", family + ".py"))
    for n in ("quorum_step_roofline.tput", "kernel_us_per_dispatch.tput",
              "compiles_in_window.tput", "leader_changes.tput",
              "stage_apply_ms.tput", "hb_block_pct.tput"):
        assert n in names
    # no other cell reports the seven
    for w in cell.bench["workloads"]:
        if w["config"] != "upstream48x3snap":
            other = {m["name"] for m in harness.Cell(
                w["name"], root=root).metrics("per_layer")}
            assert not other & set(NEW), w["name"]


def test_the_additions_edit_no_entry_that_was_there(root):
    """Membership, not position or count: the five older cells, the three
    older configurations and the bounds are there as they were, and
    whatever a later PR appends passes."""
    bench = harness.Cell(CELL, root=root).bench
    cells = {w["name"]: w for w in bench["workloads"]}
    for name, config, traffic in (
            ("upstream48x3.write_closed", "upstream48x3", "write_closed"),
            ("upstream48x3.mixed91", "upstream48x3", "mixed91"),
            ("ladder1024x3.write_closed", "ladder1024x3", "write_closed_x1"),
            ("ladder512x5.mixed91", "ladder512x5", "mixed91_x5"),
            ("ladder1024x3.mixed91", "ladder1024x3", "mixed91_g1024")):
        assert (cells[name]["config"], cells[name]["traffic"],
                cells[name]["chips"]) == (config, traffic, 1)
    assert [c["name"] for c in bench["configs"]][:3] == [
        "upstream48x3", "ladder1024x3", "ladder512x5"]
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert bounds == {"ops_per_s": 0.15, "write_p50_ms": 0.25,
                      "read_p50_ms": 0.25, "setup_s": 0.25}
    ops = next(m for m in bench["end_to_end"] if m["name"] == "ops_per_s")
    assert ops["workloads"][:2] == ["upstream48x3.write_closed",
                                    "ladder1024x3.write_closed"]
    assert CELL in ops["workloads"]
    assert bench["run_seconds"] == 48
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    for w in bench["workloads"] + bench["configs"]:
        assert 1 <= len(w["why"]) <= 200 and len(w.get("source", "x")) <= 200
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536
