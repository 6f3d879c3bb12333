"""A later PR adds a configuration, a cell and a per-layer metric as files and
entries, and edits no file the benchmark already has."""
import json
import os
import shutil

from benchmark import control, run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_new_config_cell_and_metric_are_only_additions(tmp_path):
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                        tmp_path / "benchmark" / sub)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the additions: a configuration (its file and its reference beside it),
    # one workloads entry, its name on the end-to-end metrics it reports, one
    # traffic file, one per-layer entry, one reader file
    conf = tmp_path / "benchmark" / "configs"
    with open(conf / "upstream48x3.json") as f:
        (conf / "small8x3.json").write_text(
            json.dumps(dict(json.load(f), groups=8)))
    shutil.copy(conf / "upstream48x3_reference.py",
                conf / "small8x3_reference.py")
    bench["configs"].append({
        "name": "small8x3", "source": "a test", "reduced": [],
        "file": "benchmark/configs/small8x3.json", "why": "a new deployment"})
    bench["workloads"].append({
        "name": "small8x3.mixed91", "config": "small8x3",
        "traffic": "mixed91_slow", "chips": 1, "why": "a new cell"})
    for m in bench["end_to_end"]:
        if "upstream48x3.mixed91" in m.get("workloads", ()):
            m["workloads"].append("small8x3.mixed91")
    bench["per_layer"].append({
        "name": "writes_acked.lat", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "client API",
        "moves": "write_p50_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with open(os.path.join(ROOT, "benchmark", "traffic", "mixed91.json")) as f:
        traffic = dict(json.load(f), rate_ops_per_s=400.0, warmup_s=0.1)
    (tmp_path / "benchmark" / "traffic" / "mixed91_slow.json").write_text(
        json.dumps(traffic))
    (tmp_path / "benchmark" / "layers" / "writes_acked.py").write_text(
        "def read(ctx):\n    return float(len(ctx.outcome.acked_writes))\n")

    cell = harness.Cell("small8x3.mixed91", root=str(tmp_path))
    assert cell.config["groups"] == 8
    cluster = control.build("reference", cell, 1)
    plain = harness.run(cell, cluster, 3, 0.5, False, DEVICE, True,
                        setup_clock=lambda: 0.0)
    assert set(plain["metrics"]) == {"write_p50_ms", "read_p50_ms", "setup_s"}
    traced = harness.run(cell, control.build("reference", cell, 1), 3, 0.5,
                         True, DEVICE, True, setup_clock=lambda: 0.0)
    got = traced["metrics"]
    assert got["writes_acked.lat"]["value"] > 0
    assert "achieved_ops_per_s.lat" in got and "read_p95_ms.obs" in got
    assert not any(name.endswith(".tput") for name in got)
    # an existing cell is untouched by the additions
    old = harness.Cell("upstream48x3.write_closed", root=str(tmp_path))
    assert all(m["name"].endswith(".tput") for m in old.metrics("per_layer"))
    assert [m["name"] for m in old.metrics("end_to_end")] == [
        "ops_per_s", "setup_s"]


def test_a_configuration_states_its_groups_raft_settings_as_data(
        add_configuration):
    """The documented-settings deployment: one configuration file with a
    ``group_config`` block, its reference beside it, two entries.  The
    harness hands the block to every ``Config``; nothing that exists is
    edited, and the cells without the block build what they built."""
    from benchmark.cluster import group_config

    block = {"check_quorum": True, "snapshot_entries": 10,
             "compaction_overhead": 5}
    root, name = add_configuration(
        "documented8x3", "upstream48x3.write_closed", groups=8,
        group_config=block)
    cell = harness.Cell(name, root=root)
    assert group_config(cell.config) == dict(
        block, election_rtt=10, heartbeat_rtt=1)
    old = harness.Cell("upstream48x3.write_closed", root=root)
    assert cell.config["guarantees"] == old.config["guarantees"]
    sound = harness.run(cell, control.build("reference", cell, 1), 3, 0.5,
                        False, DEVICE, True, setup_clock=lambda: 0.0)
    assert sound["correct"] and set(sound["metrics"]) == {
        "ops_per_s", "setup_s"}
    for other in ("upstream48x3.write_closed", "ladder512x5.mixed91"):
        assert group_config(harness.Cell(other, root=root).config) == {
            "election_rtt": 10, "heartbeat_rtt": 1}
