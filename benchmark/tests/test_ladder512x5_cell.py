"""``ladder512x5.mixed91`` resolves from the names in ``BENCHMARK.json``
alone: its configuration, the reference beside it, its traffic, the
end-to-end metrics it reports, a reader for every per-layer metric it
inherits or brings.  Adds nothing and runs nothing."""
import json
import os
import shutil

import pytest

from benchmark import run as harness

NEW = ("read_forwarded_pct.read", "acks_per_commit.lat", "hb_block_pct.lat",
       "rows_per_round.lat", "ticks_dropped_per_s.lat",
       "tick_flags_per_round.lat")


@pytest.fixture(params=["as_committed", "with_later_additions"])
def root(request, tmp_path):
    """The repo, and a copy to which a later PR has added a cell, a
    per-layer metric and an end-to-end metric as entries only: these tests
    hold this cell, and pass whatever is appended beside it."""
    if request.param == "as_committed":
        return harness.ROOT
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(harness.ROOT, "benchmark", sub),
                        tmp_path / "benchmark" / sub)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    bench["workloads"].append({
        "name": "ladder1024x3.mixed91", "config": "ladder1024x3",
        "traffic": "mixed91_x5", "chips": 1, "why": "a later cell"})
    bench["end_to_end"].append({
        "name": "read_p95_ms", "unit": "ms", "better": "lower", "bound": 0.3,
        "source": "host_clock"})
    bench["per_layer"].append({
        "name": "gen_late_ms.read", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "generator", "moves": "read_p50_ms"})
    for m in bench["end_to_end"]:
        if "ladder512x5.mixed91" in m.get("workloads", ()):
            m["workloads"].append("ladder1024x3.mixed91")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_the_cell_resolves_by_name(root):
    cell = harness.Cell("ladder512x5.mixed91", root=root)
    assert cell.entry["chips"] == 1
    conf, assumed = cell.config, cell.config["assumed"]
    assert (conf["groups"], conf["replicas"], conf["quorum"]) == (512, 5, 3)
    assert (conf["published_groups"], conf["published_servers"]) == (65536, 5)
    assert conf["key_bytes"] + conf["value_bytes"] == conf["payload_bytes"] == 16
    assert conf["fsync"] is True
    assert assumed["engine_block_groups"] == 512
    assert (assumed["rtt_millisecond"], assumed["election_rtt"],
            assumed["heartbeat_rtt"]) == (200, 10, 1)
    old = harness.Cell("upstream48x3.mixed91", root=root)
    assert conf["guarantees"] == old.config["guarantees"]
    entry = next(c for c in cell.bench["configs"] if c["name"] == "ladder512x5")
    assert entry["reduced"] == list(conf["reduced"]) == [
        "groups", "servers", "payload_mix"]
    # the reference: every limit exact, the deployment's own size
    assert set(cell.reference.LIMITS.values()) == {0}
    assert cell.reference.LIMITS == old.reference.LIMITS
    ref = cell.reference.cluster(conf, 1)
    assert len(ref.cids) == 512 and ref.replicas == 5
    assert cell.reference.commit_range(5, 2, 3, 1) == (8, 9)
    # the traffic: mixed91's keys, a rate of its own, reads at any host
    t = cell.traffic
    assert set(t) == set(old.traffic)
    assert {k: t[k] for k in t if k not in ("rate_ops_per_s", "why")} == {
        "loop": "open", "read_share": 0.9, "keys_per_group": 16,
        "read_host": "any", "read_newest_share": 0.5,
        "attempt_timeout_s": 5.0, "deadline_s": 30.0, "warmup_s": 3.0}
    assert isinstance(t["rate_ops_per_s"], float) and t["rate_ops_per_s"] >= 150
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert {"write_p50_ms", "read_p50_ms", "setup_s"} <= e2e
    assert "ops_per_s" not in e2e
    # every metric of upstream48x3.mixed91 is inherited, the new ones are
    # there, and each has a reader that loads; a later PR's entries, before
    # or after them, are none of this test's business
    names = {m["name"] for m, mod in cell.readers() if callable(mod.read)}
    inherited = {m["name"] for m in old.metrics("per_layer")}
    assert inherited <= names and set(NEW) <= names
    assert set(NEW[:2]) <= inherited  # no workloads list: both cells
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    for n in NEW[2:]:  # the block plane's: listed, this cell on the list
        assert "ladder512x5.mixed91" in by_name[n]["workloads"]
    for n in ("quorum_step_roofline.lat", "kernel_us_per_dispatch.lat",
              "read_fallback_pct.read", "read_slot_overflow_pct.read",
              "program_timeouts_per_kop.read", "compiles_in_window.lat"):
        assert n in names


def test_the_additions_edit_no_entry_that_was_there(root):
    """Membership, not position or count: the three older cells and the
    bounds this cell was admitted under are there as they were, and whatever
    a later PR appends (a cell, a metric, an end-to-end metric) passes."""
    bench = harness.Cell("ladder512x5.mixed91", root=root).bench
    cells = {w["name"] for w in bench["workloads"]}
    assert {"upstream48x3.write_closed", "upstream48x3.mixed91",
            "ladder1024x3.write_closed", "ladder512x5.mixed91"} <= cells
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    for name, bound in (("ops_per_s", 0.15), ("write_p50_ms", 0.25),
                        ("read_p50_ms", 0.25), ("setup_s", 0.25)):
        assert bounds.get(name) == bound
    assert set(NEW) <= {m["name"] for m in bench["per_layer"]}
    for w in bench["workloads"] + bench["configs"]:
        assert 1 <= len(w["why"]) <= 200 and len(w.get("source", "x")) <= 200
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536
