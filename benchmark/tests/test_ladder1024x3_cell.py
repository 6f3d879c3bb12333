"""``ladder1024x3.write_closed`` resolves from the names in
``BENCHMARK.json`` alone: its configuration, the reference beside it, its
traffic, the end-to-end metrics it reports, a reader for every per-layer
metric it inherits.  Adds nothing and runs nothing."""
from benchmark import run as harness


def test_the_cell_resolves_by_name():
    cell = harness.Cell("ladder1024x3.write_closed")
    assert cell.entry["chips"] == 1
    conf, assumed = cell.config, cell.config["assumed"]
    assert (conf["groups"], conf["replicas"]) == (1024, 3)
    assert conf["key_bytes"] + conf["value_bytes"] == conf["payload_bytes"] == 16
    assert assumed["engine_block_groups"] == 1024
    assert (assumed["election_rtt"], assumed["heartbeat_rtt"]) == (10, 1)
    assert assumed["rtt_millisecond"] in (50, 100, 200)
    old = harness.Cell("upstream48x3.write_closed")
    assert conf["guarantees"] == old.config["guarantees"]
    # the reference: every limit exact, the deployment's own size
    assert set(cell.reference.LIMITS.values()) == {0}
    assert cell.reference.LIMITS == old.reference.LIMITS
    ref = cell.reference.cluster(conf, 1)
    assert len(ref.cids) == 1024 and ref.replicas == 3
    # the traffic: closed loop, one writer a group, fresh keys
    t = cell.traffic
    assert (t["loop"], t["read_share"], t["inflight_per_group"]) == (
        "closed", 0.0, 1)
    assert "keys_per_group" not in t
    assert (t["attempt_timeout_s"], t["deadline_s"], t["warmup_s"]) == (
        5.0, 30.0, 3.0)
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "ops_per_s", "setup_s"]
    # every .tput metric is inherited and has a reader that loads
    names = [m["name"] for m, mod in cell.readers() if callable(mod.read)]
    assert names == [m["name"] for m in old.metrics("per_layer")]
    assert names and all(n.endswith(".tput") for n in names)
    for new in ("ticks_dropped_per_s.tput", "tick_flags_per_round.tput",
                "hb_block_pct.tput", "rows_per_round.tput"):
        assert new in names
