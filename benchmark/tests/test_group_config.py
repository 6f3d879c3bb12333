"""A configuration file may state its groups' Raft settings
(``group_config``: ``Config`` field names to JSON scalars).  Absent or
empty, every ``Config`` is the one built before the key existed; a key the
harness cannot take fails before a NodeHost exists; a configuration that
sets the block, added as files alone, snapshots and compacts inside a run
and is ``correct``."""
import pytest

from benchmark import cluster as cl, run as harness

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
SETTINGS = {"check_quorum": True, "snapshot_entries": 20,
            "compaction_overhead": 5}
CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("block", ["absent", "empty"])
def test_without_a_block_the_config_is_todays(cell_name, block):
    from dragonboat_tpu import Config

    conf = dict(harness.Cell(cell_name).config)
    if block == "empty":
        conf["group_config"] = {}
    else:
        assert "group_config" not in conf  # no cell of this PR sets one
    assumed = conf["assumed"]
    for cid, node_id in ((1, 1), (conf["groups"], conf["replicas"])):
        built = Config(cluster_id=cid, node_id=node_id,
                       **cl.group_config(conf))
        assert built == Config(cluster_id=cid, node_id=node_id,
                               election_rtt=assumed["election_rtt"],
                               heartbeat_rtt=assumed["heartbeat_rtt"])
        assert (built.check_quorum, built.snapshot_entries) == (False, 0)


@pytest.mark.parametrize("key, value", [
    ("snapshot_entires", 20),          # no field of Config
    ("max_in_mem_log", 0),
    ("cluster_id", 7),                 # the harness's own four
    ("node_id", 2),
    ("election_rtt", 20),
    ("heartbeat_rtt", 2),
    ("hier_domains", {"1": "a"}),      # a field, not a scalar
    ("snapshot_entries", 20.5),
    ("check_quorum", "true"),
    ("compaction_overhead", None),
    ("entry_compression", [1]),
])
def test_a_key_the_harness_cannot_take_fails_before_a_nodehost(
        key, value, monkeypatch):
    from dragonboat_tpu import nodehost

    def no_nodehost(*a, **k):
        raise AssertionError("a NodeHost was made")

    monkeypatch.setattr(nodehost, "NodeHost", no_nodehost)
    made = []
    monkeypatch.setattr(cl.tempfile, "mkdtemp",
                        lambda **k: made.append(k) or "/nonexistent")
    conf = dict(harness.Cell("upstream48x3.write_closed").config, groups=2,
                group_config=dict(SETTINGS, **{key: value}))
    with pytest.raises(ValueError, match=repr(key)):
        cl.LiveCluster(conf, harness.CACHE_DIR)
    with pytest.raises(ValueError, match="not an object"):
        cl.LiveCluster(dict(conf, group_config=[key]), harness.CACHE_DIR)
    assert not made  # nor a directory for one


def test_what_the_fields_mean_together_is_validates():
    """``read_lease`` without ``check_quorum`` passes the harness's rule (both
    are fields, both scalars) and fails loudly in ``Config.validate``, which
    ``start_cluster`` runs."""
    from dragonboat_tpu import Config
    from dragonboat_tpu.config import ConfigError

    conf = dict(harness.Cell("upstream48x3.mixed91").config,
                group_config={"read_lease": True})
    built = Config(cluster_id=1, node_id=1, **cl.group_config(conf))
    with pytest.raises(ConfigError, match="read_lease requires check_quorum"):
        built.validate()
    conf["group_config"]["check_quorum"] = True
    Config(cluster_id=1, node_id=1, **cl.group_config(conf)).validate()


@pytest.fixture(scope="module")
def cpu():
    from dragonboat_tpu import hostplatform

    hostplatform.force_cpu()


def test_a_configuration_with_the_block_snapshots_and_is_correct(
        cpu, add_configuration):
    """The configuration is added as files alone (its file, its reference
    beside it, one ``configs`` and one ``workloads`` entry); two groups on
    three NodeHosts take 100 generated writes and more, snapshot every 20
    applied entries and keep 5 behind the snapshot."""
    root, name = add_configuration(
        "documented2x3", "upstream48x3.write_closed", groups=2,
        group_config=SETTINGS)
    cell = harness.Cell(name, root=root)
    cell.traffic = dict(cell.traffic, warmup_s=0.2)
    cluster = cl.LiveCluster(cell.config, harness.CACHE_DIR)
    try:
        nodes = [nh.get_node(cid) for nh in cluster.nhs
                 for cid in cluster.cids]
        assert len(nodes) == 6
        for node in nodes:
            for key, value in SETTINGS.items():
                assert getattr(node.config, key) == value
            assert (node.config.election_rtt, node.config.heartbeat_rtt) == (
                10, 1)
        result = harness.run(cell, cluster, 2**31 + 36, 1.5, False, DEVICE,
                             True, setup_clock=lambda: 0.0)
        assert result["attempted"] >= 100 and result["failed"] == 0
        assert result["correct"]
        assert all(c["value"] == 0 for c in result["compared"].values())
        # every group snapshotted inside the run, on some replica at least,
        # and compacted its log up to 5 entries behind the snapshot
        for cid in cluster.cids:
            taken = [nh.get_node(cid).sm.get_snapshot_index()
                     for nh in cluster.nhs]
            assert max(taken) >= SETTINGS["snapshot_entries"], taken
            first = [nh.get_node(cid).logreader.get_range()[0]
                     for nh in cluster.nhs]
            assert max(first) > (SETTINGS["snapshot_entries"]
                                 - SETTINGS["compaction_overhead"]), first
    finally:
        cluster.stop()
