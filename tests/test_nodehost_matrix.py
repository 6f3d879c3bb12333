"""NodeHost/node behavioral matrix.

Ports the behavioral families of the reference's ``nodehost_test.go``
(4,731 LoC) that the basic suite (``test_nodehost.py``) does not cover:
config-validation failures, double start/stop, restart matrices
(same/changed membership, remove-data-then-restart), snapshot option
combinations (user-requested / exported / compaction override), session
error paths, the request error taxonomy (``requests.go:53-98`` analogs),
and stopped-NodeHost behavior.

All in-process over the chan transport + memory LogDB (the reference's
memfs test-build shape, ``docs/test.md``).
"""
import os
import time

import pytest

from dragonboat_tpu import (
    Config,
    IStateMachine,
    NodeHost,
    NodeHostConfig,
    Result,
)
from dragonboat_tpu.client import Session
from dragonboat_tpu.config import ConfigError, ExpertConfig
from dragonboat_tpu.requests import (
    ClusterAlreadyExistError,
    ClusterNotFoundError,
    InvalidSessionError,
    RejectedError,
    RequestError,
    TimeoutError_,
)
from dragonboat_tpu.transport import ChanRouter, ChanTransport

RTT_MS = 5


class KVSM(IStateMachine):
    def __init__(self, cluster_id, node_id):
        self.kv = {}
        self.count = 0

    def update(self, cmd):
        k, v = cmd.decode().split("=", 1)
        self.kv[k] = v
        self.count += 1
        return Result(value=self.count)

    def lookup(self, query):
        return self.kv.get(query)

    def save_snapshot(self, w, files, done):
        data = repr(sorted(self.kv.items())).encode()
        w.write(len(data).to_bytes(8, "little") + data)

    def recover_from_snapshot(self, r, files, done):
        import ast

        n = int.from_bytes(r.read(8), "little")
        self.kv = dict(ast.literal_eval(r.read(n).decode()))
        self.count = len(self.kv)


def mk_nh(addr, router, tmpdir=None, **kw):
    return NodeHost(
        NodeHostConfig(
            node_host_dir=tmpdir or ":memory:",
            rtt_millisecond=RTT_MS,
            raft_address=addr,
            raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                s, rh, ch, router=router
            ),
            **kw,
        )
    )


def gcfg(cid, nid, **kw):
    d = dict(cluster_id=cid, node_id=nid, election_rtt=10, heartbeat_rtt=1)
    d.update(kw)
    return Config(**d)


def wait_leader(nhs, cid, timeout=15.0):
    # load-scaled deadline (tests/loadwait.py): the r07 contention-flake
    # class — sound standalone, starved under the full sweep
    from tests.loadwait import scaled

    deadline = time.time() + scaled(timeout)
    while time.time() < deadline:
        for nh in nhs:
            lid, ok = nh.get_leader_id(cid)
            if ok:
                return lid
        time.sleep(0.02)
    raise AssertionError(f"no leader for {cid}")


@pytest.fixture
def solo():
    router = ChanRouter()
    nh = mk_nh("m1:1", router)
    nh.start_cluster({1: "m1:1"}, False, KVSM, gcfg(1, 1))
    wait_leader([nh], 1)
    yield nh
    nh.stop()


@pytest.fixture
def trio():
    router = ChanRouter()
    addrs = {i: f"t{i}:1" for i in (1, 2, 3)}
    nhs = [mk_nh(addrs[i], router) for i in (1, 2, 3)]
    for i, nh in enumerate(nhs, 1):
        nh.start_cluster(addrs, False, KVSM, gcfg(9, i))
    lid = wait_leader(nhs, 9)
    yield nhs, addrs, lid, router
    for nh in nhs:
        nh.stop()


# ======================================================================
# config validation failures (reference config.Config.Validate paths)
# ======================================================================


def test_config_zero_node_id_rejected():
    with pytest.raises(ConfigError):
        Config(cluster_id=1, node_id=0).validate()


def test_config_zero_heartbeat_rejected():
    with pytest.raises(ConfigError):
        Config(cluster_id=1, node_id=1, heartbeat_rtt=0).validate()


def test_config_zero_election_rejected():
    with pytest.raises(ConfigError):
        Config(cluster_id=1, node_id=1, election_rtt=0,
               heartbeat_rtt=1).validate()


def test_config_election_not_gt_twice_heartbeat():
    with pytest.raises(ConfigError):
        Config(cluster_id=1, node_id=1, election_rtt=4,
               heartbeat_rtt=2).validate()


def test_config_small_inmem_log_size_rejected():
    with pytest.raises(ConfigError):
        Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=1,
               max_in_mem_log_size=1024).validate()


def test_config_unknown_compression_rejected():
    with pytest.raises(ConfigError):
        Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=1,
               snapshot_compression=7).validate()


def test_config_witness_with_snapshot_entries_rejected():
    with pytest.raises(ConfigError):
        Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=1,
               is_witness=True, snapshot_entries=10).validate()


def test_config_witness_observer_conflict_rejected():
    with pytest.raises(ConfigError):
        Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=1,
               is_witness=True, is_observer=True).validate()


def test_expert_unknown_engine_rejected():
    with pytest.raises(ConfigError):
        ExpertConfig(quorum_engine="gpu").validate()


def test_nodehost_config_requires_address():
    with pytest.raises(Exception):
        NodeHostConfig(node_host_dir=":memory:", rtt_millisecond=5,
                       raft_address="").validate()


# ======================================================================
# start/stop lifecycle (double start, unknown stop, start after stop)
# ======================================================================


def test_double_start_same_cluster_rejected(solo):
    with pytest.raises(ClusterAlreadyExistError):
        solo.start_cluster({1: "m1:1"}, False, KVSM, gcfg(1, 1))


def test_start_new_node_without_members_rejected(solo):
    with pytest.raises(ValueError):
        solo.start_cluster({}, False, KVSM, gcfg(2, 1))


def test_start_join_with_members_rejected(solo):
    with pytest.raises(ValueError):
        solo.start_cluster({1: "m1:1"}, True, KVSM, gcfg(3, 1))


def test_stop_unknown_cluster_raises(solo):
    with pytest.raises(ClusterNotFoundError):
        solo.stop_cluster(424242)


def test_stop_then_restart_same_cluster(tmp_path):
    router = ChanRouter()
    nh = mk_nh("r1:1", router, str(tmp_path / "nh"))
    try:
        nh.start_cluster({1: "r1:1"}, False, KVSM, gcfg(5, 1))
        wait_leader([nh], 5)
        s = nh.get_noop_session(5)
        assert nh.sync_propose(s, b"a=1", timeout=10.0).value == 1
        nh.stop_cluster(5)
        # restarting a stopped cluster on the same NodeHost resumes from
        # its bootstrap record (empty members + join=False)
        nh.start_cluster({}, False, KVSM, gcfg(5, 1))
        wait_leader([nh], 5)
        assert nh.sync_read(5, "a", timeout=10.0) == "1"
    finally:
        nh.stop()


def test_sm_type_change_across_restart_rejected(tmp_path):
    router = ChanRouter()
    nh = mk_nh("r2:1", router, str(tmp_path / "nh"))
    try:
        nh.start_cluster({1: "r2:1"}, False, KVSM, gcfg(6, 1))
        wait_leader([nh], 6)
        nh.stop_cluster(6)
        with pytest.raises(ValueError):
            nh.start_on_disk_cluster({}, False, KVSM, gcfg(6, 1))
    finally:
        nh.stop()


def test_requests_on_stopped_cluster_raise(solo):
    solo.stop_cluster(1)
    with pytest.raises(ClusterNotFoundError):
        solo.sync_propose(Session.noop_session(1), b"x=1", timeout=1.0)
    with pytest.raises(ClusterNotFoundError):
        solo.sync_read(1, "x", timeout=1.0)
    with pytest.raises(ClusterNotFoundError):
        solo.get_node(1)


def test_stopped_nodehost_rejects_requests():
    router = ChanRouter()
    nh = mk_nh("st1:1", router)
    nh.start_cluster({1: "st1:1"}, False, KVSM, gcfg(7, 1))
    wait_leader([nh], 7)
    nh.stop()
    with pytest.raises(RequestError):
        nh.sync_propose(nh.get_noop_session(7), b"x=1", timeout=1.0)


def test_stop_node_is_stop_cluster_alias(solo):
    solo.stop_node(1, 1)
    assert not solo.has_cluster(1)


def test_has_cluster_and_get_node(solo):
    assert solo.has_cluster(1)
    assert not solo.has_cluster(2)
    assert solo.get_node(1) is not None


# ======================================================================
# restart matrices
# ======================================================================


def test_restart_full_trio_preserves_data(tmp_path):
    router = ChanRouter()
    addrs = {i: f"rt{i}:1" for i in (1, 2, 3)}
    dirs = {i: str(tmp_path / f"nh{i}") for i in (1, 2, 3)}
    nhs = [mk_nh(addrs[i], router, dirs[i]) for i in (1, 2, 3)]
    try:
        for i, nh in enumerate(nhs, 1):
            nh.start_cluster(addrs, False, KVSM, gcfg(11, i))
        wait_leader(nhs, 11)
        lid = wait_leader(nhs, 11)
        s = nhs[lid - 1].get_noop_session(11)
        for k in range(8):
            nhs[lid - 1].sync_propose(s, f"k{k}=v{k}".encode(), timeout=10.0)
        for nh in nhs:
            nh.stop()
        # full restart from on-disk state: empty members + join False
        router2 = ChanRouter()
        nhs = [mk_nh(addrs[i], router2, dirs[i]) for i in (1, 2, 3)]
        for i, nh in enumerate(nhs, 1):
            nh.start_cluster({}, False, KVSM, gcfg(11, i))
        lid = wait_leader(nhs, 11)
        assert nhs[lid - 1].sync_read(11, "k7", timeout=10.0) == "v7"
    finally:
        for nh in nhs:
            try:
                nh.stop()
            except Exception:
                pass


def test_restart_with_changed_address_rejected(tmp_path):
    """Reusing a node's data dir under a DIFFERENT raft address is
    refused (reference server.Context ownership flag: a NodeHost dir
    belongs to the address that created it — nodehost_test.go's
    address-change error family)."""
    from dragonboat_tpu.server.context import NotOwnerError

    router = ChanRouter()
    d = str(tmp_path / "nh")
    nh = mk_nh("ca1:1", router, d)
    nh.start_cluster({1: "ca1:1"}, False, KVSM, gcfg(12, 1))
    wait_leader([nh], 12)
    nh.stop()
    with pytest.raises(NotOwnerError):
        mk_nh("ca1-new:1", router, d)


def test_remove_data_then_restart_is_clean(tmp_path):
    router = ChanRouter()
    nh = mk_nh("rd1:1", router, str(tmp_path / "nh"))
    try:
        nh.start_cluster({1: "rd1:1"}, False, KVSM, gcfg(13, 1))
        wait_leader([nh], 13)
        s = nh.get_noop_session(13)
        nh.sync_propose(s, b"a=1", timeout=10.0)
        nh.stop_cluster(13)
        nh.remove_data(13, 1)
        assert not nh.has_node_info(13, 1)
        # after RemoveData the node is brand new: restart requires members
        with pytest.raises(ValueError):
            nh.start_cluster({}, False, KVSM, gcfg(13, 1))
        nh.start_cluster({1: "rd1:1"}, False, KVSM, gcfg(13, 1))
        wait_leader([nh], 13)
        # data really is gone
        assert nh.sync_read(13, "a", timeout=10.0) is None
    finally:
        nh.stop()


def test_remove_data_on_running_cluster_rejected(solo):
    with pytest.raises(RuntimeError):
        solo.remove_data(1, 1)


# ======================================================================
# snapshot option combinations
# ======================================================================


def test_user_requested_snapshot_returns_index(tmp_path):
    router = ChanRouter()
    nh = mk_nh("ss1:1", router, str(tmp_path / "nh"))
    try:
        nh.start_cluster({1: "ss1:1"}, False, KVSM, gcfg(14, 1))
        wait_leader([nh], 14)
        s = nh.get_noop_session(14)
        for k in range(5):
            nh.sync_propose(s, f"k{k}=v".encode(), timeout=10.0)
        idx = nh.sync_request_snapshot(14, timeout=10.0)
        assert idx >= 5
        # a second request without new entries is rejected (reference
        # SnapshotIndexExist path)
        with pytest.raises(RequestError):
            nh.sync_request_snapshot(14, timeout=10.0)
    finally:
        nh.stop()


def test_exported_snapshot_lands_in_export_path(tmp_path):
    router = ChanRouter()
    nh = mk_nh("ss2:1", router, str(tmp_path / "nh"))
    export = tmp_path / "export"
    export.mkdir()
    try:
        nh.start_cluster({1: "ss2:1"}, False, KVSM, gcfg(15, 1))
        wait_leader([nh], 15)
        s = nh.get_noop_session(15)
        for k in range(4):
            nh.sync_propose(s, f"k{k}=v".encode(), timeout=10.0)
        rs = nh.request_snapshot(15, export_path=str(export), timeout=10.0)
        r = rs.wait(10.0)
        assert r.completed
        dirs = list(export.iterdir())
        assert dirs, "no exported snapshot directory"
        # exported snapshots don't register locally: a user-requested one
        # right after must still succeed
        idx = nh.sync_request_snapshot(15, timeout=10.0)
        assert idx > 0
    finally:
        nh.stop()


def test_snapshot_with_compaction_override(tmp_path):
    router = ChanRouter()
    nh = mk_nh("ss3:1", router, str(tmp_path / "nh"))
    try:
        nh.start_cluster({1: "ss3:1"}, False, KVSM, gcfg(16, 1))
        wait_leader([nh], 16)
        s = nh.get_noop_session(16)
        for k in range(10):
            nh.sync_propose(s, f"k{k}=v".encode(), timeout=10.0)
        rs = nh.request_snapshot(
            16, override_compaction_overhead=True, compaction_overhead=2,
            timeout=10.0,
        )
        r = rs.wait(10.0)
        assert r.completed
        node = nh.get_node(16)
        deadline = time.time() + 10
        while time.time() < deadline:
            if node.logreader.get_range()[0] > 1:
                break
            time.sleep(0.05)
        first, _ = node.logreader.get_range()
        assert first > 1, "compaction with override never happened"
    finally:
        nh.stop()


def test_snapshot_on_unknown_cluster_raises(solo):
    with pytest.raises(ClusterNotFoundError):
        solo.sync_request_snapshot(999, timeout=2.0)


def test_auto_snapshot_after_snapshot_entries(tmp_path):
    router = ChanRouter()
    nh = mk_nh("ss4:1", router, str(tmp_path / "nh"))
    try:
        nh.start_cluster(
            {1: "ss4:1"}, False, KVSM, gcfg(17, 1, snapshot_entries=8,
                                            compaction_overhead=2),
        )
        wait_leader([nh], 17)
        s = nh.get_noop_session(17)
        for k in range(20):
            nh.sync_propose(s, f"k{k}=v".encode(), timeout=10.0)
        node = nh.get_node(17)
        deadline = time.time() + 15
        while time.time() < deadline:
            if node.sm.get_snapshot_index() > 0:
                break
            time.sleep(0.05)
        assert node.sm.get_snapshot_index() > 0, "auto snapshot never fired"
    finally:
        nh.stop()


# ======================================================================
# session error paths
# ======================================================================


def test_session_register_close_roundtrip(solo):
    s = solo.sync_get_session(1, timeout=10.0)
    assert s.client_id != 0
    r = solo.sync_propose(s, b"x=1", timeout=10.0)
    s.proposal_completed()
    assert r.value == 1
    solo.sync_close_session(s, timeout=10.0)


def test_closed_session_propose_rejected(solo):
    s = solo.sync_get_session(1, timeout=10.0)
    solo.sync_close_session(s, timeout=10.0)
    with pytest.raises(RequestError):
        r = solo.sync_propose(s, b"y=2", timeout=5.0)
        # an evicted session must not silently apply
        raise RejectedError(str(r))


def test_noop_session_never_registers(solo):
    s = solo.get_noop_session(1)
    assert s.is_noop_session()
    assert solo.sync_propose(s, b"a=1", timeout=10.0).value == 1


def test_session_dedup_same_series(solo):
    """Re-proposing the same series id must not re-apply (exactly-once)."""
    s = solo.sync_get_session(1, timeout=10.0)
    # async propose path: series id advances only on proposal_completed
    r1 = solo.propose(s, b"k=1", timeout=10.0).wait(10.0)
    assert r1.completed
    # retry under the SAME series id (client crash-retry shape)
    r2 = solo.propose(s, b"k=1", timeout=10.0).wait(10.0)
    assert r2.completed
    assert r1.result.value == r2.result.value, "duplicate series applied twice"
    s.proposal_completed()
    r3 = solo.propose(s, b"k=2", timeout=10.0).wait(10.0)
    assert r3.result.value == r1.result.value + 1
    solo.sync_close_session(s, timeout=10.0)


def test_invalid_session_for_other_cluster(trio):
    nhs, addrs, lid, router = trio
    leader = nhs[lid - 1]
    s = leader.sync_get_session(9, timeout=10.0)
    bad = Session(client_id=s.client_id, series_id=s.series_id,
                  cluster_id=777)
    with pytest.raises((InvalidSessionError, ClusterNotFoundError)):
        leader.sync_propose(bad, b"x=1", timeout=5.0)


# ======================================================================
# request error taxonomy
# ======================================================================


def test_propose_unknown_cluster(solo):
    with pytest.raises(ClusterNotFoundError):
        solo.sync_propose(Session.noop_session(999), b"x=1", timeout=1.0)


def test_read_unknown_cluster(solo):
    with pytest.raises(ClusterNotFoundError):
        solo.sync_read(999, "x", timeout=1.0)


def test_stale_read_known_and_unknown(solo):
    s = solo.get_noop_session(1)
    solo.sync_propose(s, b"sr=1", timeout=10.0)
    assert solo.stale_read(1, "sr") == "1"
    with pytest.raises(ClusterNotFoundError):
        solo.stale_read(999, "sr")


def test_zero_timeout_times_out(trio):
    nhs, addrs, lid, router = trio
    follower = nhs[lid % 3]  # any non-leader
    rs = follower.read_index(9, 0.001)
    r = rs.wait(2.0)
    # with an RTT-quantized deadline this must resolve quickly as either
    # a timeout or (if confirmation won the race) completion
    assert r is not None


def test_leader_transfer_to_unknown_target_noops(trio):
    nhs, addrs, lid, router = trio
    nhs[lid - 1].request_leader_transfer(9, 99)  # unknown target id
    # cluster keeps working
    s = nhs[lid - 1].get_noop_session(9)
    assert nhs[lid - 1].sync_propose(s, b"x=1", timeout=10.0).value == 1


def test_leader_transfer_to_real_target(trio):
    from tests.loadwait import wait_until

    nhs, addrs, lid, router = trio
    target = (lid % 3) + 1
    asked = 0.0

    def led_by_target():
        nonlocal asked
        if nhs[target - 1].get_node(9).is_leader():
            return True
        # a transfer the leader dropped (target behind, a round lost) is
        # asked for again, of whoever leads now, as a client would
        if time.time() - asked >= 1.0:
            asked = time.time()
            for nh in nhs:
                if nh.get_node(9).is_leader():
                    nh.request_leader_transfer(9, target)
        return False

    wait_until(led_by_target, 15.0, what=f"node {target} leads group 9")
    # and the others follow it
    wait_until(
        lambda: all(nh.get_leader_id(9) == (target, True) for nh in nhs),
        15.0, what=f"every host names node {target} leader",
    )


def test_concurrent_config_change_rejected(trio):
    nhs, addrs, lid, router = trio
    leader = nhs[lid - 1]
    rs = leader.request_add_node(9, 4, "t4:1", timeout=10.0)
    try:
        with pytest.raises(RequestError):
            leader.request_add_node(9, 5, "t5:1", timeout=10.0)
            raise RejectedError("second in-flight config change accepted")
    finally:
        rs.wait(10.0)


def test_membership_query_reflects_add_observer(trio):
    nhs, addrs, lid, router = trio
    leader = nhs[lid - 1]
    leader.sync_request_add_observer(9, 7, "t7:1", timeout=10.0)
    m = leader.sync_get_cluster_membership(9, timeout=10.0)
    assert 7 in m.observers
    assert set(m.addresses) == {1, 2, 3}


def test_get_node_host_info_shape(trio):
    nhs, addrs, lid, router = trio
    info = nhs[0].get_node_host_info()
    assert info.raft_address == addrs[1]
    assert any(ci.cluster_id == 9 for ci in info.cluster_info_list)
    assert info.log_info, "skip_log_info=False must include log info"
    info2 = nhs[0].get_node_host_info(skip_log_info=True)
    assert not info2.log_info


# ======================================================================
# observer / witness / join lifecycle
# ======================================================================


def test_observer_replica_serves_stale_read(trio):
    nhs, addrs, lid, router = trio
    leader = nhs[lid - 1]
    obs = mk_nh("t4:1", router)
    try:
        leader.sync_request_add_observer(9, 4, "t4:1", timeout=10.0)
        obs.start_cluster({}, True, KVSM, gcfg(9, 4, is_observer=True))
        s = leader.get_noop_session(9)
        leader.sync_propose(s, b"ob=1", timeout=10.0)
        deadline = time.time() + 15
        ok = False
        while time.time() < deadline and not ok:
            try:
                ok = obs.stale_read(9, "ob") == "1"
            except Exception:
                ok = False
            time.sleep(0.05)
        assert ok, "observer never caught up"
    finally:
        obs.stop()


def test_witness_join_and_data_free(trio):
    nhs, addrs, lid, router = trio
    leader = nhs[lid - 1]
    wit = mk_nh("t8:1", router)
    try:
        leader.sync_request_add_witness(9, 8, "t8:1", timeout=10.0)
        wit.start_cluster({}, True, KVSM, gcfg(9, 8, is_witness=True))
        s = leader.get_noop_session(9)
        for k in range(5):
            leader.sync_propose(s, f"w{k}=1".encode(), timeout=10.0)
        m = leader.sync_get_cluster_membership(9, timeout=10.0)
        assert 8 in m.witnesses
        # the witness replica never applies user data
        assert wit.get_node(9).sm.lookup("w0") is None
    finally:
        wit.stop()


def test_delete_node_then_requests_rejected(trio):
    nhs, addrs, lid, router = trio
    leader = nhs[lid - 1]
    victim = (lid % 3) + 1
    leader.sync_request_delete_node(9, victim, timeout=10.0)
    m = leader.sync_get_cluster_membership(9, timeout=10.0)
    assert victim not in m.addresses
    # the removed replica steps itself down into self_removed state; new
    # proposals through it fail once it learns (bounded wait)
    deadline = time.time() + 15
    removed = False
    while time.time() < deadline and not removed:
        node = nhs[victim - 1].get_node(9)
        removed = node.peer.raft.self_removed()
        time.sleep(0.05)
    assert removed


# ======================================================================
# on-disk / concurrent SM lifecycle through the facade
# ======================================================================


class ConcSM:
    def __init__(self, cluster_id, node_id):
        self.v = 0

    def update(self, entries):
        for e in entries:
            self.v += 1
            e.result = Result(value=self.v)
        return entries

    def lookup(self, q):
        return self.v

    def prepare_snapshot(self):
        return self.v

    def save_snapshot(self, ctx, w, files, done):
        w.write(int(ctx).to_bytes(8, "little"))

    def recover_from_snapshot(self, r, files, done):
        self.v = int.from_bytes(r.read(8), "little")

    def close(self):
        pass


def test_concurrent_sm_cluster_roundtrip():
    router = ChanRouter()
    nh = mk_nh("cc1:1", router)
    try:
        nh.start_concurrent_cluster({1: "cc1:1"}, False, ConcSM, gcfg(21, 1))
        wait_leader([nh], 21)
        s = nh.get_noop_session(21)
        for k in range(6):
            assert nh.sync_propose(s, b"x", timeout=10.0).value == k + 1
        assert nh.sync_read(21, None, timeout=10.0) == 6
    finally:
        nh.stop()


class DiskSM:
    def __init__(self, cluster_id, node_id):
        self.v = 0
        self.applied = 0

    def open(self, stopc):
        return self.applied

    def update(self, entries):
        for e in entries:
            self.v += 1
            self.applied = e.index
            e.result = Result(value=self.v)
        return entries

    def lookup(self, q):
        return self.v

    def sync(self):
        pass

    def prepare_snapshot(self):
        return self.v

    def save_snapshot(self, ctx, w, done):
        w.write(int(ctx).to_bytes(8, "little"))

    def recover_from_snapshot(self, r, done):
        self.v = int.from_bytes(r.read(8), "little")

    def close(self):
        pass


def test_on_disk_sm_cluster_roundtrip():
    router = ChanRouter()
    nh = mk_nh("od1:1", router)
    try:
        nh.start_on_disk_cluster({1: "od1:1"}, False, DiskSM, gcfg(22, 1))
        wait_leader([nh], 22)
        s = nh.get_noop_session(22)
        for k in range(6):
            assert nh.sync_propose(s, b"x", timeout=10.0).value == k + 1
    finally:
        nh.stop()


# ======================================================================
# misc API surface
# ======================================================================


def test_propose_batch_orders_and_completes(solo):
    s = solo.get_noop_session(1)
    states = solo.propose_batch(s, [f"b{i}=1".encode() for i in range(10)],
                                timeout=10.0)
    vals = [rs.wait(10.0).result.value for rs in states]
    assert vals == sorted(vals), "batch completions out of order"
    assert len(set(vals)) == 10


def test_read_index_on_leader_completes(solo):
    s = solo.get_noop_session(1)
    solo.sync_propose(s, b"ri=1", timeout=10.0)
    rs = solo.read_index(1, 10.0)
    r = rs.wait(10.0)
    assert r.completed


def test_compaction_wrong_node_id_raises(solo):
    # unknown cluster ids legitimately compact leftover data (the
    # post-remove_data path, reference RequestCompaction); a LIVE cluster
    # under a wrong node id is refused
    with pytest.raises(ClusterNotFoundError):
        solo.request_compaction(1, 42)


def test_get_node_user_matches_get_node(solo):
    assert solo.get_node_user(1) is solo.get_node(1)
