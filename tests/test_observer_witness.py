"""NodeHost-level observer and witness lifecycle tests.

Reference: observer catch-up + promotion (``raft.go:1145-1152``), witness
replicas that store metadata-only entries and vote but never lead
(§4.2.1 of the raft thesis; ``raft.go`` witness paths).  Raft-level suites
cover the protocol; these exercise the public NodeHost surface:
start_cluster with is_observer/is_witness, runtime add + promote.
"""
from __future__ import annotations

import time

import pytest

from dragonboat_tpu import Config, NodeHost, NodeHostConfig, Result
from dragonboat_tpu.transport import ChanRouter, ChanTransport


RTT = 10
CID = 5


class KVSM:
    def __init__(self, cluster_id, node_id):
        self.kv = {}

    def update(self, cmd):
        k, v = cmd.decode().split("=", 1)
        self.kv[k] = v
        return Result(value=len(self.kv))

    def lookup(self, query):
        return self.kv.get(query)

    def save_snapshot(self, w, files, done):
        import json

        data = json.dumps(sorted(self.kv.items())).encode()
        w.write(len(data).to_bytes(8, "little") + data)

    def recover_from_snapshot(self, r, files, done):
        import json

        n = int.from_bytes(r.read(8), "little")
        self.kv = dict(json.loads(r.read(n).decode()))

    def close(self):
        pass


def _mk(i, router, sms, addrs, initial_members, **cfg_kw):
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=":memory:",
            rtt_millisecond=RTT,
            raft_address=addrs[i],
            raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                s, rh, ch, router=router
            ),
        )
    )

    def create(cluster_id, node_id):
        sm = KVSM(cluster_id, node_id)
        sms[i] = sm
        return sm

    join = i not in initial_members
    nh.start_cluster(
        {} if join else {j: addrs[j] for j in initial_members},
        join,
        create,
        Config(cluster_id=CID, node_id=i, election_rtt=10, heartbeat_rtt=1,
               snapshot_entries=0, **cfg_kw),
    )
    return nh


def _leader(nhs, timeout=20.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        for nh in nhs.values():
            lid, ok = nh.get_leader_id(CID)
            if ok and lid in nhs:
                return lid, nhs[lid]
        time.sleep(0.02)
    raise AssertionError("no leader")


def _propose_ok(leader, cmd, timeout=10.0):
    s = leader.get_noop_session(CID)
    rs = leader.propose(s, cmd, timeout=timeout)
    return rs.wait(timeout).completed


def test_observer_replicates_and_promotes():
    router = ChanRouter()
    addrs = {i: f"ow{i}:1" for i in (1, 2, 3, 4)}
    sms = {}
    nhs = {i: _mk(i, router, sms, addrs, (1, 2, 3)) for i in (1, 2, 3)}
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        assert _propose_ok(leader, b"a=1")
        # add node 4 as a non-voting observer, then start it with join=True
        leader.sync_request_add_observer(CID, 4, addrs[4], timeout=10.0)
        nhs[4] = _mk(4, router, sms, addrs, (1, 2, 3),
                     is_observer=True)
        # the observer catches up with replicated entries
        assert _propose_ok(leader, b"b=2")
        deadline = time.time() + 20
        while time.time() < deadline:
            if sms.get(4) is not None and sms[4].kv.get("b") == "2":
                break
            time.sleep(0.05)
        assert sms[4].kv.get("b") == "2", "observer never caught up"
        # the observer never becomes leader / never votes: membership says so
        m = leader.sync_get_cluster_membership(CID, timeout=10.0)
        assert 4 in m.observers and 4 not in m.addresses
        # promote: add_node on the same id turns the observer into a voter
        leader.sync_request_add_node(CID, 4, addrs[4], timeout=10.0)
        deadline = time.time() + 20
        while time.time() < deadline:
            m = leader.sync_get_cluster_membership(CID, timeout=10.0)
            if 4 in m.addresses and 4 not in m.observers:
                break
            time.sleep(0.1)
        assert 4 in m.addresses and 4 not in m.observers
        # the promoted voter participates: writes still commit after
        # stopping one ORIGINAL voter (quorum now needs 3 of 4)
        assert _propose_ok(leader, b"c=3")
        stop_id = next(i for i in (1, 2, 3) if i != lid)
        nhs[stop_id].stop()
        del nhs[stop_id]
        lid2, leader = _leader(nhs)
        assert _propose_ok(leader, b"d=4", timeout=15.0), (
            "cluster with promoted observer lost availability"
        )
    finally:
        for nh in nhs.values():
            nh.stop()


def test_witness_votes_but_stores_no_payloads():
    router = ChanRouter()
    addrs = {i: f"wt{i}:1" for i in (1, 2, 3)}
    sms = {}
    # 2 full replicas; the witness is ADDED then joins (witnesses are never
    # part of the bootstrap membership — reference startCluster semantics)
    nhs = {
        1: _mk(1, router, sms, addrs, (1, 2)),
        2: _mk(2, router, sms, addrs, (1, 2)),
    }
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader({1: nhs[1], 2: nhs[2]})
        assert _propose_ok(leader, b"pre=w")
        leader.sync_request_add_witness(CID, 3, addrs[3], timeout=10.0)
        nhs[3] = _mk(3, router, sms, addrs, (1, 2), is_witness=True)
        deadline = time.time() + 20
        while time.time() < deadline:
            m = leader.sync_get_cluster_membership(CID, timeout=10.0)
            if 3 in m.witnesses:
                break
            time.sleep(0.1)
        assert 3 in m.witnesses
        for j in range(10):
            assert _propose_ok(leader, f"k{j}=v{j}".encode())
        # give replication a beat to reach the witness
        deadline = time.time() + 10
        while time.time() < deadline:
            r3 = nhs[3].get_node(CID).peer.raft
            if r3.log.last_index() >= 10:
                break
            time.sleep(0.05)
        # the witness's raft log holds only metadata entries (no payloads)
        wnode = nhs[3].get_node(CID)
        r = wnode.peer.raft
        assert r.is_witness()
        ents = r.log.get_entries(
            r.log.first_index(), r.log.last_index() + 1, 1 << 62
        )
        from dragonboat_tpu.wire import EntryType

        assert ents, "witness received no entries"
        # application payloads are stripped to METADATA; config changes are
        # replicated in full (the witness needs membership)
        assert all(
            e.type in (EntryType.METADATA, EntryType.CONFIG_CHANGE)
            or not e.cmd
            for e in ents
        ), "witness stored application payloads"
        # witness's SM applies nothing
        assert sms[3].kv == {}
        # availability with witness as the tie-breaker: stop the non-leader
        # full replica; leader + witness still form a quorum of 2/3
        stop_id = 2 if lid == 1 else 1
        nhs[stop_id].stop()
        del nhs[stop_id]
        time.sleep(0.5)
        assert _propose_ok(nhs[lid], b"tie=breaker", timeout=15.0), (
            "leader+witness quorum failed to commit"
        )
    finally:
        for nh in nhs.values():
            nh.stop()


def test_user_operations_on_witness_are_rejected():
    """Reference node.go:352-442 (ErrInvalidOperation) — a witness
    replica serves NO user operations: proposals (plain, batch and
    session ops), reads, config changes, snapshot requests and leader
    transfers are all rejected locally, before anything is enqueued.
    Ports TestConfigChangeOnWitnessWillBeRejected / ReadOnWitness /
    MakingProposalOnWitnessNode / ProposingSessionOnWitnessNode /
    RequestingSnapshotOnWitness (node_test.go)."""
    from dragonboat_tpu import InvalidOperationError
    from dragonboat_tpu.rsm import SSReqType, SSRequest

    router = ChanRouter()
    addrs = {i: f"wr{i}:1" for i in (1, 2, 3)}
    sms = {}
    nhs = {
        1: _mk(1, router, sms, addrs, (1, 2)),
        2: _mk(2, router, sms, addrs, (1, 2)),
    }
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader({1: nhs[1], 2: nhs[2]})
        assert _propose_ok(leader, b"pre=w")
        leader.sync_request_add_witness(CID, 3, addrs[3], timeout=10.0)
        nhs[3] = _mk(3, router, sms, addrs, (1, 2), is_witness=True)
        wnode = nhs[3].get_node(CID)
        deadline = time.time() + 20
        while time.time() < deadline and not wnode.peer.raft.is_witness():
            time.sleep(0.1)
        assert wnode.peer.raft.is_witness()

        s = nhs[3].get_noop_session(CID)
        with pytest.raises(InvalidOperationError):
            nhs[3].propose(s, b"k=v", timeout=5.0)
        with pytest.raises(InvalidOperationError):
            wnode.propose_batch(s, [b"k=v"], 5.0)
        with pytest.raises(InvalidOperationError):
            wnode.propose_session(s, 5.0)
        with pytest.raises(InvalidOperationError):
            nhs[3].sync_read(CID, "pre", timeout=5.0)
        with pytest.raises(InvalidOperationError):
            nhs[3].request_add_node(CID, 9, "wr9:1", timeout=5.0)
        with pytest.raises(InvalidOperationError):
            wnode.request_snapshot(
                SSRequest(type=SSReqType.USER_REQUESTED), 5.0
            )
        with pytest.raises(InvalidOperationError):
            wnode.request_leader_transfer(1, 5.0)
        # the full replicas still serve everything
        assert _propose_ok(leader, b"post=w")
    finally:
        for nh in nhs.values():
            nh.stop()


def test_payload_too_big_rejected():
    """Reference node.go:363-381 (ErrPayloadTooBig): with
    max_in_mem_log_size configured, an oversized payload is rejected
    before it is enqueued; a small one passes."""
    from dragonboat_tpu import PayloadTooBigError

    router = ChanRouter()
    addrs = {1: "pb1:1"}
    sms = {}
    nh = _mk(1, router, sms, addrs, (1,), max_in_mem_log_size=64 * 1024)
    try:
        nh.get_node(CID).request_campaign()
        _leader({1: nh})
        s = nh.get_noop_session(CID)
        assert _propose_ok(nh, b"small=ok")
        with pytest.raises(PayloadTooBigError):
            nh.propose(s, b"x" * (64 * 1024), timeout=5.0)
        node = nh.get_node(CID)
        with pytest.raises(PayloadTooBigError):
            node.propose_batch(s, [b"ok", b"y" * (64 * 1024)], 5.0)
    finally:
        nh.stop()


def test_stale_read_on_witness_rejected():
    """A witness's SM never applies payloads, so even the relaxed
    stale-read path must refuse (reference StaleRead:
    ErrInvalidOperation) rather than answer from permanently empty
    state."""
    from dragonboat_tpu import InvalidOperationError

    router = ChanRouter()
    addrs = {i: f"sr{i}:1" for i in (1, 2, 3)}
    sms = {}
    nhs = {
        1: _mk(1, router, sms, addrs, (1, 2)),
        2: _mk(2, router, sms, addrs, (1, 2)),
    }
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader({1: nhs[1], 2: nhs[2]})
        assert _propose_ok(leader, b"sk=sv")
        leader.sync_request_add_witness(CID, 3, addrs[3], timeout=10.0)
        nhs[3] = _mk(3, router, sms, addrs, (1, 2), is_witness=True)
        wnode = nhs[3].get_node(CID)
        deadline = time.time() + 20
        while time.time() < deadline and not wnode.peer.raft.is_witness():
            time.sleep(0.1)
        with pytest.raises(InvalidOperationError):
            nhs[3].stale_read(CID, "sk")
        # the full replicas still serve stale reads
        deadline = time.time() + 10
        while time.time() < deadline and leader.stale_read(CID, "sk") != "sv":
            time.sleep(0.05)
        assert leader.stale_read(CID, "sk") == "sv"
    finally:
        for nh in nhs.values():
            nh.stop()
