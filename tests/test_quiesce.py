"""Quiesce behavior at the NodeHost level.

Ports the reference's node-level quiesce family
(``/root/reference/node_test.go``: TestRaftNodeQuiesceCanBeDisabled,
TestNodesCanEnterQuiesce, TestNodesCanExitQuiesceByMakingProposal /
ByReadIndex / ByConfigChange; mechanism in ``quiesce.go``): a group with
no message activity for 10x election ticks enters quiesce on every
replica, stops heartbeating, and wakes on any user activity.  The runs
use the in-proc chan transport and a small rtt so the 10x window
elapses in wall-clock seconds.

Every case runs under both quorum engines: ``scalar`` (the host-side
``QuiesceManager``) and ``tpu`` (the device tick plane: the row's idle
clock and its sleep are columns of the tick kernel, ``Node.quiesced()``
is the host's copy of the flag).
"""
from __future__ import annotations

import time

import pytest

from dragonboat_tpu import Config, NodeHost, NodeHostConfig, Result
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.transport import ChanRouter, ChanTransport

RTT = 5
CID = 3

pytestmark = pytest.mark.parametrize("engine", ["scalar", "tpu"])


class KVSM:
    def __init__(self, cluster_id, node_id):
        self.kv = {}

    def update(self, cmd):
        k, v = cmd.decode().split("=", 1)
        self.kv[k] = v
        return Result(value=len(self.kv))

    def lookup(self, query):
        return self.kv.get(query)

    def get_hash(self):
        return 0

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


def _mk_trio(quiesce=True, engine="scalar"):
    addrs = {1: "q1:1", 2: "q2:1", 3: "q3:1"}
    router = ChanRouter()
    nhs = {}
    for i in addrs:
        nh = NodeHost(
            NodeHostConfig(
                node_host_dir=":memory:",
                rtt_millisecond=RTT,
                raft_address=addrs[i],
                raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                    src, rh, ch, router=router
                ),
                expert=ExpertConfig(
                    quorum_engine=engine, engine_block_groups=16,
                    engine_warm_fused=False,
                ),
            )
        )
        nh.start_cluster(
            addrs, False, lambda c, n: KVSM(c, n),
            Config(cluster_id=CID, node_id=i, election_rtt=10,
                   heartbeat_rtt=1, quiesce=quiesce),
        )
        nhs[i] = nh
    return nhs


def _leader(nhs, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        for nh in nhs.values():
            lid, ok = nh.get_leader_id(CID)
            if ok and lid in nhs:
                return lid, nhs[lid]
        time.sleep(0.05)
    raise AssertionError("no leader")


def _quiesced(nhs):
    return [
        nh.get_node(CID).quiesced() for nh in nhs.values()
    ]


def _wait_all_quiesced(nhs, timeout=60.0):
    """The 10x-election-tick idle window at rtt 5ms / election_rtt 10 is
    ~0.5s of ticks; generous deadline for slow CI."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(_quiesced(nhs)):
            return True
        time.sleep(0.1)
    return False


def _stop_all(nhs):
    for nh in nhs.values():
        nh.stop()


def test_nodes_can_enter_quiesce(engine):
    """Reference TestNodesCanEnterQuiesce: an idle group quiesces on
    every replica (leader included) after the idle window."""
    nhs = _mk_trio(quiesce=True, engine=engine)
    try:
        nhs[1].get_node(CID).request_campaign()
        _leader(nhs)
        assert _wait_all_quiesced(nhs), _quiesced(nhs)
    finally:
        _stop_all(nhs)


def test_quiesce_can_be_disabled(engine):
    """Reference TestRaftNodeQuiesceCanBeDisabled: with quiesce off
    (the default) the idle window never quiesces anybody."""
    nhs = _mk_trio(quiesce=False, engine=engine)
    try:
        nhs[1].get_node(CID).request_campaign()
        _leader(nhs)
        # the enter window at these settings is ~0.5s; wait well past it
        time.sleep(3.0)
        assert not any(_quiesced(nhs)), _quiesced(nhs)
    finally:
        _stop_all(nhs)


def test_exit_quiesce_by_proposal(engine):
    """Reference TestNodesCanExitQuiesceByMakingProposal — and the
    proposal commits, proving replication actually resumed."""
    nhs = _mk_trio(quiesce=True, engine=engine)
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        assert _wait_all_quiesced(nhs)
        s = leader.get_noop_session(CID)
        rs = leader.propose(s, b"k=v", timeout=30.0)
        assert rs.wait(60.0).completed
        assert not leader.get_node(CID).quiesced()
        # peers wake too (the exchanged activity exits their quiesce)
        deadline = time.time() + 30
        while time.time() < deadline and any(_quiesced(nhs)):
            time.sleep(0.1)
        assert not any(_quiesced(nhs)), _quiesced(nhs)
    finally:
        _stop_all(nhs)


def test_exit_quiesce_by_read_index(engine):
    """Reference TestNodesCanExitQuiesceByReadIndex."""
    nhs = _mk_trio(quiesce=True, engine=engine)
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        s = leader.get_noop_session(CID)
        assert leader.propose(s, b"a=b", timeout=30.0).wait(60.0).completed
        assert _wait_all_quiesced(nhs)
        v = leader.sync_read(CID, "a", timeout=30.0)
        assert v == "b"
        assert not leader.get_node(CID).quiesced()
    finally:
        _stop_all(nhs)


def test_exit_quiesce_by_config_change(engine):
    """Reference TestNodesCanExitQuiesceByConfigChange: a membership
    request wakes the group and completes."""
    nhs = _mk_trio(quiesce=True, engine=engine)
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        assert _wait_all_quiesced(nhs)
        rs = leader.request_add_observer(CID, 9, "q9:1", timeout=30.0)
        assert rs.wait(60.0).completed
        assert not leader.get_node(CID).quiesced()
        members = leader.sync_get_cluster_membership(CID, timeout=30.0)
        assert 9 in members.observers
    finally:
        _stop_all(nhs)


def test_requiesce_after_activity_settles(engine):
    """After a wake, a second idle window re-enters quiesce — the cycle
    is repeatable, not one-shot (quiesce.go's tick clock resets on
    activity)."""
    nhs = _mk_trio(quiesce=True, engine=engine)
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        assert _wait_all_quiesced(nhs)
        s = leader.get_noop_session(CID)
        assert leader.propose(s, b"x=1", timeout=30.0).wait(60.0).completed
        assert not leader.get_node(CID).quiesced()
        assert _wait_all_quiesced(nhs), "group never re-quiesced"
    finally:
        _stop_all(nhs)
