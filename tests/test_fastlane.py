"""Integration tests for the native replication fast lane.

Three NodeHosts over the real framed-TCP transport with the durable native
LogDB — the deployment shape where `ExpertConfig.fast_lane` activates.
Covers: enrollment at quiescence, native steady-state replication with
client completion, in-lane ReadIndex on both leader and followers (zero
ejects), observer/witness-bearing enrollment, follower and leader
kill/restart recovery through the eject protocol, and full-cluster
restart replaying natively written WAL records through the Python path.
"""
from __future__ import annotations

import socket

from tests import loadwait
import time

import pytest

from dragonboat_tpu import Config, NodeHost, NodeHostConfig, Result
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.native import natraft

# heavy multi-NodeHost tests never overlap each other (the lock in
# tests/conftest.py): side by side they starve each other on an 8-vCPU box
pytestmark = [pytest.mark.skipif(
    not natraft.available(), reason="libnatraft unavailable"
), pytest.mark.xdist_group("heavy-multiprocess")]

RTT = 20
CID = 31


class CountSM:
    def __init__(self, cluster_id, node_id):
        self.applied = []

    def update(self, cmd):
        self.applied.append(bytes(cmd))
        return Result(value=len(self.applied))

    def lookup(self, query):
        return list(self.applied)

    def save_snapshot(self, w, files, done):
        import json

        data = json.dumps([c.decode() for c in self.applied]).encode()
        w.write(len(data).to_bytes(8, "little") + data)

    def recover_from_snapshot(self, r, files, done):
        import json

        n = int.from_bytes(r.read(8), "little")
        self.applied = [c.encode() for c in json.loads(r.read(n).decode())]

    def close(self):
        pass


def _ports(n):
    return loadwait.ports(n)


def _mk(i, addrs, tmp_path, sms, snapshot_entries=0, join=False,
        is_observer=False, is_witness=False, initial=None):
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=str(tmp_path / f"nh{i}"),
            rtt_millisecond=RTT,
            raft_address=addrs[i],
            expert=ExpertConfig(fast_lane=True, logdb_shards=2),
        )
    )
    assert nh.fastlane is not None and nh.fastlane.enabled

    def create(cluster_id, node_id):
        sm = CountSM(cluster_id, node_id)
        sms[i] = sm
        return sm

    nh.start_cluster(
        {} if join else (initial if initial is not None else addrs),
        join, create,
        Config(cluster_id=CID, node_id=i, election_rtt=10, heartbeat_rtt=1,
               snapshot_entries=snapshot_entries, compaction_overhead=5,
               is_observer=is_observer, is_witness=is_witness),
    )
    return nh


def _cluster(tmp_path, sms, n=3):
    ports = _ports(n)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(n)}
    nhs = {i: _mk(i, addrs, tmp_path, sms) for i in addrs}
    return nhs, addrs


def _leader(nhs, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        for nh in nhs.values():
            try:
                lid, ok = nh.get_leader_id(CID)
                if ok and lid in nhs:
                    return lid, nhs[lid]
            except Exception:
                pass
        time.sleep(0.05)
    raise TimeoutError("no leader")


def _wait_enrolled(nh, timeout=45.0, want=True):
    node = nh.get_node(CID)
    deadline = time.time() + timeout
    while time.time() < deadline:
        if node.fast_lane == want:
            return True
        time.sleep(0.05)
    return False


def _propose_all(nh, payloads, deadline_s=180.0):
    """Exact-count helper: every payload must complete exactly once, so
    timed-out proposes are NOT retried (outcome unknown -> duplicate
    risk); instead the tick budget is generous and completion is waited
    to a shared wall deadline, so CI starvation stretches runtime, not
    the verdict."""
    s = nh.get_noop_session(CID)
    deadline = time.time() + deadline_s
    pending = [nh.propose(s, p, timeout=60.0) for p in payloads]
    for rs in pending:
        r = rs.wait(max(0.1, deadline - time.time()))
        assert r.completed, r
    return len(pending)


def _propose_through_lane(leader, tag, n):
    """Batches of ``n`` payloads until one rode the native lane; how many
    payloads were proposed in all.  The lane can EJECT under full-suite
    load between an enroll check and the proposals (liveness timeouts on
    a starved box — the r07 contention-flake class): retry through
    re-enrollment instead of asserting on a single window.  A genuinely
    broken lane never carries a batch and still fails here."""
    sent = 0
    for attempt in range(4):
        st0 = leader.fastlane.stats()
        sent += _propose_all(
            leader, [b"%s%d-%d" % (tag, attempt, i) for i in range(n)]
        )
        if leader.fastlane.stats()["proposed"] > st0["proposed"]:
            return sent
        assert _wait_enrolled(leader), "lane never re-enrolled"
    raise AssertionError(
        f"fast lane carried no proposals in 4 batches: "
        f"{leader.fastlane.stats()}"
    )


def _wait_converged(sms, count, timeout=90.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        lens = [len(sm.applied) for sm in sms.values()]
        if all(n == count for n in lens):
            return True
        time.sleep(0.1)
    raise AssertionError(
        f"replicas did not converge: {[len(sm.applied) for sm in sms.values()]}"
        f" != {count}"
    )


def _stop_all(nhs):
    # regression pin (round-3 chaos failure): a span delivered before the
    # node was registered was dropped, losing committed entries from the
    # apply stream; registration now precedes native enrollment, so this
    # must never fire
    drops = {
        i: nh.fastlane.dropped_spans
        for i, nh in nhs.items()
        if nh.fastlane is not None and nh.fastlane.enabled
    }
    for nh in nhs.values():
        try:
            nh.stop()
        except Exception:
            pass
    assert all(v == 0 for v in drops.values()), f"dropped apply spans: {drops}"


def test_enroll_and_native_replication(tmp_path):
    sms = {}
    nhs, _ = _cluster(tmp_path, sms)
    try:
        lid, leader = _leader(nhs)
        assert _wait_enrolled(leader), "leader never enrolled"
        n = _propose_all(leader, [b"k%d" % i for i in range(200)])
        _wait_converged(sms, n)
        # under full-suite load an eject window can push a slice of a
        # batch to the scalar path while the cluster stays healthy (the
        # r07 contention-flake class): top up through re-enrollment
        # until the lane has provably carried >= 200 proposals; a
        # genuinely broken lane never accumulates them and still fails
        for attempt in range(4):
            if leader.fastlane.stats()["proposed"] >= 200:
                break
            assert _wait_enrolled(leader), "lane never re-enrolled"
            n += _propose_all(
                leader, [b"t%d-%d" % (attempt, i) for i in range(100)]
            )
            _wait_converged(sms, n)
        st = leader.fastlane.stats()
        assert st["proposed"] >= 200, st
        assert st["commits_advanced"] > 0
        # followers served acks natively once enrolled
        total_fast = sum(nh.fastlane.stats()["ingested_fast"] for nh in nhs.values())
        assert total_fast > 0
        # order is identical across replicas
        base = sms[lid].applied
        for i, sm in sms.items():
            assert sm.applied == base, f"replica {i} diverged"
    finally:
        _stop_all(nhs)


def test_leader_read_index_served_natively(tmp_path):
    """Historic name: reads used to force an eject; since the native
    ReadIndex (hinted heartbeats + echo quorum) the leader serves them
    in-lane — assert the read completes AND costs no eject."""
    sms = {}
    nhs, _ = _cluster(tmp_path, sms)
    try:
        lid, leader = _leader(nhs)
        assert _wait_enrolled(leader)
        _propose_all(leader, [b"a", b"b", b"c"])
        node = leader.get_node(CID)
        before = dict(leader.fastlane.eject_reasons)
        got = leader.sync_read(CID, None, timeout=10.0)
        assert len(got) == 3
        assert node.fast_lane, "leader read should not leave the lane"
        assert leader.fastlane.eject_reasons == before
        _propose_all(leader, [b"d"])
        _wait_converged(sms, 4)
        assert not node._stopped.is_set()
    finally:
        _stop_all(nhs)


def test_follower_kill_and_restart(tmp_path):
    sms = {}
    nhs, addrs = _cluster(tmp_path, sms)
    try:
        lid, leader = _leader(nhs)
        assert _wait_enrolled(leader)
        _propose_all(leader, [b"w%d" % i for i in range(20)])
        victim = next(i for i in nhs if i != lid)
        nhs[victim].stop()
        # quorum holds: native leader keeps committing with one follower
        _propose_all(leader, [b"x%d" % i for i in range(20)])
        # restart the follower; recovery runs through the scalar path
        nhs[victim] = _mk(victim, addrs, tmp_path, sms)
        _propose_all(leader, [b"y%d" % i for i in range(20)])
        _wait_converged(sms, 60, timeout=60.0)
    finally:
        _stop_all(nhs)


def test_leader_kill_failover(tmp_path):
    sms = {}
    nhs, addrs = _cluster(tmp_path, sms)
    try:
        lid, leader = _leader(nhs)
        assert _wait_enrolled(leader)
        _propose_all(leader, [b"p%d" % i for i in range(10)])
        nhs.pop(lid).stop()
        # followers eject on contact loss and elect a new leader scalar-side
        new_lid, new_leader = _leader(nhs, timeout=90.0)
        assert new_lid != lid
        _propose_all(new_leader, [b"q%d" % i for i in range(10)])
        live = {i: sm for i, sm in sms.items() if i in nhs}
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(len(sm.applied) == 20 for sm in live.values()):
                break
            time.sleep(0.1)
        assert all(len(sm.applied) == 20 for sm in live.values())
    finally:
        _stop_all(nhs)


def test_full_restart_replays_native_wal(tmp_path):
    """Entries written by the native core must replay through the normal
    Python recovery path (byte-identical record formats)."""
    sms = {}
    nhs, addrs = _cluster(tmp_path, sms)
    lid, leader = _leader(nhs)
    assert _wait_enrolled(leader)
    _propose_all(leader, [b"r%d" % i for i in range(30)])
    _wait_converged(sms, 30)
    _stop_all(nhs)

    sms2 = {}
    nhs2 = {i: _mk(i, addrs, tmp_path, sms2) for i in addrs}
    try:
        lid2, leader2 = _leader(nhs2, timeout=90.0)
        _propose_all(leader2, [b"s%d" % i for i in range(5)])
        _wait_converged(sms2, 35, timeout=120.0)
        base = sms2[lid2].applied
        assert base[:30] == [b"r%d" % i for i in range(30)]
    finally:
        _stop_all(nhs2)


def test_periodic_snapshot_forces_eject(tmp_path):
    """snapshot_entries > 0: the enrolled step detects the due snapshot,
    ejects, and the normal auto-snapshot machinery runs."""
    sms = {}
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
    nhs = {
        i: _mk(i, addrs, tmp_path, sms, snapshot_entries=25) for i in addrs
    }
    try:
        lid, leader = _leader(nhs)
        node = leader.get_node(CID)
        _propose_all(leader, [b"z%d" % i for i in range(80)])
        _wait_converged(sms, 80)
        deadline = time.time() + 30
        while time.time() < deadline:
            if node.sm.get_snapshot_index() > 0:
                break
            time.sleep(0.2)
        assert node.sm.get_snapshot_index() > 0, "auto snapshot never ran"
    finally:
        _stop_all(nhs)


def test_propose_batch_both_paths(tmp_path):
    """propose_batch == N propose calls: one future per command, applied
    in order, on the native lane and on the scalar fallback."""
    sms = {}
    nhs, _ = _cluster(tmp_path, sms)
    try:
        lid, leader = _leader(nhs)
        assert _wait_enrolled(leader)
        s = leader.get_noop_session(CID)
        states = leader.propose_batch(s, [b"b%d" % i for i in range(40)], 10.0)
        assert len(states) == 40
        for rs in states:
            assert rs.wait(30.0).completed
        # force the scalar path (eject via a leader transfer request slot
        # check is heavyweight; simply eject directly) and batch again
        node = leader.get_node(CID)
        node.fast_eject()
        states = leader.propose_batch(s, [b"c%d" % i for i in range(40)], 10.0)
        for rs in states:
            assert rs.wait(30.0).completed
        _wait_converged(sms, 80)
        base = sms[lid].applied
        assert base == [b"b%d" % i for i in range(40)] + [
            b"c%d" % i for i in range(40)
        ]
        for i, sm in sms.items():
            assert sm.applied == base
    finally:
        _stop_all(nhs)


def test_follower_read_served_natively_no_eject(tmp_path):
    """A linearizable read on an enrolled FOLLOWER forwards natively
    (READ_INDEX to the leader, READ_INDEX_RESP back — natraft twins of
    handle_follower_read_index / handle_follower_read_index_resp,
    raft.py:1258,1271) and completes without costing the group an
    eject/re-enroll cycle."""
    sms = {}
    nhs, _ = _cluster(tmp_path, sms)
    try:
        lid, leader = _leader(nhs)
        _propose_all(leader, [b"a", b"b", b"c"])
        fid = next(i for i in nhs if i != lid)
        follower = nhs[fid]
        assert _wait_enrolled(follower)
        node = follower.get_node(CID)
        before = dict(follower.fastlane.eject_reasons)
        for _ in range(5):
            got = follower.sync_read(CID, None, timeout=10.0)
            assert len(got) == 3
        assert node.fast_lane, "follower read should not leave the lane"
        after = follower.fastlane.eject_reasons
        assert after.get("read", 0) == before.get("read", 0)
        assert after.get("read-fallback", 0) == before.get("read-fallback", 0)
        # the leader meanwhile keeps its own native read service
        assert len(leader.sync_read(CID, None, timeout=10.0)) == 3
    finally:
        _stop_all(nhs)


def test_observer_group_enrolls_and_replicates(tmp_path):
    """A group WITH an observer still enrolls (observers become
    non-voting native replication targets — reference nonVoting member
    semantics); proposals commit at voter quorum through the lane, and
    the observer's SM catches up from natively-proposed entries."""
    sms = {}
    ports = _ports(4)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(4)}
    voters = {i: addrs[i] for i in (1, 2, 3)}
    nhs = {i: _mk(i, addrs, tmp_path, sms, initial=voters) for i in (1, 2, 3)}
    try:
        lid, leader = _leader(nhs)
        _propose_all(leader, [b"a", b"b"])
        leader.sync_request_add_observer(CID, 4, addrs[4], timeout=30.0)
        nhs[4] = _mk(4, addrs, tmp_path, sms, join=True, is_observer=True)
        # the config change ejected; the group must RE-enroll with the
        # observer present (the old eligibility refused observer-bearing
        # groups outright)
        assert _wait_enrolled(leader), "observer-bearing group never enrolled"
        want = 2 + _propose_through_lane(leader, b"c", 30)
        # the observer (never part of quorum) still receives everything
        deadline = time.time() + 30
        while time.time() < deadline:
            if sms.get(4) is not None and len(sms[4].applied) == want:
                break
            time.sleep(0.05)
        assert sms.get(4) is not None and len(sms[4].applied) == want, (
            "observer did not catch up through the native lane"
        )
        # quorum stays voter-only: stop BOTH non-leader voters; with only
        # the leader + observer alive a proposal must NOT complete
        for i in (1, 2, 3):
            if i != lid:
                nhs[i].stop()
                del nhs[i]
        s = nhs[lid].get_noop_session(CID)
        rs = nhs[lid].propose(s, b"never", timeout=2.0)
        assert not rs.wait(3.0).completed, (
            "observer was counted toward the commit quorum"
        )
    finally:
        _stop_all(nhs)


def test_witness_group_enrolls_and_witness_ack_commits(tmp_path):
    """A witness-bearing group enrolls; the witness receives metadata-only
    native replication and its ack CARRIES quorum weight: with one voter
    stopped, leader + witness keep committing (reference witness role)."""
    sms = {}
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
    voters = {i: addrs[i] for i in (1, 2)}
    nhs = {i: _mk(i, addrs, tmp_path, sms, initial=voters) for i in (1, 2)}
    try:
        lid, leader = _leader(nhs)
        _propose_all(leader, [b"pre"])
        leader.sync_request_add_witness(CID, 3, addrs[3], timeout=30.0)
        nhs[3] = _mk(3, addrs, tmp_path, sms, join=True, is_witness=True)
        deadline = time.time() + 20
        while time.time() < deadline:
            m = leader.sync_get_cluster_membership(CID, timeout=10.0)
            if 3 in m.witnesses:
                break
            time.sleep(0.1)
        assert 3 in m.witnesses
        assert _wait_enrolled(leader), "witness-bearing group never enrolled"
        _propose_through_lane(leader, b"w", 20)
        # the witness's scalar log holds only metadata twins
        r3 = nhs[3].get_node(CID).peer.raft
        deadline = time.time() + 20
        while time.time() < deadline and r3.log.last_index() < 22:
            time.sleep(0.05)
        from dragonboat_tpu.wire import EntryType

        # under the group's lock: its step worker appends and persists
        # meanwhile, and a read between the two finds a hole
        with nhs[3].get_node(CID).raft_mu:
            ents = r3.log.get_entries(
                r3.log.first_index(), r3.log.last_index() + 1, 1 << 62
            )
        assert ents and all(
            e.type in (EntryType.METADATA, EntryType.CONFIG_CHANGE)
            for e in ents
        ), "witness log must hold only METADATA/CONFIG_CHANGE entries"
        # stop the OTHER voter: leader + witness = 2 of 3 voting members,
        # proposals must still complete (the witness ack is the quorum)
        other = next(i for i in (1, 2) if i != lid)
        nhs[other].stop()
        del nhs[other]
        _propose_all(nhs[lid], [b"after-voter-loss"])
    finally:
        _stop_all(nhs)
