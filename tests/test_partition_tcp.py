"""Asymmetric network partitions over the REAL transport.

The reference's monkey harness partitions NodeHosts at the transport
layer (``monkey.go:184-213``).  Here the injection lives in the native
engine (``natr_set_partition``): in fast-lane deployments every raft
message for a remote — fast-path AND scalar-path — rides the single
ordered native stream, so dropping at the ingest choke point (inbound)
and the flush pass (outbound) is a true netsplit: a partitioned leader
loses its quorum, the majority side elects and commits without it, and
healing lets the protocol's own machinery (resends, ejects,
re-enrollment, catch-up) reconverge the fleet.
"""
from __future__ import annotations

import time

import pytest

from tests import loadwait

from dragonboat_tpu import Config, NodeHost, NodeHostConfig
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.native import natraft, natsm
from dragonboat_tpu.native.natsm import NativeKVStateMachine

pytestmark = [pytest.mark.skipif(
    not (natraft.available() and natsm.available()),
    reason="native libraries unavailable",
), pytest.mark.xdist_group("heavy-multiprocess")]

CID = 61


def _ports(n):
    return loadwait.ports(n)


def _mk(i, addrs, tmp_path, sms):
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=str(tmp_path / f"nh{i}"),
            rtt_millisecond=20,
            raft_address=addrs[i],
            expert=ExpertConfig(fast_lane=True, logdb_shards=2),
        )
    )

    def create(cluster_id, node_id):
        sm = NativeKVStateMachine(cluster_id, node_id)
        sms[i] = sm
        return sm

    nh.start_cluster(
        addrs, False, create,
        Config(cluster_id=CID, node_id=i, election_rtt=10, heartbeat_rtt=1,
               check_quorum=True, snapshot_entries=0),
    )
    return nh


def _leader_id(nhs, exclude=None, timeout=60.0):
    # load-scaled (tests/loadwait.py): elections under a loaded tier-1
    # sweep stretch far past the idle-box margin (r07/r11 flake class).
    # The budget RE-SAMPLES while waiting (the r14 wait_until treatment)
    # — a deadline priced at an idle instant underprices a heavy
    # neighbor spinning up mid-election
    start = time.time()
    budget = loadwait.scaled(timeout)
    while True:
        for i, nh in nhs.items():
            if exclude is not None and i == exclude:
                continue  # the isolated rank's own (stale) view
            try:
                lid, ok = nh.get_leader_id(CID)
                if ok and lid in nhs and lid != exclude:
                    return lid
            except Exception:
                pass
        budget = max(budget, timeout * loadwait.scale())
        if time.time() - start >= budget:
            raise TimeoutError("no leader")
        time.sleep(0.05)


def _write(nhs, payload, exclude=None):
    """A client's write: to whoever leads now (never the isolated rank),
    asked again when an election drops it or runs it out — a set of one
    key is idempotent."""
    def once():
        try:
            nh = nhs[_leader_id(nhs, exclude=exclude, timeout=5.0)]
        except TimeoutError:
            return False
        rs = nh.propose(nh.get_noop_session(CID), payload, timeout=5.0)
        return rs.wait(10.0).completed

    loadwait.wait_until(once, 120.0, what=f"{payload!r} commits")


def test_partitioned_leader_deposed_then_heals(tmp_path):
    sms = {}
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
    nhs = {i: _mk(i, addrs, tmp_path, sms) for i in addrs}
    try:
        nhs[1].get_node(CID).request_campaign()
        _leader_id(nhs)
        for j in range(50):
            _write(nhs, f"a{j}=b{j}".encode())
        lid = _leader_id(nhs)
        leader = nhs[lid]

        # full symmetric netsplit: {leader} | {other two}
        others = [i for i in nhs if i != lid]
        for i in others:
            nhs[i].fastlane.set_partition(addrs[lid], True)
            leader.fastlane.set_partition(addrs[i], True)

        # the majority side must elect a replacement and commit without
        # the isolated rank
        new_lid = _leader_id(nhs, exclude=lid, timeout=90.0)
        assert new_lid != lid
        nh2 = nhs[new_lid]
        for j in range(50):
            _write(nhs, f"c{j}=d{j}".encode(), exclude=lid)
        assert nh2.sync_read(CID, "c49", timeout=20.0) == "d49"

        # the partition actually dropped traffic at the native layer
        dropped = sum(
            nhs[i].fastlane.stats().get("part_in_dropped", 0)
            + nhs[i].fastlane.stats().get("part_out_dropped", 0)
            for i in nhs
        )
        assert dropped > 0, "partition injection never dropped a message"

        # heal; the deposed rank rejoins and catches up
        for i in others:
            nhs[i].fastlane.set_partition(addrs[lid], False)
            leader.fastlane.set_partition(addrs[i], False)
        deadline = time.time() + loadwait.scaled(90.0)
        while time.time() < deadline:
            hs = {i: sm.get_hash() for i, sm in sms.items()}
            if len(set(hs.values())) == 1:
                break
            time.sleep(0.2)
        assert len(set(hs.values())) == 1, f"diverged after heal: {hs}"

        # and the healed fleet still commits.  The healed rank comes back
        # at a higher term and may force one more election: a proposal in
        # flight across it is dropped, by Raft's own rules, and a client
        # asks again
        _write(nhs, b"post=heal")
        assert nh2.sync_read(CID, "post", timeout=20.0) == "heal"
    finally:
        for nh in nhs.values():
            nh.stop()


def test_partition_minority_follower_no_disruption(tmp_path):
    """Isolating ONE follower must not disturb the majority: the leader
    keeps committing throughout, and the follower reconverges on heal."""
    sms = {}
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
    nhs = {i: _mk(i, addrs, tmp_path, sms) for i in addrs}
    try:
        nhs[1].get_node(CID).request_campaign()
        lid = _leader_id(nhs)
        leader = nhs[lid]
        s = leader.get_noop_session(CID)
        for j in range(30):
            assert leader.propose(
                s, f"w{j}=x{j}".encode(), timeout=60.0
            ).wait(120.0).completed

        victim = [i for i in nhs if i != lid][0]
        for i in nhs:
            if i != victim:
                nhs[i].fastlane.set_partition(addrs[victim], True)
                nhs[victim].fastlane.set_partition(addrs[i], True)

        for j in range(60):
            assert leader.propose(
                s, f"m{j}=n{j}".encode(), timeout=60.0
            ).wait(120.0).completed
        # the leader never lost its quorum: still the same leader (no
        # wall-clock assert — per-op completion + stable leadership is
        # the load-tolerant form of "no disruption")
        cur, ok = leader.get_leader_id(CID)
        assert ok and cur == lid, (cur, lid)

        for i in nhs:
            if i != victim:
                nhs[i].fastlane.set_partition(addrs[victim], False)
                nhs[victim].fastlane.set_partition(addrs[i], False)
        deadline = time.time() + loadwait.scaled(90.0)
        while time.time() < deadline:
            hs = {i: sm.get_hash() for i, sm in sms.items()}
            if len(set(hs.values())) == 1:
                break
            time.sleep(0.2)
        assert len(set(hs.values())) == 1, f"diverged after heal: {hs}"
    finally:
        for nh in nhs.values():
            nh.stop()


def test_partition_blocks_snapshot_catchup_until_heal(tmp_path):
    """The snapshot path must respect the partition too (it rides its own
    transfer connections, not the native streams): a partitioned lagging
    follower stays stale — no snapshot sneaks through the split — and
    catches up only after heal (by whatever mix of entries/snapshot the
    leader chooses)."""
    sms = {}
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
    nhs = {}
    for i in addrs:
        nh = NodeHost(
            NodeHostConfig(
                node_host_dir=str(tmp_path / f"nh{i}"),
                rtt_millisecond=20,
                raft_address=addrs[i],
                expert=ExpertConfig(fast_lane=True, logdb_shards=2),
            )
        )

        def create(cluster_id, node_id, i=i):
            sm = NativeKVStateMachine(cluster_id, node_id)
            sms[i] = sm
            return sm

        nh.start_cluster(
            addrs, False, create,
            Config(cluster_id=CID, node_id=i, election_rtt=10,
                   heartbeat_rtt=1, check_quorum=True,
                   snapshot_entries=40, compaction_overhead=5),
        )
        nhs[i] = nh
    try:
        nhs[1].get_node(CID).request_campaign()
        for j in range(30):
            _write(nhs, f"a{j}=b{j}".encode())
        lid = _leader_id(nhs)

        victim = [i for i in nhs if i != lid][0]
        # settle BEFORE partitioning: pre-split entries may still be in
        # the victim's apply pipeline, and a baseline captured mid-flight
        # would later read as a "leak" when they finish applying
        deadline = time.time() + loadwait.scaled(60.0)
        while time.time() < deadline:
            if len({sm.get_hash() for sm in sms.values()}) == 1:
                break
            time.sleep(0.1)
        assert len({sm.get_hash() for sm in sms.values()}) == 1
        for i in nhs:
            if i != victim:
                nhs[i].fastlane.set_partition(addrs[victim], True)
                nhs[victim].fastlane.set_partition(addrs[i], True)
        stale = sms[victim].get_hash()

        # push the leader far past several snapshot boundaries so catching
        # the victim up will want a snapshot, not just entries
        for j in range(160):
            _write(nhs, f"z{j}=w{j}".encode(), exclude=victim)
        time.sleep(2.0)  # window in which a leaky snapshot would land
        assert sms[victim].get_hash() == stale, (
            "snapshot/entries leaked through the partition"
        )

        for i in nhs:
            if i != victim:
                nhs[i].fastlane.set_partition(addrs[victim], False)
                nhs[victim].fastlane.set_partition(addrs[i], False)
        deadline = time.time() + loadwait.scaled(120.0)
        while time.time() < deadline:
            hs = {i: sm.get_hash() for i, sm in sms.items()}
            if len(set(hs.values())) == 1:
                break
            time.sleep(0.2)
        assert len(set(hs.values())) == 1, f"victim never caught up: {hs}"
    finally:
        for nh in nhs.values():
            nh.stop()
