"""Streamed-snapshot catch-up for a far-behind restarted follower.

Round-4 soak caught a wedge here: a restore Update can carry BOTH the
snapshot and log entries past it, and appending the entries before the
LogReader window moved tripped the gap check — the committer then
retried the same update forever and the replica froze (applied below
commit through a 90s settle).  This pins the deterministic shape: a
follower restarts so far behind a compacted leader log that catch-up
MUST stream a snapshot while writes keep racing it.
"""
from __future__ import annotations

import socket

from tests import loadwait
import threading
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
CID = 55


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


def _ports(n):
    return loadwait.ports(n)


def _mk(i, addrs, tmp_path, sms):
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=str(tmp_path / f"nh{i}"),
            rtt_millisecond=RTT,
            raft_address=addrs[i],
            expert=ExpertConfig(fast_lane=True, logdb_shards=2),
        )
    )

    def create(cluster_id, node_id):
        sm = KVSM(cluster_id, node_id)
        sms[i] = sm
        return sm

    nh.start_cluster(
        addrs, False, create,
        Config(cluster_id=CID, node_id=i, election_rtt=10, heartbeat_rtt=1,
               # aggressive: the leader log compacts far behind a stopped
               # follower fast, forcing the streamed-snapshot path
               snapshot_entries=25, compaction_overhead=5),
    )
    return nh


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
    raise AssertionError("no leader")


def test_far_behind_follower_streams_snapshot_under_load(tmp_path):
    addrs = {i: f"127.0.0.1:{p}" for i, p in enumerate(_ports(3), start=1)}
    sms = {}
    nhs = {i: _mk(i, addrs, tmp_path, sms) for i in (1, 2, 3)}
    stop = threading.Event()
    done = [0]
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        s = leader.get_noop_session(CID)

        def put(j, deadline):
            # retry timed-out proposes until the deadline: on a starved CI
            # box a single write can time out without implying anything
            # about snapshot catch-up (the thing under test); 3 s an
            # attempt, so that one the lane loses costs the test 3 s
            while True:
                rs = leader.propose(s, f"w{j}=a{j}".encode(), timeout=3.0)
                if rs.wait(10.0).completed:
                    return
                assert time.time() < deadline, f"write w{j} never completed"

        deadline = time.time() + 240
        for j in range(40):
            put(j, deadline)
        # stop a follower, push FAR past its log (many snapshot cycles)
        fid = next(i for i in (1, 2, 3) if i != lid)
        nhs[fid].stop()
        del nhs[fid]
        for j in range(40, 400):
            put(j, deadline)

        # restart it with writes RACING the snapshot catch-up: the restore
        # update then carries entries chasing the installed snapshot
        def load():
            j = 400
            while not stop.is_set():
                j += 1
                try:
                    rs = leader.propose(
                        s, f"w{j}=a{j}".encode(), timeout=5.0
                    )
                    if rs.wait(5.0).completed:
                        done[0] = j
                except Exception:
                    time.sleep(0.02)

        t = threading.Thread(target=load, daemon=True)
        t.start()
        nhs[fid] = _mk(fid, addrs, tmp_path, sms)
        time.sleep(6.0)  # catch-up (snapshot stream + tail) under load
        stop.set()
        t.join(timeout=10)
        last = done[0] or 399
        deadline = time.time() + 60
        while time.time() < deadline:
            if all(
                sms[i].kv.get(f"w{last}") == f"a{last}" for i in (1, 2, 3)
            ):
                break
            time.sleep(0.2)
        for i in (1, 2, 3):
            assert sms[i].kv.get(f"w{last}") == f"a{last}", (
                i, len(sms[i].kv),
                nhs[i].get_node(CID).sm.get_last_applied(),
                nhs[i].get_node(CID).peer.raft.log.committed,
            )
    finally:
        stop.set()
        for nh in nhs.values():
            try:
                nh.stop()
            except Exception:
                pass
