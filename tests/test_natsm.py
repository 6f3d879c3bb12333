"""Native C-ABI state machine (natsm.cpp + natsm.py) tests.

Covers the adapter unit contract, and the fast-lane integration where
enrolled groups apply committed entries natively (natraft apply_native)
with only batched completion records crossing the GIL: client futures
still complete, lookups see the writes, ejects hand over cleanly (the
shared instance serves both planes), and replicas converge to identical
native hashes through kill/restart churn.
"""
from __future__ import annotations

import io
import socket

from tests import loadwait
import time

import pytest

from dragonboat_tpu import Config, NodeHost, NodeHostConfig
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.native import natraft, natsm
from dragonboat_tpu.native.natsm import NativeKVStateMachine

# heavy multi-NodeHost tests never overlap each other (the lock in
# tests/conftest.py): side by side they starve each other on an 8-vCPU box
pytestmark = [pytest.mark.skipif(
    not (natraft.available() and natsm.available()),
    reason="native libraries unavailable",
), pytest.mark.xdist_group("heavy-multiprocess")]

RTT = 20
CID = 41


# ------------------------------------------------------------------- unit


def test_adapter_roundtrip():
    sm = NativeKVStateMachine(1, 1)
    try:
        assert sm.update(b"a=1").value == 1
        assert sm.update(b"b=2").value == 2
        assert sm.update(b"a=3").value == 2  # overwrite: size unchanged
        assert sm.lookup("a") == "3"
        assert sm.lookup("b") == "2"
        assert sm.lookup("missing") is None
        h = sm.get_hash()
        buf = io.BytesIO()
        sm.save_snapshot(buf, None, None)
        sm2 = NativeKVStateMachine(1, 2)
        try:
            buf.seek(0)
            sm2.recover_from_snapshot(buf, None, None)
            assert sm2.get_hash() == h
            assert sm2.lookup("a") == "3"
        finally:
            sm2.close()
    finally:
        sm.close()


def test_adapter_matches_python_dict_sm():
    """Same command sequence -> same observable state as the dict SM."""
    import random

    sm = NativeKVStateMachine(1, 1)
    ref = {}
    rng = random.Random(7)
    try:
        for _ in range(500):
            k = f"k{rng.randrange(40)}"
            v = f"v{rng.randrange(1000)}"
            r = sm.update(f"{k}={v}".encode())
            ref[k] = v
            assert r.value == len(ref)
        for k, v in ref.items():
            assert sm.lookup(k) == v
    finally:
        sm.close()


# ------------------------------------------------------- fast-lane cluster


def _ports(n):
    return loadwait.ports(n)


def _mk(i, addrs, tmp_path, sms, snapshot_entries=0):
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
        sm = NativeKVStateMachine(cluster_id, node_id)
        sms[i] = sm
        return sm

    nh.start_cluster(
        addrs, False, create,
        Config(cluster_id=CID, node_id=i, election_rtt=10, heartbeat_rtt=1,
               snapshot_entries=snapshot_entries, compaction_overhead=5),
    )
    return nh


def _cluster(tmp_path, sms):
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
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


def _wait_native_applies(nhs, timeout=20.0):
    """True once some rank reports native-SM attach + enrolled lane."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        for nh in nhs.values():
            node = nh.get_node(CID)
            if node is not None and node.fast_lane and node._natsm_attached:
                return True
        time.sleep(0.05)
    return False


def _converged_hashes(sms, timeout=60.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        hs = {i: sm.get_hash() for i, sm in sms.items()}
        if len(set(hs.values())) == 1:
            return hs
        time.sleep(0.1)
    raise AssertionError(f"native hashes diverged: {hs}")


def test_native_apply_end_to_end(tmp_path):
    """Writes complete through the native apply path; lookups and
    cross-replica hashes agree; dropped spans stay zero."""
    sms = {}
    nhs, addrs = _cluster(tmp_path, sms)
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        s = leader.get_noop_session(CID)
        # first writes may ride the scalar plane (pre-enrollment)
        pend = [
            leader.propose(s, f"k{j}=v{j}".encode(), timeout=60.0)
            for j in range(200)
        ]
        for rs in pend:
            assert rs.wait(120.0).completed
        assert _wait_native_applies(nhs), "native SM never attached"
        # these complete through the NATIVE apply + completion pump
        pend = [
            leader.propose(s, f"n{j}=w{j}".encode(), timeout=60.0)
            for j in range(300)
        ]
        for rs in pend:
            assert rs.wait(120.0).completed
        assert leader.sync_read(CID, "n299", timeout=10.0) == "w299"
        _converged_hashes(sms)
        for i, nh in nhs.items():
            assert nh.fastlane.dropped_spans == 0
    finally:
        for nh in nhs.values():
            nh.stop()


def test_native_apply_eject_and_snapshot(tmp_path):
    """Snapshot triggers (periodic) force ejects mid-native-stream: the
    scalar plane resumes on the SAME instance, snapshots serialize through
    the C ABI, and the group re-enrolls and re-attaches."""
    sms = {}
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
    nhs = {
        i: _mk(i, addrs, tmp_path, sms, snapshot_entries=40) for i in addrs
    }
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        s = leader.get_noop_session(CID)
        for j in range(150):  # crosses several snapshot boundaries
            rs = leader.propose(s, f"s{j}=x{j}".encode(), timeout=60.0)
            assert rs.wait(120.0).completed
        assert leader.sync_read(CID, "s149", timeout=10.0) == "x149"
        _converged_hashes(sms)
        # the lane must still be usable after the snapshot eject cycles
        assert _wait_native_applies(nhs, timeout=30.0)
    finally:
        for nh in nhs.values():
            nh.stop()


def test_native_apply_leader_kill_failover(tmp_path):
    sms = {}
    nhs, addrs = _cluster(tmp_path, sms)
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        s = leader.get_noop_session(CID)
        for j in range(100):
            rs = leader.propose(s, f"a{j}=b{j}".encode(), timeout=60.0)
            assert rs.wait(120.0).completed
        assert _wait_native_applies(nhs)
        leader.stop()
        del nhs[lid]
        new_lid, new_leader = _leader(nhs, timeout=90.0)
        assert new_lid != lid
        s2 = new_leader.get_noop_session(CID)
        for j in range(50):
            rs = new_leader.propose(s2, f"c{j}=d{j}".encode(), timeout=60.0)
            assert rs.wait(120.0).completed
        assert new_leader.sync_read(CID, "c49", timeout=20.0) == "d49"
        # restart the killed rank against its dirs; all three converge
        sms2 = dict(sms)
        nhs[lid] = _mk(lid, addrs, tmp_path, sms2)
        deadline = time.time() + 90
        while time.time() < deadline:
            hs = {i: sm.get_hash() for i, sm in sms2.items()}
            if len(set(hs.values())) == 1:
                break
            time.sleep(0.2)
        assert len(set(hs.values())) == 1, hs
    finally:
        for nh in nhs.values():
            try:
                nh.stop()
            except Exception:
                pass


# ------------------------------------------------------- native sessions


def test_native_session_manager_differential():
    """NativeSessionManager mirrors the Python SessionManager op-for-op:
    LRU registration/eviction, dedup history, clear_to GC — with BYTE-
    identical serialization (snapshots interop across planes) and equal
    hashes, checked after every op."""
    import random

    from dragonboat_tpu.native.natsm import NativeSessionManager
    from dragonboat_tpu.rsm.session import SessionManager
    from dragonboat_tpu.statemachine import Result

    user = NativeKVStateMachine(1, 1)
    try:
        nat = NativeSessionManager(user)
        py = SessionManager()
        rng = random.Random(77)
        for step in range(400):
            cid = rng.randrange(1, 40)
            op = rng.randrange(6)
            if op == 0:
                assert (
                    nat.register_client_id(cid).value
                    == py.register_client_id(cid).value
                )
            elif op == 1:
                assert (
                    nat.unregister_client_id(cid).value
                    == py.unregister_client_id(cid).value
                )
            else:
                a = nat.client_registered(cid)
                b = py.client_registered(cid)
                assert (a is None) == (b is None)
                if a is None:
                    continue
                sid = rng.randrange(1, 9)
                assert a.has_responded(sid) == b.has_responded(sid)
                ra, oka = a.get_response(sid)
                rb, okb = b.get_response(sid)
                assert oka == okb
                if oka:
                    assert ra.value == rb.value and ra.data == rb.data
                elif not a.has_responded(sid):
                    v = rng.randrange(1000)
                    a.add_response(sid, Result(value=v))
                    b.add_response(sid, Result(value=v))
                if rng.random() < 0.25:
                    ct = rng.randrange(1, 7)
                    a.clear_to(ct)
                    b.clear_to(ct)
            assert len(nat) == len(py)
            assert nat.save() == py.save(), f"image diverged at step {step}"
        assert nat.hash() == py.hash()
        # cross-plane snapshot interop, both directions
        img = py.save()
        nat.recover_image(img)
        assert nat.save() == img
        py2 = SessionManager.load(nat.save())
        assert py2.save() == nat.save()
    finally:
        user.close()


def test_native_session_lru_eviction_parity():
    """Eviction at the LRU cap replays identically native vs Python."""
    from dragonboat_tpu.native.natsm import NativeSessionManager
    from dragonboat_tpu.rsm.session import SessionManager

    user = NativeKVStateMachine(1, 1)
    try:
        nat = NativeSessionManager(user)
        py = SessionManager()
        cap = py._max
        for cid in range(1, cap + 10):
            nat.register_client_id(cid)
            py.register_client_id(cid)
        # touch a survivor so LRU order differs from insertion order
        assert nat.client_registered(cap // 2 + 8) is not None
        assert py.client_registered(cap // 2 + 8) is not None
        for cid in range(cap + 10, cap + 20):
            nat.register_client_id(cid)
            py.register_client_id(cid)
        assert len(nat) == len(py) == cap
        assert nat.save() == py.save()
    finally:
        user.close()


def test_native_session_exactly_once_end_to_end(tmp_path):
    """Session-managed clients stay on the native apply path: register,
    dedup (a re-proposed series returns the cached result and applies the
    command ONCE), responded_to GC, and unregister all complete natively
    — zero sm-punt ejects, session hashes equal across replicas."""
    sms = {}
    nhs, addrs = _cluster(tmp_path, sms)
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        # make sure the lane is up before session traffic (otherwise the
        # scalar plane serves it — also correct, but not what we test)
        s0 = leader.get_noop_session(CID)
        for j in range(20):
            assert leader.propose(
                s0, f"w{j}=v{j}".encode(), timeout=60.0
            ).wait(120.0).completed
        assert _wait_native_applies(nhs)

        sess = leader.sync_get_session(CID, timeout=60.0)
        first = leader.propose(sess, b"k=1", timeout=60.0)
        r1 = first.wait(120.0)
        assert r1.completed
        # duplicate retry of the SAME series id: cached result, no
        # re-apply — proposed with a DIFFERENT command so a re-apply
        # would be visible in the KV
        dup = leader.propose(sess, b"leaked=1", timeout=60.0)
        r2 = dup.wait(120.0)
        assert r2.completed
        assert r2.result.value == r1.result.value
        assert leader.sync_read(CID, "leaked", timeout=20.0) is None
        sess.proposal_completed()
        # next series: applies; the responded_to watermark GCs the history
        nxt = leader.propose(sess, b"k2=2", timeout=60.0)
        r3 = nxt.wait(120.0)
        assert r3.completed
        sess.proposal_completed()
        assert leader.sync_read(CID, "k", timeout=20.0) == "1"
        assert leader.sync_read(CID, "k2", timeout=20.0) == "2"
        leader.sync_close_session(sess, timeout=60.0)

        # the lane never punted: no sm-punt ejects anywhere, the leader
        # is still enrolled, and the session stores converged
        # (register/apply/unregister replicated)
        assert leader.get_node(CID).fast_lane
        for nh in nhs.values():
            st = nh.fastlane.stats()
            assert st["eject_reasons"].get("sm-punt", 0) == 0, st
        deadline = time.time() + 60
        while time.time() < deadline:
            hs = {
                i: nh.get_node(CID).sm.get_session_hash()
                for i, nh in nhs.items()
            }
            if len(set(hs.values())) == 1:
                break
            time.sleep(0.1)
        assert len(set(hs.values())) == 1, hs
        sizes = {i: len(nh.get_node(CID).sm.sessions) for i, nh in nhs.items()}
        assert set(sizes.values()) == {0}, sizes  # closed session evicted
    finally:
        for nh in nhs.values():
            nh.stop()


def test_periodic_snapshot_triggers_while_enrolled(tmp_path):
    """The periodic snapshot trigger rides the scalar update path, which
    is idle during native steady state — this pins the completion-pump
    trigger: sustained native-applied load must advance the snapshot
    index (bounding the log) with NO manual snapshot request, and —
    since the no-eject capture path (natr_capture_sm) — with ZERO
    snapshot-due ejects: the group never leaves the lane."""
    sms = {}
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
    nhs = {i: _mk(i, addrs, tmp_path, sms, snapshot_entries=64)
           for i in addrs}
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        s = leader.get_noop_session(CID)
        # warm the lane, then record the snapshot index once enrolled
        for j in range(30):
            assert leader.propose(
                s, f"a{j}=b{j}".encode(), timeout=60.0
            ).wait(120.0).completed
        assert _wait_native_applies(nhs)
        node = leader.get_node(CID)
        si0 = node.sm.get_snapshot_index()
        # several snapshot_entries worth of writes through the native lane
        for j in range(300):
            assert leader.propose(
                s, f"k{j % 50}=v{j}".encode(), timeout=60.0
            ).wait(120.0).completed
        deadline = time.time() + 60
        while time.time() < deadline:
            if node.sm.get_snapshot_index() > si0:
                break
            time.sleep(0.1)
        assert node.sm.get_snapshot_index() > si0, (
            "periodic snapshot never fired under enrolled load"
        )
        # the native capture path snapshots IN PLACE: no snapshot-due
        # eject fired and the group never left the lane
        assert leader.fastlane.stats()["eject_reasons"].get(
            "snapshot-due", 0
        ) == 0
        assert node.fast_lane, "group left the lane for a snapshot"
        _converged_hashes(sms)
    finally:
        for nh in nhs.values():
            nh.stop()


def test_capture_snapshot_recovers_on_restart(tmp_path):
    """A snapshot produced by the no-eject native capture path
    (natr_capture_sm -> save_from_capture) must be a first-class
    snapshot: after a full-cluster stop, a cold restart recovers the KV
    AND the exactly-once session store from it (plus log replay), and
    the replicas converge on the pre-restart state.  This pins the
    format symmetry between _CaptureSavable's write and the shared
    adapter recover path."""
    from dragonboat_tpu.client import Session

    sms = {}
    ports = _ports(3)
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(3)}
    nhs = {i: _mk(i, addrs, tmp_path, sms, snapshot_entries=32)
           for i in addrs}
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        # a REGISTERED session: its dedup state must survive the restart
        # through the captured session image
        sess = leader.sync_get_session(CID, timeout=30.0)
        for j in range(80):
            rs = leader.propose(sess, f"k{j}=v{j}".encode(), timeout=60.0)
            assert rs.wait(120.0).completed
            if j != 79:
                # the LAST series id stays un-acked: its cached response
                # must survive the restart for the dedup assert below
                sess.proposal_completed()
        node = leader.get_node(CID)
        deadline = time.time() + 60
        while time.time() < deadline and node.sm.get_snapshot_index() == 0:
            time.sleep(0.1)
        si = node.sm.get_snapshot_index()
        assert si > 0, "no capture snapshot fired"
        assert leader.fastlane.stats()["eject_reasons"].get(
            "snapshot-due", 0
        ) == 0
        _converged_hashes(sms)
        pre_hash = {i: sms[i].get_hash() for i in addrs}
    finally:
        for nh in nhs.values():
            nh.stop()

    # ---- cold restart over the same dirs: recovery runs from the
    # captured snapshot + log tail ----
    sms2 = {}
    nhs = {i: _mk(i, addrs, tmp_path, sms2, snapshot_entries=32)
           for i in addrs}
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        _converged_hashes(sms2)
        v = leader.sync_read(CID, "k79", timeout=60.0)
        assert v == "v79"
        assert sms2[lid].get_hash() == next(iter(pre_hash.values()))
        # the recovered session store still dedups: retrying the
        # pre-restart session's un-acked series id (with DIFFERENT
        # bytes) must return the cached response, not re-apply
        rs = leader.propose(sess, b"k79=CLOBBER", timeout=60.0)
        assert rs.wait(120.0).completed
        assert leader.sync_read(CID, "k79", timeout=60.0) == "v79"
        assert sms2[lid].get_hash() == next(iter(pre_hash.values()))
    finally:
        for nh in nhs.values():
            nh.stop()


def test_cached_response_payload_completes_natively(tmp_path):
    """A cached session response that carries DATA bytes (a history entry
    from a Python-era apply whose Result had a payload — e.g. imported
    with the session image at attach) completes through the native path
    via the completion payload side-channel instead of ejecting the
    group (round-4: one sm-punt eject per such retry)."""
    from dragonboat_tpu.client import Session

    sms = {}
    nhs, addrs = _cluster(tmp_path, sms)
    try:
        nhs[1].get_node(CID).request_campaign()
        lid, leader = _leader(nhs)
        s0 = leader.get_noop_session(CID)
        for j in range(20):
            assert leader.propose(
                s0, f"w{j}=v{j}".encode(), timeout=60.0
            ).wait(120.0).completed
        assert _wait_native_applies(nhs)

        sess = leader.sync_get_session(CID, timeout=60.0)
        assert leader.propose(sess, b"k=1", timeout=60.0).wait(120.0).completed
        sess.proposal_completed()
        # inject a payload-bearing cached response at a FUTURE series id
        # on every replica's shared native store (the deterministic twin
        # of a session image whose history carries Result.data bytes)
        future_sid = sess.series_id + 3
        payload = b"cached-data-bytes" * 3
        from dragonboat_tpu.native import natsm as natsm_mod

        lib = natsm_mod._load()
        for i, nh in nhs.items():
            sm = sms[i]
            lib.natsm_sess_add_response(
                sm.natsm_sess_handle, sess.client_id, future_sid,
                7777, payload, len(payload),
            )
        # the client "retries" that series: the native dedup finds the
        # cached payload and the future completes WITH the data
        retry = Session(
            cluster_id=CID, client_id=sess.client_id, series_id=future_sid,
        )
        r = leader.propose(retry, b"ignored=1", timeout=60.0).wait(120.0)
        assert r.completed
        assert r.result.value == 7777
        assert r.result.data == payload
        # no re-apply, no punt, still enrolled
        assert leader.sync_read(CID, "ignored", timeout=20.0) is None
        assert leader.get_node(CID).fast_lane
        for nh in nhs.values():
            st = nh.fastlane.stats()
            assert st["eject_reasons"].get("sm-punt", 0) == 0, st
    finally:
        for nh in nhs.values():
            nh.stop()
