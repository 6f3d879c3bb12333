"""Snapshot worker pool + snapshot-status feedback tests.

Reference: dedicated snapshot workers (``execengine.go:240-635``) so a slow
user snapshot never stalls other groups' applies, and the delayed
snapshot-status feedback (``feedback.go:23-129``) so a dropped status/ack
message cannot strand a follower in Snapshot state (VERDICT r2 item 7).
"""
from __future__ import annotations

import time

import pytest

from dragonboat_tpu import Config, NodeHost, NodeHostConfig, Result
from dragonboat_tpu.feedback import SnapshotFeedback
from dragonboat_tpu.transport import ChanRouter, ChanTransport

RTT_MS = 5


# ------------------------------------------------- feedback unit tests


def test_feedback_delays_push_until_release():
    pushed = []
    fb = SnapshotFeedback(lambda c, n, f: pushed.append((c, n, f)) or True,
                          push_delay_ms=1000)
    fb.add_status(1, 2, False, now_ms=0)
    fb.push_ready(now_ms=500)
    assert pushed == []  # still parked
    fb.push_ready(now_ms=1001)
    assert pushed == [(1, 2, False)]
    assert fb.pending_count() == 0


def test_feedback_confirm_accelerates_release():
    pushed = []
    fb = SnapshotFeedback(lambda c, n, f: pushed.append((c, n, f)) or True,
                          push_delay_ms=100000, confirmed_delay_ms=100)
    fb.add_status(1, 2, False, now_ms=0)
    fb.confirm(1, 2, now_ms=10)
    fb.push_ready(now_ms=50)
    assert pushed == []
    fb.push_ready(now_ms=111)
    assert pushed == [(1, 2, False)]


def test_feedback_retries_failed_push():
    """A status the node queue rejected is re-parked and re-pushed — the
    'dropped status message still recovers' guarantee."""
    attempts = []

    def push(c, n, f):
        attempts.append((c, n, f))
        return len(attempts) >= 3  # fail twice, then succeed

    fb = SnapshotFeedback(push, push_delay_ms=10, retry_delay_ms=10)
    fb.add_status(9, 3, True, now_ms=0)
    now = 11
    for _ in range(5):
        fb.push_ready(now_ms=now)
        now += 11
    assert attempts == [(9, 3, True)] * 3
    assert fb.pending_count() == 0


def test_feedback_failed_status_preserved_through_retry():
    seen = []
    fb = SnapshotFeedback(lambda c, n, f: seen.append(f) or False,
                          push_delay_ms=1, retry_delay_ms=1)
    fb.add_status(1, 2, True, now_ms=0)
    fb.push_ready(now_ms=5)
    fb.push_ready(now_ms=10)
    assert seen == [True, True]


# --------------------------------------- slow save doesn't stall applies


class SlowSnapSM:
    """save_snapshot blocks; updates are instant."""

    SAVE_SECONDS = 2.0

    def __init__(self, cluster_id, node_id):
        self.count = 0

    def update(self, cmd):
        self.count += 1
        return Result(value=self.count)

    def lookup(self, query):
        return self.count

    def save_snapshot(self, w, files, done):
        time.sleep(self.SAVE_SECONDS)
        w.write(self.count.to_bytes(8, "little"))

    def recover_from_snapshot(self, r, files, done):
        self.count = int.from_bytes(r.read(8), "little")

    def close(self):
        pass


def test_slow_snapshot_save_does_not_block_other_groups():
    """Two groups on the same apply worker (cid % workers equal); a
    multi-second snapshot save on one must not delay the other's applies."""
    router = ChanRouter()

    def factory(src, rh, ch):
        return ChanTransport(src, rh, ch, router=router)

    nhs = [
        NodeHost(
            NodeHostConfig(
                node_host_dir=":memory:",
                rtt_millisecond=RTT_MS,
                raft_address=f"sp{i}:1",
                raft_rpc_factory=factory,
            )
        )
        for i in (1, 2, 3)
    ]
    addrs = {i: f"sp{i}:1" for i in (1, 2, 3)}
    # default engine: 4 step/apply workers → cids 1 and 5 share worker 1
    slow_cid, fast_cid = 1, 5
    try:
        for cid in (slow_cid, fast_cid):
            for i, nh in enumerate(nhs, 1):
                nh.start_cluster(
                    addrs, False, SlowSnapSM,
                    Config(cluster_id=cid, node_id=i, election_rtt=10,
                           heartbeat_rtt=1, snapshot_entries=0),
                )
            nhs[0].get_node(cid).request_campaign()
        deadline = time.time() + 20
        leaders = {}
        while len(leaders) < 2 and time.time() < deadline:
            for cid in (slow_cid, fast_cid):
                for nh in nhs:
                    lid, ok = nh.get_leader_id(cid)
                    if ok:
                        leaders[cid] = nhs[lid - 1]
            time.sleep(0.02)
        assert len(leaders) == 2
        # a few writes so there is something to snapshot
        for cid in (slow_cid, fast_cid):
            s = leaders[cid].get_noop_session(cid)
            rs = leaders[cid].propose(s, b"x", timeout=5.0)
            assert rs.wait(5.0).completed
        # kick the slow snapshot on every replica of slow_cid
        for nh in nhs:
            nh.get_node(slow_cid).request_snapshot(
                __import__(
                    "dragonboat_tpu.rsm", fromlist=["SSRequest"]
                ).SSRequest(type=1),
                timeout_s=30.0,
            )
        time.sleep(0.1)  # let the saves start on the snapshot pool
        # applies on the co-scheduled fast group must stay fast
        s = leaders[fast_cid].get_noop_session(fast_cid)
        t0 = time.perf_counter()
        for _ in range(5):
            rs = leaders[fast_cid].propose(s, b"y", timeout=5.0)
            assert rs.wait(5.0).completed
        elapsed = time.perf_counter() - t0
        assert elapsed < SlowSnapSM.SAVE_SECONDS / 2, (
            f"applies stalled behind the slow snapshot: {elapsed:.2f}s"
        )
    finally:
        for nh in nhs:
            nh.stop()


# ------------------------- replica instruments: inert when off (ISSUE 37)


class _CountSM(SlowSnapSM):
    SAVE_SECONDS = 0.0


@pytest.mark.parametrize("on", [False, True], ids=["off", "metrics_on"])
def test_replica_instruments_are_built_only_when_switched_on(on, monkeypatch):
    """With tracer and ``enable_metrics`` off a NodeHost that takes
    snapshots builds no ``ReplicaObs``, no ``snapshot_save`` span, no
    annotation and no per-second count: ``Node.replica_obs`` and
    ``Engine.replica_obs`` stay ``None``.  Switched on, the same run
    writes a span a save."""
    from dragonboat_tpu import obs
    from dragonboat_tpu.obs import instruments

    built = []
    for name in ("_SaveScope", "ReplicaObs"):
        cls = getattr(instruments, name)

        def counting(*a, _cls=cls, _name=name, **k):
            built.append(_name)
            return _cls(*a, **k)

        monkeypatch.setattr(instruments, name, counting)
    from dragonboat_tpu.obs import recorder

    annotated = []
    real_annotate = recorder.annotate
    for mod in (instruments, recorder):  # a scope's, and its phases'
        monkeypatch.setattr(
            mod, "annotate",
            lambda phase: annotated.append(phase) or real_annotate(phase))
    from dragonboat_tpu.rsm import statemachine

    clocked = []
    real_held = statemachine._Held

    def held(mu, scope):
        if scope is not None:
            clocked.append(scope)
        h = real_held(mu, scope)
        assert (h.t0 != 0.0) == (scope is not None)  # off: no clock read
        return h

    monkeypatch.setattr(statemachine, "_Held", held)
    live0 = len(instruments.replica_obs_live())
    spans0 = obs.default_recorder().to_json(limit=1)["count"]
    from dragonboat_tpu.events import DEFAULT_REGISTRY

    # the process's registry: a test file that ran before on this worker
    # may have counted saves of its own
    saved0 = DEFAULT_REGISTRY.counter_value(
        "dragonboat_snapshot_saves_total", {"kind": "periodic"})

    router = ChanRouter()
    nh = NodeHost(NodeHostConfig(
        node_host_dir=":memory:", rtt_millisecond=RTT_MS,
        raft_address="ro1:1", enable_metrics=on,
        raft_rpc_factory=lambda src, rh, ch: ChanTransport(
            src, rh, ch, router=router),
    ))
    try:
        nh.start_cluster(
            {1: "ro1:1"}, False, _CountSM,
            Config(cluster_id=1, node_id=1, election_rtt=10, heartbeat_rtt=1,
                   snapshot_entries=5, compaction_overhead=2),
        )
        node = nh.get_node(1)
        deadline = time.time() + 20
        while not nh.get_leader_id(1)[1] and time.time() < deadline:
            time.sleep(0.02)
        s = nh.get_noop_session(1)
        for _ in range(30):
            assert nh.propose(s, b"x", timeout=5.0).wait(5.0).completed
        while (not nh.logdb.list_snapshots(1, 1)
               or node._snapshotting.locked()) and time.time() < deadline:
            time.sleep(0.02)
        assert nh.logdb.list_snapshots(1, 1)  # it did snapshot
        saves = [sp for sp in obs.default_recorder().spans()
                 if sp is not None and sp["kind"] == "snapshot_save"
                 and sp["seq"] >= spans0 and sp["host"] == "ro1:1"]
        if not on:
            assert nh.tracer is None
            assert nh.replica_obs is None and node.replica_obs is None
            assert nh.engine.replica_obs is None
            assert built == [] and annotated == [] and saves == []
            assert len(instruments.replica_obs_live()) == live0
            assert node._ss_refuse_from == 0  # not even the bookkeeping
            assert clocked == []  # nor a stamp around the update lock
            return
        assert len(clocked) == len(saves)
        for sp in saves:  # ISSUE 38: what the save issued, and its lock
            assert sp["image_buffered"] is True
            assert sp["logdb_commits"] == 2 and sp["fsyncs"] == 6
            assert 0 < sp["update_lock_ms"] < sp["sm_save_ms"]
        assert node.replica_obs is nh.replica_obs is nh.engine.replica_obs
        assert built.count("ReplicaObs") == 1
        assert built.count("_SaveScope") == len(saves) >= 1
        assert "snapshot_save" in annotated and "compact" in annotated
        assert all(sp["saved"] and sp["save_kind"] == "periodic"
                   for sp in saves)
        assert nh.replica_obs in instruments.replica_obs_live()
        assert nh.metrics_registry.counter_value(
            "dragonboat_snapshot_saves_total", {"kind": "periodic"}
        ) - saved0 == len(saves)
    finally:
        nh.stop()
    assert len(instruments.replica_obs_live()) == live0  # closed with it
