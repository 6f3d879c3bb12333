"""Leader-lease read plane differential suite (ISSUE 10).

Contracts under test:

- lease-off structural identity: ``read_lease=False`` keeps
  ``raft.lease is None`` and the READ_INDEX path byte-for-byte on the
  pending-request + hint-broadcast protocol (the ``_read_plane_used``
  precedent);
- lease reads ≡ ReadIndex ≡ scalar oracle on released values: the same
  scripted sequence releases identical (ctx → index) maps with the lease
  on and off, and both equal the committed watermark at read time;
- the invalidation matrix: expiry (no quorum acks for ``duration``
  ticks), leadership transfer (lease ceded BEFORE TIMEOUT_NOW can fire),
  membership change (add/remove node recycles the bases), term change;
- expiry mid-batch: reads served under the lease and reads falling back
  after expiry both release correct indices within one batch window;
- clock-jump fault injection: a negative jump makes a stale lease serve
  a read its (correct) clock would have refused — deterministically at
  the raft level, and end-to-end where the ``HistoryRecorder`` +
  ``check_linearizable`` catch the resulting stale read as a
  linearizability violation (not by luck);
- the live stack: lease-served ``read_index``/``sync_read`` on 3
  in-process NodeHosts across an injected cross-domain topology, the
  ``dragonboat_lease_*`` metric families, and the tpu coordinator's
  advisory ``LeaseTable``.
"""
from __future__ import annotations

import sys
import threading
import time

import pytest

from dragonboat_tpu import Config, NodeHostConfig, Result
from dragonboat_tpu.config import ConfigError, ExpertConfig
from dragonboat_tpu.lease import LeaderLease, LeaseTable
from dragonboat_tpu.linearizability import (
    HistoryRecorder,
    check_linearizable,
)
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.raft import InMemLogDB, Raft
from dragonboat_tpu.transport import ChanRouter, ChanTransport
from dragonboat_tpu.transport.latency import LatencyInjector, crossdomain
from dragonboat_tpu.wire import Entry, Message, MessageType, SystemCtx

from tests.raft_harness import Network
from tests.loadwait import wait_until

MT = MessageType


# ======================================================================
# raft-level harness
# ======================================================================


def mk_raft(nid: int, lease: bool = True, election: int = 10) -> Raft:
    c = Config(
        node_id=nid, cluster_id=1, election_rtt=election, heartbeat_rtt=1,
        check_quorum=True, read_lease=lease,
    )
    r = Raft(c, InMemLogDB(), seed=nid)
    r.has_not_applied_config_change = lambda: False
    return r


def mk_net(lease: bool = True, n: int = 3, election: int = 10) -> Network:
    return Network(*[mk_raft(i, lease, election) for i in range(1, n + 1)])


def elect(net: Network, nid: int = 1) -> Raft:
    net.send(Message(from_=nid, to=nid, type=MT.ELECTION))
    r = net.raft(nid)
    assert r.is_leader()
    return r


def hb_round(net: Network, leader: Raft) -> None:
    """One leader tick (fires a heartbeat broadcast) + full delivery of
    everything it triggers (acks included)."""
    leader.tick()
    net.send(*net.filter(net.take_msgs(leader)))


def read(r: Raft, lo: int) -> SystemCtx:
    ctx = SystemCtx(low=lo, high=lo + 1)
    r.handle(
        Message(type=MT.READ_INDEX, from_=r.node_id, hint=lo, hint_high=lo + 1)
    )
    return ctx


def propose(net: Network, leader: Raft, payload: bytes = b"x") -> None:
    leader.handle(
        Message(
            type=MT.PROPOSE, from_=leader.node_id,
            entries=[Entry(cmd=payload)],
        )
    )
    net.send(*net.filter(net.take_msgs(leader)))


# ======================================================================
# config gate
# ======================================================================


def test_read_lease_requires_check_quorum():
    with pytest.raises(ConfigError):
        Config(
            node_id=1, cluster_id=1, election_rtt=10, heartbeat_rtt=1,
            read_lease=True,
        ).validate()
    with pytest.raises(ConfigError):
        Config(
            node_id=1, cluster_id=1, election_rtt=10, heartbeat_rtt=1,
            check_quorum=True, quiesce=True, read_lease=True,
        ).validate()


def test_lease_off_structural_identity():
    """read_lease=False: raft.lease is None (the structural latch) and a
    READ_INDEX runs the full pending-request + hint-broadcast protocol."""
    net = mk_net(lease=False)
    r = elect(net)
    assert r.lease is None
    hb_round(net, r)
    net.take_msgs(r)  # drain
    r.handle(Message(type=MT.READ_INDEX, from_=1, hint=7, hint_high=8))
    assert r.read_index.has_pending_request()  # pending entry exists
    assert not r.ready_to_read  # nothing served locally
    # the confirmation hint rides a heartbeat broadcast
    hints = [m for m in r.msgs if m.type == MT.HEARTBEAT and m.hint == 7]
    assert len(hints) == 2


# ======================================================================
# the short path
# ======================================================================


def test_lease_read_serves_locally_with_zero_rounds():
    net = mk_net(lease=True)
    r = elect(net)
    for _ in range(2):
        hb_round(net, r)
    net.take_msgs(r)
    assert r.lease.valid(r.tick_count, r.quorum(), r.voting_members(), 1)
    ctx = read(r, 7)
    assert [(x.index, x.system_ctx, x.lease) for x in r.ready_to_read] == [
        (r.log.committed, ctx, True)
    ]
    assert not r.read_index.has_pending_request()
    # zero confirmation traffic: no hint-carrying heartbeat left raft
    assert not [m for m in r.msgs if m.type == MT.HEARTBEAT and m.hint == 7]
    assert r.lease.stats()["reads_local"] == 1


def test_lease_remote_requester_gets_read_index_resp():
    """A follower-forwarded read is answered directly with
    READ_INDEX_RESP at the committed index — the same routing a confirmed
    release uses (apply_read_releases)."""
    net = mk_net(lease=True)
    r = elect(net)
    for _ in range(2):
        hb_round(net, r)
    net.take_msgs(r)
    r.handle(Message(type=MT.READ_INDEX, from_=2, hint=9, hint_high=10))
    resp = [m for m in r.msgs if m.type == MT.READ_INDEX_RESP]
    assert len(resp) == 1
    assert resp[0].to == 2
    assert resp[0].log_index == r.log.committed
    assert resp[0].hint == 9 and resp[0].hint_high == 10
    assert not r.ready_to_read  # the requester is remote


# ======================================================================
# differential: lease ≡ ReadIndex ≡ scalar oracle on released values
# ======================================================================


def _run_scripted(lease: bool):
    """One scripted write+read interleave; returns [(ctx_low, index)]
    releases observed on the leader plus the oracle (committed at read
    time)."""
    net = mk_net(lease=lease)
    r = elect(net)
    released = []
    oracle = []
    lo = 100

    def do_read():
        nonlocal lo
        lo += 1
        oracle.append((lo, r.log.committed))
        read(r, lo)
        # deliver whatever the read produced (hint broadcasts + echoes on
        # the fallback path; nothing on the lease path)
        net.send(*net.filter(net.take_msgs(r)))
        for x in r.ready_to_read:
            released.append((x.system_ctx.low, x.index))
        r.clear_ready_to_read()

    for i in range(3):
        hb_round(net, r)
        propose(net, r, b"w%d" % i)
        do_read()
        do_read()
    return released, oracle


def test_differential_lease_equals_readindex_equals_oracle():
    with_lease, oracle_a = _run_scripted(True)
    without, oracle_b = _run_scripted(False)
    assert with_lease == without == oracle_a == oracle_b
    assert len(with_lease) == 6


# ======================================================================
# invalidation matrix
# ======================================================================


def test_lease_expires_without_quorum_acks_mid_batch():
    net = mk_net(lease=True)
    r = elect(net)
    for _ in range(2):
        hb_round(net, r)
    net.take_msgs(r)
    # batch half 1: served under the lease
    read(r, 50)
    assert len(r.ready_to_read) == 1
    # cut off the followers; tick past the lease duration (8 of the
    # 10-tick election timeout) but short of a second check-quorum window
    net.isolate(1)
    for _ in range(r.lease.duration + 1):
        r.tick()
        net.send(*net.filter(net.take_msgs(r)))  # all dropped
    assert r.is_leader()  # check-quorum hasn't deposed it yet
    # batch half 2: the lease is expired — full ReadIndex fallback
    read(r, 51)
    assert len(r.ready_to_read) == 1  # unchanged
    assert r.read_index.has_pending_request()
    assert r.lease.stats()["expiries"] == 1
    # heal; the pending ctx confirms through the echo quorum and releases
    # at the same committed watermark
    net.recover()
    hb_round(net, r)
    assert [(x.system_ctx.low, x.index) for x in r.ready_to_read] == [
        (50, r.log.committed), (51, r.log.committed)
    ]


def test_leadership_transfer_cedes_lease_before_timeout_now():
    net = mk_net(lease=True)
    r = elect(net)
    for _ in range(2):
        hb_round(net, r)
    net.take_msgs(r)
    assert r.lease.valid(r.tick_count, r.quorum(), r.voting_members(), 1)
    # transfer to 2 (caught up → TIMEOUT_NOW fires immediately); the
    # lease must already be ceded when that message is emitted
    r.handle(Message(type=MT.LEADER_TRANSFER, from_=2, hint=2))
    assert r.leader_transfering()
    assert r.lease.ceded
    # acks are still fresh — only the cede blocks the short path
    read(r, 60)
    assert not r.ready_to_read
    assert r.read_index.has_pending_request()
    # complete the transfer; node 2 leads at the higher term
    net.send(*net.filter(net.take_msgs(r)))
    r2 = net.raft(2)
    assert r2.is_leader() and not r.is_leader()
    # the new leader arms its own lease and serves locally
    hb_round(net, r2)
    net.take_msgs(r2)
    read(r2, 61)
    assert [x.system_ctx.low for x in r2.ready_to_read] == [61]


def test_membership_change_invalidates_and_rearms():
    net = mk_net(lease=True)
    r = elect(net)
    for _ in range(2):
        hb_round(net, r)
    net.take_msgs(r)
    assert r.lease.valid(r.tick_count, r.quorum(), r.voting_members(), 1)
    r.remove_node(3)
    assert not r.lease.bases  # bases recycled with the membership
    read(r, 70)
    assert not r.ready_to_read  # fallback until the new quorum acks
    assert r.read_index.has_pending_request()
    # one heartbeat round against the shrunk membership re-arms it (and
    # the echo releases the pending fallback read)
    hb_round(net, r)
    r.clear_ready_to_read()
    read(r, 71)
    assert [x.system_ctx.low for x in r.ready_to_read] == [71]


def test_term_change_invalidates():
    net = mk_net(lease=True)
    r = elect(net)
    for _ in range(2):
        hb_round(net, r)
    assert r.lease.bases
    r.handle(Message(type=MT.HEARTBEAT, from_=2, term=r.term + 5))
    assert r.is_follower()
    assert not r.lease.bases and not r.lease.ceded


# ======================================================================
# clock-jump fault injection (deterministic half)
# ======================================================================


def test_clock_jump_makes_stale_lease_serve_and_checker_catches_it():
    """The raft-level deterministic version of the soak fault: node 1's
    clock jumps backward while it is partitioned; a new leader commits a
    later write; node 1's (wrongly still-valid) lease serves a read of
    the OLD state.  The history is non-linearizable and the checker must
    say so — and the same history with the correct (un-jumped) refusal
    must pass."""
    net = mk_net(lease=True)
    r1 = elect(net)
    for _ in range(2):
        hb_round(net, r1)
    propose(net, r1, b"v1")
    committed_v1 = r1.log.committed
    net.isolate(1)
    # clock fault on the isolated leader
    r1.lease.inject_clock_jump(-1000)
    # node 2 eventually campaigns and wins over {2, 3} (the §6 vote
    # lease has expired for them once their clocks pass the timeout)
    r2, r3 = net.raft(2), net.raft(3)
    for _ in range(25):
        r2.tick()
        r3.tick()
        net.send(*net.filter(net.take_msgs(r2)))
        net.send(*net.filter(net.take_msgs(r3)))
        if r2.is_leader() or r3.is_leader():
            break
    new_leader = r2 if r2.is_leader() else r3
    assert new_leader.is_leader()
    net.send(
        Message(
            type=MT.PROPOSE, from_=new_leader.node_id, to=new_leader.node_id,
            entries=[Entry(cmd=b"v2")],
        )
    )
    assert new_leader.log.committed > committed_v1
    # meanwhile node 1 still believes it leads, and ticks have pushed it
    # far past its real lease expiry — only the jump keeps it "valid"
    for _ in range(r1.lease.duration + 1):
        r1.tick()
        net.send(*net.filter(net.take_msgs(r1)))
    assert r1.is_leader()  # first check-quorum window not yet consumed
    read(r1, 80)
    assert r1.ready_to_read, "jumped lease must (wrongly) serve"
    stale_index = r1.ready_to_read[0].index
    assert stale_index == committed_v1 < new_leader.log.committed
    # build the equivalent client history: put v1 ok, put v2 ok, then a
    # get that observed v1 — the checker must flag it
    rec = HistoryRecorder()
    rec.invoke(1, "put", "k", "v1")(True)
    rec.invoke(1, "put", "k", "v2")(True)
    rec.invoke(2, "get", "k", None)("v1")
    ok, bad = check_linearizable(rec.history())
    assert not ok and bad == ["k"]
    # the correct-clock refusal (read times out / retries on the new
    # leader) yields the linearizable history
    rec2 = HistoryRecorder()
    rec2.invoke(1, "put", "k", "v1")(True)
    rec2.invoke(1, "put", "k", "v2")(True)
    rec2.invoke(2, "get", "k", None)("v2")
    ok2, _ = check_linearizable(rec2.history())
    assert ok2
    # and indeed: without the jump the same lease refuses
    r1.lease.skew = 0
    r1.clear_ready_to_read()
    read(r1, 81)
    assert not r1.ready_to_read


# ======================================================================
# LeaderLease / LeaseTable units
# ======================================================================


def test_lease_ack_attribution_is_conservative():
    lease = LeaderLease(10)  # epsilon 2, duration 8
    lease.record_send(5, [2, 3])
    lease.record_send(6, [2, 3])
    # the ack attributes to the OLDEST recorded send
    lease.record_ack(2, 7)
    assert lease.bases[2] == 5
    lease.record_ack(2, 8)
    assert lease.bases[2] == 6
    # a full FIFO refuses NEW sends — but COUNTS them, because the
    # refused heartbeats are on the wire and will elicit acks
    for t in range(100):
        lease.record_send(t + 10, [2])
    dq = lease._pending[2]
    cap = LeaderLease.PENDING_CAP
    assert len(dq) == cap and dq[0] == [10, 1]
    assert lease._unrecorded[2] == 100 - cap
    # (review-caught hole) acks for refused sends must NOT pop sends
    # recorded after them: drain the cap'd entries, then the refusal
    # count absorbs the rest attributing NOTHING — even a send recorded
    # mid-drain waits behind the outstanding refusals
    for i in range(cap):
        lease.record_ack(2, 200)
    assert lease.bases[2] == 10 + cap - 1
    lease.record_send(300, [2])  # still suspended: refusals outstanding
    assert not lease._pending[2]
    for _ in range(100 - cap + 1):
        lease.record_ack(2, 201)
    assert lease.bases[2] == 10 + cap - 1  # unchanged — nothing newer
    assert lease._unrecorded[2] == 0
    # balance restored: recording and exact pairing resume
    lease.record_send(400, [2])
    lease.record_ack(2, 401)
    assert lease.bases[2] == 400


def test_lease_wall_guard_expires_starved_tick_clock():
    """ISSUE 17 churn-soak caught: the lease clock is the event loop's
    tick counter, so a starved/descheduled leader's tick-valid lease can
    outlive the majority's WALL-time election and serve a stale read.
    With ``tick_interval_s`` set, validity additionally requires the
    quorum-th newest ack to be wall-fresh — starvation expires the
    lease, never extends it."""
    wall = [100.0]
    lease = LeaderLease(10, tick_interval_s=0.05)  # duration 8 ticks
    lease.wall_clock = lambda: wall[0]
    voters, quorum, self_id = [1, 2, 3], 2, 1
    lease.record_send(5, [2, 3])
    lease.record_ack(2, 6)
    assert lease.valid(6, quorum, voters, self_id)
    # tick clock FROZEN at 6 (starved loop) while wall time runs past
    # duration * tick_interval_s = 0.4s: the guard must expire it even
    # though the tick arithmetic still says valid
    wall[0] += 0.39
    assert lease.valid(6, quorum, voters, self_id)
    wall[0] += 0.02
    assert not lease.valid(6, quorum, voters, self_id)
    # a fresh quorum ack re-arms it (tick basis AND wall basis move)
    lease.record_send(6, [2, 3])
    lease.record_ack(2, 7)
    assert lease.valid(7, quorum, voters, self_id)
    # a bare LeaderLease (no NodeHost gave it the tick period: what the
    # tick-driven unit tests build) has only the tick clock, and the
    # same freeze stays valid — start_cluster never leaves one so
    bare = LeaderLease(10)
    bare.record_send(5, [2, 3])
    bare.record_ack(2, 6)
    assert bare.valid(6, quorum, voters, self_id)


def test_lease_survives_sustained_hint_broadcast_load():
    """Review-caught liveness hole: every ReadIndex fallback broadcasts
    a hint heartbeat (= one record_send), so per-SEND FIFO capacity
    overflowed under sustained read load, pinned the refusal counter and
    froze the bases — the lease could never (re-)arm under exactly its
    target workload.  Tick-granular folding bounds the window by
    in-flight TICKS (the RTT), so heavy same-tick broadcast load must
    keep exact pairing and a current basis."""
    import collections as c

    lease = LeaderLease(10)
    rtt = 5
    in_flight = c.deque()
    last = 0
    for tick in range(200):
        for _ in range(8):  # 8 hint broadcasts per tick, RTT 5 ticks
            lease.record_send(tick, [2])
            in_flight.append(tick)
        while in_flight and in_flight[0] <= tick - rtt:
            in_flight.popleft()
            lease.record_ack(2, tick)
        last = tick
    assert not lease._unrecorded.get(2)  # never suspended
    assert len(lease._pending[2]) <= rtt + 1  # window = RTT ticks
    assert lease.bases[2] >= last - rtt - 1  # basis stays current
    assert lease.remaining(last, 2, [1, 2], 1) > 0


def test_membership_reset_keeps_fifo_aligned_with_inflight_acks():
    """Review-caught: a same-term membership change must NOT clear the
    send FIFO — acks still in flight pass raft's term filter, and with a
    cleared FIFO they would pop post-change sends and inflate the basis
    (persistently).  The partial reset drops only the bases; the stale
    ack then consumes the pre-change send it actually answers."""
    lease = LeaderLease(10)
    lease.record_send(3, [2])  # in flight when the membership changes
    lease.membership_changed()
    assert not lease.bases
    lease.record_send(7, [2])  # post-change send
    # the STALE ack (answers tick 3) arrives first — must attribute the
    # pre-change send, not the tick-7 one
    lease.record_ack(2, 8)
    assert lease.bases[2] == 3
    lease.record_ack(2, 9)
    assert lease.bases[2] == 7  # pairing stayed exact
    # a full (term-change) reset still clears everything: old-term acks
    # never reach record_ack (term-filtered), so alignment holds
    lease.reset()
    assert not lease._pending and not lease.bases


def test_lease_quorum_reduction_matches_kth_largest():
    lease = LeaderLease(10)
    # 5 voters, quorum 3: self counts at now; bases {2: 4, 3: 2}, 4/5 none
    lease.record_send(2, [3])
    lease.record_send(4, [2])
    lease.record_ack(3, 5)
    lease.record_ack(2, 6)
    voters = [1, 2, 3, 4, 5]
    # sorted bases: [-1, -1, 2, 4, now] → 3rd newest = 2
    assert lease.remaining(6, 3, voters, 1) == 2 + 8 - 6
    assert lease.remaining(10, 3, voters, 1) == 0
    # quorum 2: 2nd newest = 4
    assert lease.remaining(6, 2, voters, 1) == 4 + 8 - 6


def test_lease_table_round_tally():
    lt = LeaseTable()
    lt.configure(7, quorum=2, duration=8, self_id=1, voters=[1, 2, 3])
    assert lt.tracks(7) and not lt.tracks(8)
    assert not lt.valid(7, 0)
    lt.note_round({7: {2}}, 10)  # one follower + self = quorum
    assert lt.valid(7, 11) and not lt.valid(7, 18)
    assert lt.held_count(11) == 1
    lt.drop(7)
    assert not lt.valid(7, 11)
    # below-quorum tallies never extend
    lt.configure(9, quorum=3, duration=8, self_id=1, voters=[1, 2, 3, 4, 5])
    lt.note_round({9: {2}}, 10)
    assert not lt.valid(9, 11)
    # (review-caught) observer acks are filtered — hbresp ops are staged
    # for EVERY responder, but only voting members extend the deadline
    lt.configure(11, quorum=2, duration=8, self_id=1, voters=[1, 2, 3])
    lt.note_round({11: {8, 9}}, 10)  # observers only
    assert not lt.valid(11, 11)
    lt.note_round({11: {8, 2}}, 12)  # one voter + self = quorum
    assert lt.valid(11, 13)


# ======================================================================
# live stack: cross-domain lease reads, metrics, tpu lease table
# ======================================================================


class KVSM:
    def __init__(self, cluster_id, node_id):
        self.kv = {}

    def update(self, cmd):
        k, _, v = bytes(cmd).partition(b"=")
        self.kv[k.decode()] = v.decode()
        return Result(value=len(self.kv))

    def lookup(self, query):
        return self.kv.get(query)

    def save_snapshot(self, w, files, done):
        import json

        b = json.dumps(self.kv).encode()
        w.write(len(b).to_bytes(8, "little") + b)

    def recover_from_snapshot(self, r, files, done):
        import json

        n = int.from_bytes(r.read(8), "little")
        self.kv = json.loads(r.read(n).decode())

    def close(self):
        pass


CID = 770


class _HeldLinks(LatencyInjector):
    """Every link into ``dst`` can be held shut: a batch for it waits in
    its sender thread until :meth:`release`.  No delay otherwise."""

    def __init__(self, dst):
        super().__init__()
        self._dst = dst
        self._open = threading.Event()
        self._open.set()

    def hold(self):
        self._open.clear()

    def release(self):
        self._open.set()

    def delay(self, src, dst):
        if dst == self._dst:
            self._open.wait()
        return 0.0


def _mk_hosts(n=3, rtt_ms=5, engine="scalar", metrics=False, prefix="ls"):
    router = ChanRouter()
    nhs = []
    for i in range(1, n + 1):
        nhs.append(
            NodeHost(
                NodeHostConfig(
                    node_host_dir=":memory:",
                    rtt_millisecond=rtt_ms,
                    raft_address=f"{prefix}{i}:1",
                    raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                        s, rh, ch, router=router
                    ),
                    enable_metrics=metrics,
                    expert=ExpertConfig(
                        quorum_engine=engine,
                        engine_block_groups=64,
                        engine_warm_fused=False,
                    ),
                )
            )
        )
    return nhs, router


def _start(nhs, prefix="ls", cid=CID, election_rtt=10, lease=True,
           sm=KVSM):
    addrs = {i: f"{prefix}{i}:1" for i in range(1, len(nhs) + 1)}
    for i, nh in enumerate(nhs, start=1):
        nh.start_cluster(
            addrs, False, sm,
            Config(
                cluster_id=cid, node_id=i, election_rtt=election_rtt,
                heartbeat_rtt=1, check_quorum=True, read_lease=lease,
            ),
        )
    # host 1 must lead: the first campaign can race the bootstrap
    # config-change apply (campaign_skipped) or lose to a randomized
    # timeout elsewhere — retry, transferring back when another host won
    def _drive_leader1():
        n1 = nhs[0].get_node(cid)
        if n1.is_leader():
            return True
        lid, ok = n1.get_leader_id()
        if ok and lid != 1 and 1 <= lid <= len(nhs):
            try:
                nhs[lid - 1].request_leader_transfer(cid, 1)
            except Exception:
                pass
        else:
            n1.request_campaign()
        return False

    wait_until(
        _drive_leader1, timeout=20.0, interval=0.2, what="leader on host 1"
    )


def _stop(nhs):
    for nh in nhs:
        try:
            nh.stop()
        except Exception:
            pass


def _propose_retry(nh, s, data, timeout=30.0, attempts=3):
    """Noop-session propose with a load-scaled timeout and retry (the
    test_tpuquorum helper, ISSUE 13 deflake): under full-suite load one
    live-stack window can starve past a single timeout — the documented
    r07/r10/r12 rotating leadership-timing flake — while the cluster is
    perfectly healthy.  A noop-session duplicate is harmless here."""
    from dragonboat_tpu.requests import TimeoutError_
    from tests.loadwait import scaled

    for a in range(attempts):
        try:
            return nh.sync_propose(s, data, timeout=scaled(timeout))
        except TimeoutError_:
            if a == attempts - 1:
                raise


def _read_retry(nh, cid, query, timeout=10.0, attempts=3):
    """Load-scaled, retried sync_read (idempotent — safe to repeat)."""
    from dragonboat_tpu.requests import TimeoutError_
    from tests.loadwait import scaled

    for a in range(attempts):
        try:
            return nh.sync_read(cid, query, timeout=scaled(timeout))
        except TimeoutError_:
            if a == attempts - 1:
                raise


def test_live_lease_reads_cross_domain_and_metrics():
    """3 hosts, follower quorum one injected far link away: lease reads
    complete without paying the domain RTT; the dragonboat_lease_*
    families round-trip HELP+TYPE through the exposition."""
    nhs, _router = _mk_hosts(metrics=True)
    try:
        from dragonboat_tpu.monkey import set_latency

        set_latency(
            nhs, crossdomain(["ls1:1"], ["ls2:1", "ls3:1"], 0.015)
        )
        _start(nhs)
        nh = nhs[0]
        _propose_retry(nh, nh.get_noop_session(CID), b"a=1")
        # let a heartbeat/ack round trip arm the lease
        wait_until(
            lambda: (nh.lease_status(CID) or {}).get("held"),
            timeout=10.0, what="lease armed",
        )
        v = _read_retry(nh, CID, "a")
        assert v == "1"
        st = nh.lease_status(CID)
        assert st["reads_local"] >= 1
        assert st["grants"] >= 1
        # lease-served reads beat the 30ms domain RTT by construction:
        # time a burst and require it to complete far under ONE far RTT
        # per read.  The margin is load-scaled (scheduler pressure
        # stretches even a zero-round local read) but HARD-CAPPED below
        # the far round trip — a read that actually paid the link can
        # never pass (ISSUE 13 deflake of the r07/r10/r12 profile).
        from tests.loadwait import scaled as _scaled

        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            assert _read_retry(nh, CID, "a") == "1"
        per_read = (time.perf_counter() - t0) / n
        bound = min(_scaled(0.015), 0.028)
        assert per_read < bound, f"lease read paid the far link: {per_read}"
        # exposition: every lease family carries HELP + TYPE
        import io

        buf = io.StringIO()
        nh.write_health_metrics(buf)
        text = buf.getvalue()
        assert "# HELP dragonboat_lease_reads_local_total" in text
        assert "# TYPE dragonboat_lease_reads_local_total counter" in text
        assert "# TYPE dragonboat_lease_remaining_validity_ticks histogram" \
            in text
    finally:
        _stop(nhs)


def test_live_transfer_soak_linearizable_and_stale_lease_caught():
    """HistoryRecorder-checked lease reads under leadership transfer:
    (a) the correct protocol — transfer cedes the lease — yields a
    linearizable history; (b) the injected fault (cede suppressed, the
    old leader's inbound delayed so it serves during the handoff window)
    yields a history the checker FLAGS.  The checker catches the stale
    read; the pass in (a) is not luck."""
    # ---- (a) the correct protocol under transfer churn ----
    nhs, _router = _mk_hosts(rtt_ms=5)
    try:
        _start(nhs, election_rtt=10)
        rec = HistoryRecorder()
        stop = threading.Event()
        seq = [0]

        def current_leader():
            for nh in nhs:
                lid, ok = nh.get_leader_id(CID)
                if ok and 1 <= lid <= 3:
                    return nhs[lid - 1]
            return nhs[0]

        def writer():
            while not stop.is_set():
                seq[0] += 1
                v = str(seq[0])
                done = rec.invoke(1, "put", "k", v)
                try:
                    nh = current_leader()
                    nh.sync_propose(
                        nh.get_noop_session(CID), f"k={v}".encode(),
                        timeout=5.0,
                    )
                    done(True)
                except Exception:
                    done(unknown=True)

        def reader():
            while not stop.is_set():
                done = rec.invoke(2, "get", "k", None)
                try:
                    nh = current_leader()
                    done(nh.sync_read(CID, "k", timeout=5.0))
                except Exception:
                    done(unknown=True)
                time.sleep(0.005)

        ts = [threading.Thread(target=writer), threading.Thread(target=reader)]
        for t in ts:
            t.start()
        # transfer leadership around the ring under load
        for i in range(4):
            time.sleep(0.6)
            try:
                leader = current_leader()
                lid, _ = leader.get_leader_id(CID)
                target = (lid % 3) + 1
                leader.request_leader_transfer(CID, target)
            except Exception:
                pass
        time.sleep(0.6)
        stop.set()
        for t in ts:
            t.join(timeout=30)
        ok, bad = check_linearizable(rec.history())
        assert ok, f"non-linearizable keys under transfer churn: {bad}"
        # the lease actually served load (the soak exercised the short
        # path, not just the fallback)
        local = sum(
            (nh.lease_status(CID) or {}).get("reads_local", 0) for nh in nhs
        )
        assert local > 0
    finally:
        _stop(nhs)

    # ---- (b) the injected fault: suppressed cede + delayed handoff ----
    nhs, _router = _mk_hosts(rtt_ms=10, prefix="lf")
    # everything INBOUND to host 1 goes through links the test can hold
    # shut: the handoff window in which a non-ceding leader would serve
    # stale reads is as long as the test keeps it
    inj = _HeldLinks("lf1:1")
    try:
        # a lease outlives its last quorum of acks by election_rtt less
        # the drift margin and no longer, held links or not: 4.8 s here,
        # so the stale read below is asked inside it on a loaded box too
        # (the handoff itself is TIMEOUT_NOW: it waits for no timeout)
        _start(nhs, prefix="lf", election_rtt=600)
        nh1 = nhs[0]
        _propose_retry(nh1, nh1.get_noop_session(CID), b"k=v1")
        wait_until(
            lambda: (nh1.lease_status(CID) or {}).get("held"),
            timeout=10.0, what="lease armed",
        )
        rec = HistoryRecorder()
        rec.invoke(1, "put", "k", "v1")(True)
        from dragonboat_tpu.monkey import set_latency

        set_latency(nhs, inj)
        node1 = nh1.get_node(CID)
        lease = node1.peer.raft.lease

        # a transfer can fizzle when the target's TIMEOUT_NOW campaign
        # races its apply watermark (has_config_change_to_apply guard) —
        # drive it until it lands.  Each attempt: request (the step
        # worker applies it and cedes — the protocol's correct
        # behavior), then inject the FAULT by un-ceding (as if the
        # transfer path forgot); with the correct cede this window
        # falls back (case (a)).
        def _drive_transfer():
            if nhs[1].get_node(CID).is_leader():
                return True
            if not node1.is_leader():
                return False
            # the leader sends TIMEOUT_NOW at once only to a target that
            # has acknowledged its whole log: let the acks in, and shut
            # the links before the transfer, not after it (a handoff is
            # over in milliseconds): host 1 then cannot hear of the new
            # term until the test lets it
            inj.release()
            with node1.raft_mu:
                r = node1.peer.raft
                if r.remotes[2].match != r.log.last_index():
                    return False
            inj.hold()
            try:
                nh1.request_leader_transfer(CID, 2)
            except Exception:
                pass
            # wait for the step worker to apply the transfer (which
            # cedes — the protocol's correct behavior), then promptly
            # inject the fault so the handoff window runs un-ceded
            t0 = time.time()
            while time.time() - t0 < 1.0 and not lease.ceded:
                time.sleep(0.01)
            if lease.ceded:
                with node1.raft_mu:
                    lease.ceded = False
            return nhs[1].get_node(CID).is_leader()

        wait_until(
            _drive_transfer, timeout=30.0, interval=0.1,
            what="transfer target leading",
        )
        # the target now leads and commits v2 with host 3 (near link)
        # while host 1 cannot hear of the new term
        done_v2 = rec.invoke(1, "put", "k", "v2")
        _propose_retry(nhs[1], nhs[1].get_noop_session(CID), b"k=v2",
                       timeout=10.0)
        done_v2(True)
        # stale read on the old leader inside the delayed-handoff window
        assert node1.is_leader()
        done_get = rec.invoke(2, "get", "k", None)
        rs = nh1.read_index(CID, 5.0)
        r = rs.wait(5.0)
        assert r.completed, "un-ceded lease must (wrongly) serve"
        done_get(node1.sm.lookup("k"))
        ok, bad = check_linearizable(rec.history())
        assert not ok and bad == ["k"], (
            "the checker must catch the stale lease read"
        )
    finally:
        inj.release()
        _stop(nhs)


def test_live_tpu_engine_lease_and_coordinator_table():
    """Lease reads with the batched device engine: the scalar lease still
    serves (the short path never stages device reads), and the
    coordinator's advisory LeaseTable tracks the group's validity from
    the heartbeat-ack ops it drains."""
    nhs, _router = _mk_hosts(engine="tpu", prefix="lt")
    try:
        _start(nhs, prefix="lt")
        nh = nhs[0]
        # retried + load-scaled: the first live-tpu propose shares the
        # core with the engine's first-dispatch compiles, and one
        # starved window was the documented r12 rotating flake
        _propose_retry(nh, nh.get_noop_session(CID), b"a=2", timeout=60.0)
        # generous, load-scaled waits: a live 3-host tpu-engine cluster
        # on a contended box arms slowly (first-dispatch compiles share
        # the core with raft) — the gate must not flake on weather.
        # 60s base: the 30s scaled budget still expired once per loaded
        # sweep (the r12 rotating profile's most frequent site) while
        # the same wait passes standalone in seconds — arming is
        # contention-bound, not broken, so only the margin widens.
        # r15 deflake (the ONE remaining rotating site of the r14
        # sweeps, observed at load >4): the no-arm mode was PROBED, not
        # guessed — on a starved box leadership CHURNS (one probe
        # caught host 1 twenty terms past its driven win, leader on
        # host 2), and a wait that only polls `held` then watches a
        # FOLLOWER forever: a follower's lease can never arm, so no
        # margin is wide enough.  The wait therefore re-drives host-1
        # leadership while it waits (the `_start` transfer/campaign
        # treatment applied continuously) under ONE hard-capped total
        # budget — load-scaled like every loadwait site but never past
        # 300s, so a pathological box surfaces one attributable
        # failure instead of eating the sweep's global timeout (naive
        # stacked retries of scaled 60s waits measured exactly that)
        from tests.loadwait import scaled as _lease_scaled

        def _lead_and_armed():
            n1 = nh.get_node(CID)
            if not n1.is_leader():
                lid, ok = n1.get_leader_id()
                if ok and lid != 1 and 1 <= lid <= len(nhs):
                    try:
                        nhs[lid - 1].request_leader_transfer(CID, 1)
                    except Exception:
                        pass
                else:
                    n1.request_campaign()
                return False
            return bool((nh.lease_status(CID) or {}).get("held"))

        arm_deadline = time.time() + min(300.0, _lease_scaled(90.0))
        while not _lead_and_armed():
            if time.time() >= arm_deadline:
                raise AssertionError(
                    f"lease armed not reached (leader "
                    f"{nh.get_leader_id(CID)!r}, status "
                    f"{nh.lease_status(CID)!r})"
                )
            time.sleep(0.2)
        before = (nh.lease_status(CID) or {}).get("reads_local", 0)
        assert _read_retry(nh, CID, "a", timeout=30.0) == "2"
        st = nh.lease_status(CID)
        assert st["reads_local"] > before
        qc = nh.quorum_coordinator
        assert qc is not None and qc.lease_table is not None
        assert qc.lease_table.tracks(CID)
        wait_until(
            lambda: qc.lease_table.valid(CID, qc._tick_seen),
            timeout=30.0, what="coordinator lease table armed",
        )
    finally:
        _stop(nhs)
