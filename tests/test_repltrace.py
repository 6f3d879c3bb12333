"""Replication-path tracing and commit quorum attribution (ISSUE 14).

Contracts under test:

- trace-OFF structural identity on both wires: with
  ``trace_sample_every=0`` no attribution plane exists anywhere
  (``NodeHost.replattr`` / ``Node.replattr`` / ``Raft.replattr`` all
  None) and ``Message.trace`` stays None; at the codec level a
  trace-less message's encoding is BIT-identical to the pre-trace
  layout — attaching a context changes exactly one flag byte and
  appends the payload, nothing else moves;
- stage completeness leader→follower→leader on the chan AND tcp wires:
  a sampled proposal's closed attribution record decomposes the
  quorum-closing ack into the five replication stages (wire_out /
  follower_append / follower_fsync / ack_send / wire_back) that sum to
  the measured RTT, the follower files the matching leg in ITS tracer,
  the leader trace gains the ``repl_quorum`` stage, and
  ``tools/trace_merge.py`` joins the per-host dumps into one flow;
- quorum-closing-peer correctness vs a scalar oracle (the
  ``kth_largest`` rule ``raft.try_commit`` runs) under an injected slow
  peer, driven deterministically through ``ReplAttr`` with a clamped
  clock;
- attribution under mid-trace leadership transfer: term-pinned records
  never cross terms (acks and commits from a later term drop the
  record instead of attributing), and ``Raft.reset`` clears the
  group's open records;
- satellites: ``dragonboat_transport_*`` counters land in the shared
  registry with ``# HELP`` round-trip, and
  ``LatencyInjector.health_snapshot`` labels peers by latency class.
"""
from __future__ import annotations

import io
import json
import socket
import time

import pytest

from tests import loadwait

from dragonboat_tpu import Config, NodeHostConfig, Result
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.events import MetricsRegistry
from dragonboat_tpu.monkey import set_latency
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.obs import replattr as replattr_mod
from dragonboat_tpu.obs.replattr import ReplAttr, STAGES
from dragonboat_tpu.transport import ChanRouter, ChanTransport
from dragonboat_tpu.transport.latency import LatencyInjector, crossdomain
from dragonboat_tpu.transport.metrics import TransportMetrics
from dragonboat_tpu.wire import Entry, Message, MessageType, ReplTrace
from dragonboat_tpu.wire.codec import decode_message, encode_message

from tests.loadwait import wait_until

CID = 940
RTT_MS = 5


class CounterSM:
    def __init__(self, cluster_id, node_id):
        self.count = 0

    def update(self, cmd):
        self.count += 1
        return Result(value=self.count)

    def lookup(self, query):
        return self.count

    def save_snapshot(self, w, files, done):
        w.write(self.count.to_bytes(8, "little"))

    def recover_from_snapshot(self, r, files, done):
        self.count = int.from_bytes(r.read(8), "little")

    def close(self):
        pass


def _mk_chan_hosts(n=3, trace=1):
    router = ChanRouter()
    nhs = []
    for i in range(1, n + 1):
        nhs.append(
            NodeHost(
                NodeHostConfig(
                    node_host_dir=":memory:",
                    rtt_millisecond=RTT_MS,
                    raft_address=f"rt{i}:1",
                    raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                        s, rh, ch, router=router
                    ),
                    trace_sample_every=trace,
                    expert=ExpertConfig(quorum_engine="scalar"),
                )
            )
        )
    return nhs


def _ports(n):
    return loadwait.ports(n)


def _mk_tcp_hosts(tmp_path, n=3, trace=1):
    ports = _ports(n)
    nhs = []
    for i in range(1, n + 1):
        nhs.append(
            NodeHost(
                NodeHostConfig(
                    node_host_dir=str(tmp_path / f"nh{i}"),
                    rtt_millisecond=RTT_MS,
                    raft_address=f"127.0.0.1:{ports[i - 1]}",
                    trace_sample_every=trace,
                    expert=ExpertConfig(
                        quorum_engine="scalar", logdb_shards=2
                    ),
                )
            )
        )
    return nhs


def _start(nhs, cid=CID):
    addrs = {i: nh.raft_address() for i, nh in enumerate(nhs, start=1)}
    for i, nh in enumerate(nhs, start=1):
        nh.start_cluster(
            addrs, False, CounterSM,
            Config(cluster_id=cid, node_id=i, election_rtt=10,
                   heartbeat_rtt=1),
        )
    wait_until(
        lambda: nhs[0].get_leader_id(cid)[1], timeout=30.0, what="leader"
    )


def _force_leader(nhs, target=1, cid=CID):
    """Deterministic placement: transfer/campaign until nhs[target-1]
    leads (the run_crossdomain placement loop's shape)."""
    node = nhs[target - 1].get_node(cid)
    deadline = time.time() + 60

    def _try():
        if node.is_leader():
            return True
        lid, ok = node.get_leader_id()
        if ok and lid != target and 1 <= lid <= len(nhs):
            try:
                nhs[lid - 1].request_leader_transfer(cid, target)
            except Exception:
                pass
        else:
            node.request_campaign()
        return False

    while time.time() < deadline:
        if _try():
            return
        time.sleep(0.2)
    raise AssertionError(f"node {target} never became leader")


def _stop_all(nhs):
    for nh in nhs:
        try:
            nh.stop()
        except Exception:
            pass


# ----------------------------------------------------------------------
# trace OFF: structural identity (chan and tcp)
# ----------------------------------------------------------------------


def _assert_repl_off(nh, cid=CID):
    assert nh.replattr is None
    node = nh.get_node(cid)
    assert node.replattr is None
    assert node.peer.raft.replattr is None
    if nh.quorum_coordinator is not None:
        assert nh.quorum_coordinator.replattr is None


def test_trace_off_structural_identity_chan():
    nhs = _mk_chan_hosts(trace=0)
    try:
        _start(nhs)
        _force_leader(nhs)
        s = nhs[0].get_noop_session(CID)
        nhs[0].sync_propose(s, b"x", timeout=30.0)
        for nh in nhs:
            _assert_repl_off(nh)
    finally:
        _stop_all(nhs)


def test_trace_off_structural_identity_tcp(tmp_path):
    nhs = _mk_tcp_hosts(tmp_path, trace=0)
    try:
        _start(nhs)
        _force_leader(nhs)
        s = nhs[0].get_noop_session(CID)
        nhs[0].sync_propose(s, b"x", timeout=30.0)
        for nh in nhs:
            _assert_repl_off(nh)
    finally:
        _stop_all(nhs)


def test_codec_trace_none_bit_identity():
    """A trace-less message's bytes are the pre-trace layout: attaching
    a context flips exactly ONE header byte (the flags) and appends the
    payload — nothing in the original encoding moves."""
    m = Message(
        type=MessageType.REPLICATE, to=2, from_=1, cluster_id=CID,
        term=3, log_term=3, log_index=9, commit=8,
        entries=[Entry(term=3, index=10, key=77, cmd=b"payload")],
    )
    b_none = encode_message(m)
    m.trace = ReplTrace(
        tid=41, origin="rt1:1", index=10, t_send=1234.5, t_recv=1234.6,
        t_append=1234.61, t_fsync=1234.62, t_ack=1234.63,
        t_ack_recv=1234.7,
    )
    b_trace = encode_message(m)
    assert len(b_trace) > len(b_none)
    diffs = [
        i for i in range(len(b_none)) if b_none[i] != b_trace[i]
    ]
    assert len(diffs) == 1, (
        f"trace attachment moved bytes besides the flag: {diffs}"
    )
    # round trips on both shapes
    d_trace = decode_message(b_trace)
    assert d_trace.trace is not None
    assert d_trace.trace.tid == 41
    assert d_trace.trace.origin == "rt1:1"
    assert d_trace.trace.index == 10
    assert d_trace.trace.t_ack_recv == 1234.7
    assert decode_message(b_none).trace is None
    # the clone a chan delivery hands the receiver is an isolated copy
    c = m.trace.clone()
    c.t_recv = 9.0
    assert m.trace.t_recv != 9.0


# ----------------------------------------------------------------------
# stage completeness leader -> follower -> leader (chan and tcp)
# ----------------------------------------------------------------------


def _propose_n(nh, n, cid=CID):
    s = nh.get_noop_session(cid)
    for _ in range(n):
        nh.sync_propose(s, b"x", timeout=30.0)


def _assert_complete(nhs, far_peer=None):
    ra = nhs[0].replattr
    assert ra is not None
    recs = wait_until(lambda: ra.records(), timeout=10.0, what="records")
    full = [r for r in recs if r["stages_ms"]]
    assert full, f"no record decomposed stages: {recs[:2]}"
    for rec in full:
        assert rec["closer"] is not None
        assert rec["close_ms"] is not None and rec["close_ms"] >= 0
        assert set(rec["stages_ms"]) == set(STAGES)
        # offset-corrected stages sum to the closer's measured RTT
        closer = str(rec["closer"])
        rtt = rec["peers"][closer]["rtt_ms"]
        assert rtt is not None
        assert sum(rec["stages_ms"].values()) == pytest.approx(
            rtt, abs=0.05
        )
        if far_peer is not None:
            assert rec["closer"] != far_peer
            assert far_peer in rec["laggards"]
    # the follower halves got filed in the FOLLOWERS' tracers, with
    # monotone stamps in the follower's own clock
    legs = [leg for nh in nhs[1:] for leg in nh.tracer.repl_legs()]
    assert legs, "no follower filed a replication leg"
    for leg in legs:
        assert leg["origin"] == nhs[0].raft_address()
        assert 0 < leg["t_recv"] <= leg["t_append"]
        assert leg["t_append"] <= leg["t_fsync"] <= leg["t_ack"]
    # the sampled leader traces carry the repl_quorum stage + summary
    done = [t for t in nhs[0].tracer.traces() if t.done and t.repl]
    assert done, "no completed leader trace carries a repl summary"
    assert any(
        any(e[0] == "repl_quorum" for e in t.events) for t in done
    )
    return recs


def test_stage_completeness_chan_slow_peer():
    nhs = _mk_chan_hosts(trace=1)
    try:
        _start(nhs)
        _force_leader(nhs)
        # peer 2 sits one far link away; leader + peer 3 are near.  50 ms
        # one way: the near peer closes every record unless a stall holds
        # its ack for a whole far round trip
        far_s = 0.05
        set_latency(
            nhs,
            crossdomain(["rt1:1", "rt3:1"], ["rt2:1"], far_s),
        )
        _propose_n(nhs[0], 8)
        time.sleep(0.3)
        recs = _assert_complete(nhs, far_peer=2)
        # the slow peer's late acks still priced its RTT.  Pipelined
        # sends coalesce onto one far round trip (the ack covering a
        # batch closes every record in it), so only the FIRST record of
        # a burst pays the full round trip — p99 sees it, p50 still sees
        # at least the one-way leg.  Lower bounds NOT load-scaled.
        wait_until(
            lambda: (nhs[0].replattr.summary()["peers"].get("2") or {})
            .get("rtt_p50_ms"),
            timeout=10.0, what="far-peer rtt",
        )
        summary = nhs[0].replattr.summary()
        assert summary["peers"]["2"]["rtt_p99_ms"] >= 2 * far_s * 1e3
        assert summary["peers"]["2"]["rtt_p50_ms"] >= far_s * 1e3
        assert summary["peers"]["2"]["laggard"] >= len(recs) - 1
        assert summary["peers"]["2"]["cls"] == "B"
        assert summary["peers"]["3"]["closer"] >= 1
        # quorum-closing-peer vs the scalar oracle on the live records:
        # reconstruct each peer's ack time (t_send + rtt) and check the
        # kth-smallest (leader self-acks at fan-out) names the closer
        for rec in recs:
            acks = {
                int(p): d["t_send"] + d["rtt_ms"] / 1e3
                for p, d in rec["peers"].items()
                if d["acked"] and d["t_send"] and d["rtt_ms"] is not None
            }
            t0 = min(d["t_send"] for d in rec["peers"].values()
                     if d["t_send"])
            oracle = _oracle_closer(t0, acks, rec["quorum"])
            if oracle and rec["closer"] in acks:
                assert rec["closer"] == oracle
    finally:
        _stop_all(nhs)


def test_stage_completeness_and_merge_tcp(tmp_path):
    nhs = _mk_tcp_hosts(tmp_path, trace=1)
    try:
        _start(nhs)
        _force_leader(nhs)
        _propose_n(nhs[0], 6)
        time.sleep(0.3)
        wait_until(
            lambda: [
                r for r in nhs[0].replattr.records() if r["stages_ms"]
            ],
            timeout=10.0, what="tcp records",
        )
        _assert_complete(nhs)
        # multi-host merge: the per-host dumps join into one timeline
        # with every host on the leader's clock and the leader's flow
        # ids preserved across processes
        import os
        import sys
        tools_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools",
        )
        sys.path.insert(0, tools_dir)
        try:
            from trace_merge import merge_dumps
        finally:
            sys.path.remove(tools_dir)
        dumps = [nh.dump_trace() for nh in nhs]
        merged = merge_dumps(dumps)
        md = merged["metadata"]
        assert md["reference_host"] == nhs[0].raft_address()
        assert set(md["merged_hosts"]) == {
            nh.raft_address() for nh in nhs
        }
        # every follower that filed a leg got a clock shift estimate
        legged = {
            nh.raft_address() for nh in nhs[1:] if nh.tracer.repl_legs()
        }
        assert legged - set(md["unsynced_hosts"]) == legged
        pids = {
            ev["pid"] for ev in merged["traceEvents"]
            if ev.get("cat") == "repl"
        }
        assert pids, "merged file lost the follower replication slices"
        # a leader flow id appears in >1 process: the cross-host join
        by_id = {}
        for ev in merged["traceEvents"]:
            if "id" in ev:
                by_id.setdefault(ev["id"], set()).add(ev["pid"])
        assert any(len(p) > 1 for p in by_id.values()), (
            "no flow spans leader and follower processes"
        )
    finally:
        _stop_all(nhs)


# ----------------------------------------------------------------------
# quorum-closing peer vs the scalar oracle (deterministic clock)
# ----------------------------------------------------------------------


class _FakeTrace:
    def __init__(self, tid):
        self.tid = tid
        self.done = False
        self.repl = None
        self.events = []

    def add(self, stage):
        self.events.append(stage)


class _FakeTracer:
    def __init__(self, by_key):
        self._by_key = by_key


def _oracle_closer(self_t0, acks, quorum):
    """The scalar oracle: ``try_commit`` advances when the quorum-th
    voter's match covers the index — sorted ack times ascending, the
    quorum-th smallest is the closing ack (leader counts at t0)."""
    times = sorted([(self_t0, 0)] + [(t, p) for p, t in acks.items()])
    return times[quorum - 1][1] if len(times) >= quorum else None


@pytest.fixture
def clock(monkeypatch):
    state = {"t": 1000.0}

    def now():
        return state["t"]

    monkeypatch.setattr(replattr_mod.time, "time", now)

    def advance(dt):
        state["t"] += dt
        return state["t"]

    return advance


def _open_record(ra, tr, peers=(2, 3), index=10, term=5, cid=CID):
    msgs = [
        Message(
            type=MessageType.REPLICATE, to=p, from_=1, cluster_id=cid,
            term=term, entries=[Entry(term=term, index=index, key=tr.tid)],
        )
        for p in peers
    ]
    ra.attach_sends(cid, msgs, _FakeTracer({tr.tid: tr}))
    assert all(m.trace is not None for m in msgs)
    return msgs


def test_quorum_closer_matches_oracle(clock):
    ra = ReplAttr(host="rt1:1", registry=MetricsRegistry())
    tr = _FakeTrace(tid=7)
    t0 = 1000.0
    _open_record(ra, tr, peers=(2, 3), index=10, term=5)
    # peer 3 acks first (fast), peer 2 is the injected slow peer
    t3 = clock(0.002)
    ra.on_ack(CID, 3, 10, 5)
    ra.on_commit(CID, 10, 5, {1: None, 2: None, 3: None}, 2, 1)
    rec = ra.records()[-1]
    assert rec["closer"] == 3
    assert rec["closer"] == _oracle_closer(t0, {3: t3}, 2)
    assert rec["laggards"] == [2]
    assert rec["close_ms"] == pytest.approx(2.0, abs=1e-6)
    assert tr.repl is rec
    assert "repl_quorum" in tr.events
    # the slow peer's ack lands AFTER the close: laggard keeps its
    # measured RTT in the summary (straggler window)
    clock(0.050)
    ra.on_ack(CID, 2, 10, 5)
    assert rec["peers"]["2"]["acked"]
    assert rec["peers"]["2"]["rtt_ms"] == pytest.approx(52.0, abs=1e-3)
    assert rec["peers"]["2"]["after_close_ms"] == pytest.approx(
        50.0, abs=1e-3
    )
    assert ra.commits_attributed == 1
    assert ra.records_dropped == 0


def test_quorum_closer_oracle_five_voters(clock):
    ra = ReplAttr(host="rt1:1", registry=MetricsRegistry())
    tr = _FakeTrace(tid=9)
    t0 = 1000.0
    voters = {1: None, 2: None, 3: None, 4: None, 5: None}
    _open_record(ra, tr, peers=(2, 3, 4, 5), index=20, term=5)
    acks = {}
    for dt, peer in ((0.001, 4), (0.003, 2), (0.009, 5)):
        acks[peer] = clock(dt)
        ra.on_ack(CID, peer, 20, 5)
    # quorum 3 of 5: self@t0, peer4, peer2 — peer 2's ack closes
    ra.on_commit(CID, 20, 5, voters, 3, 1)
    rec = ra.records()[-1]
    oracle = _oracle_closer(t0, acks, 3)
    assert rec["closer"] == 2 == oracle
    assert rec["laggards"] == [3]


def test_stage_decomposition_sums_to_rtt(clock):
    ra = ReplAttr(host="rt1:1", registry=MetricsRegistry())
    ra.resolver = lambda cid, nid: f"peer{nid}:1"
    tr = _FakeTrace(tid=11)
    _open_record(ra, tr, peers=(2,), index=30, term=5)
    # follower clock runs 1h ahead: the ack-pair estimate must still
    # reconcile the stages to the leader-measured RTT
    skew = 3600.0
    t_send = 1000.0
    ctx = ReplTrace(
        tid=11, origin="rt1:1", index=30, t_send=t_send,
        t_recv=t_send + skew + 0.010,   # 10ms wire out (follower clock)
        t_append=t_send + skew + 0.012,
        t_fsync=t_send + skew + 0.015,
        t_ack=t_send + skew + 0.016,
    )
    t_ack_recv = clock(0.026)
    ctx.t_ack_recv = t_ack_recv
    ra.on_ack(CID, 2, 30, 5, ctx)
    ra.on_commit(CID, 30, 5, {1: None, 2: None, 3: None}, 2, 1)
    rec = ra.records()[-1]
    assert rec["closer"] == 2
    st = rec["stages_ms"]
    assert set(st) == set(STAGES)
    assert sum(st.values()) == pytest.approx(26.0, abs=1e-3)
    assert st["follower_append"] == pytest.approx(2.0, abs=1e-3)
    assert st["follower_fsync"] == pytest.approx(3.0, abs=1e-3)
    assert st["ack_send"] == pytest.approx(1.0, abs=1e-3)
    # the 1h skew never leaks into a stage (offset-corrected)
    assert all(0 <= v < 30.0 for v in st.values())
    # and the offset estimate recovers the skew for trace_merge
    off = ra.offsets()
    assert off and all(abs(v - skew) < 0.1 for v in off.values())


# ----------------------------------------------------------------------
# mid-trace leadership transfer: no cross-term attribution
# ----------------------------------------------------------------------


def test_no_cross_term_attribution(clock):
    ra = ReplAttr(host="rt1:1", registry=MetricsRegistry())
    tr = _FakeTrace(tid=13)
    _open_record(ra, tr, peers=(2, 3), index=40, term=5)
    clock(0.002)
    # acks arriving with a LATER term never fold into the term-5 record
    ra.on_ack(CID, 3, 40, 6)
    assert ra.records() == []
    assert ra.records_dropped == 1
    # a commit in the later term covering the index attributes nothing
    tr2 = _FakeTrace(tid=14)
    _open_record(ra, tr2, peers=(2, 3), index=41, term=5)
    ra.on_commit(CID, 41, 6, {1: None, 2: None, 3: None}, 2, 1)
    assert ra.commits_attributed == 0
    assert ra.records_dropped == 2
    assert tr2.repl is None


def test_reset_drops_open_records(clock):
    ra = ReplAttr(host="rt1:1", registry=MetricsRegistry())
    tr = _FakeTrace(tid=15)
    _open_record(ra, tr, peers=(2, 3), index=50, term=5)
    ra.on_reset(CID)
    assert ra.records_dropped == 1
    # post-reset commits find nothing to misattribute
    ra.on_commit(CID, 50, 6, {1: None, 2: None, 3: None}, 2, 1)
    assert ra.commits_attributed == 0


def test_live_transfer_no_cross_term(clock):
    """Live half of the transfer contract: records opened under the old
    leader never close against the new leader's commits."""
    ra = ReplAttr(host="rt1:1", registry=MetricsRegistry())
    tr = _FakeTrace(tid=16)
    _open_record(ra, tr, peers=(2, 3), index=60, term=5)
    # transfer: raft.reset fires on the stepped-down leader
    ra.on_reset(CID)
    # the new leader (this host again, later term) re-proposes the
    # entry at the same index — a fresh record in the new term
    tr3 = _FakeTrace(tid=17)
    _open_record(ra, tr3, peers=(2, 3), index=60, term=7)
    clock(0.001)
    ra.on_ack(CID, 2, 60, 7)
    ra.on_commit(CID, 60, 7, {1: None, 2: None, 3: None}, 2, 1)
    recs = ra.records()
    assert len(recs) == 1
    assert recs[0]["term"] == 7
    assert recs[0]["tid"] == 17


def test_observer_ack_keeps_straggler_window_open(clock):
    """A non-voter (observer/witness) ack must not count toward the
    straggler-window release: with voters {1,2,3} and observer 9, the
    closed record stays registered until the lagging VOTER acks, so its
    late RTT still enriches the summary."""
    ra = ReplAttr(host="rt1:1", registry=MetricsRegistry())
    tr = _FakeTrace(tid=19)
    _open_record(ra, tr, peers=(2, 3, 9), index=80, term=5)
    voters = {1: None, 2: None, 3: None}
    clock(0.001)
    ra.on_ack(CID, 3, 80, 5)       # fast voter
    ra.on_commit(CID, 80, 5, voters, 2, 1)
    rec = ra.records()[-1]
    assert rec["closer"] == 3 and rec["laggards"] == [2]
    clock(0.001)
    ra.on_ack(CID, 9, 80, 5)       # observer ack — window must survive
    clock(0.050)
    ra.on_ack(CID, 2, 80, 5)       # the lagging voter, 52ms out
    assert rec["peers"]["2"]["acked"]
    assert rec["peers"]["2"]["rtt_ms"] == pytest.approx(52.0, abs=1e-3)


def test_sweep_expires_abandoned_records(clock):
    ra = ReplAttr(host="rt1:1", registry=MetricsRegistry(), expire_s=1.0)
    tr = _FakeTrace(tid=18)
    _open_record(ra, tr, peers=(2, 3), index=70, term=5)
    assert ra.sweep() == 0
    clock(2.0)
    assert ra.sweep() == 1
    assert ra.records_dropped == 1


# ----------------------------------------------------------------------
# satellites: transport metric families + latency-class introspection
# ----------------------------------------------------------------------


def test_transport_metrics_help_roundtrip():
    reg = MetricsRegistry()
    tm = TransportMetrics(registry=reg)
    tm.message_sent(3)
    tm.batch_sent(128)
    tm.batch_received(64)
    tm.snapshot_chunks_sent(4)
    tm.snapshot_chunks_received()
    out = io.StringIO()
    reg.write_health_metrics(out)
    text = out.getvalue()
    seen_help = set()
    for line in text.splitlines():
        if line.startswith("# HELP "):
            seen_help.add(line.split()[2])
        elif line.startswith("# TYPE "):
            name = line.split()[2]
            if name.startswith("dragonboat_transport_"):
                assert name in seen_help, f"{name} TYPE without HELP"
    # every family is zero-registered at construction: an idle
    # transport scrapes as zeros, not as absent families
    for name in TransportMetrics.NAMES:
        assert f"\n{name}" in text or text.startswith(name), (
            f"{name} missing from the exposition"
        )
    assert tm.value("dragonboat_transport_batch_sent_total") == 1
    assert tm.value("dragonboat_transport_bytes_sent_total") == 128
    assert tm.value("dragonboat_transport_bytes_received_total") == 64
    assert tm.value(
        "dragonboat_transport_snapshot_chunk_sent_total"
    ) == 4


def test_latency_injector_health_snapshot():
    inj = crossdomain(["a:1", "b:1"], ["c:1"], 0.04)
    assert inj.domain_of("a:1") == "A"
    assert inj.domain_of("c:1") == "B"
    assert inj.domain_of("nope:1") is None
    snap = inj.health_snapshot()
    assert snap["domains"] == {"a:1": "A", "b:1": "A", "c:1": "B"}
    assert snap["classes"]
    link = snap["links"].get("A|B")
    assert link is not None
    assert link["one_way_s"] == pytest.approx(0.04)
    assert link["cls"] is not None  # labeled by latency class
    # the nearest-class resolver tolerates unknown delays
    assert inj.class_name(12345.0) is None


def test_repl_metric_families_help_roundtrip():
    reg = MetricsRegistry()
    ReplAttr(host="rt1:1", registry=reg)
    out = io.StringIO()
    reg.write_health_metrics(out)
    text = out.getvalue()
    seen_help = set()
    for line in text.splitlines():
        if line.startswith("# HELP "):
            seen_help.add(line.split()[2])
        elif line.startswith("# TYPE "):
            name = line.split()[2]
            if name.startswith("dragonboat_repl_"):
                assert name in seen_help, f"{name} TYPE without HELP"
    assert "dragonboat_repl_commits_attributed_total" in text
