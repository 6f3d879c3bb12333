"""A group's sleep on the device tick plane (``Config.quiesce`` with
``quorum_engine="tpu"``).

The row's idle clock, its quiesced flag and its threshold are columns of
the tick kernel (``ops/kernels.tick_step``, ``quiesce_marks``); the twin is
``quiesce.QuiesceManager``, which the scalar engine keeps.  First the
columns against the manager, tick for tick over seeded activity traces;
then live trios: a sleeping replica gets no ``LOCAL_TICK`` and no
step-worker turn a tick, a request wakes its group without an election and
is answered by that attempt, and a leader that died during the sleep is
replaced once the group wakes.
"""
from __future__ import annotations

import random
import signal
import threading
import time

import numpy as np
import pytest

from dragonboat_tpu import Config, NodeHost, NodeHostConfig
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.ops import BatchedQuorumEngine
from dragonboat_tpu.quiesce import QuiesceManager
from dragonboat_tpu.requests import RequestResultCode
from dragonboat_tpu.transport import ChanRouter, ChanTransport
from dragonboat_tpu.wire import MessageType as MT

from tests.test_quiesce import KVSM


@pytest.fixture(autouse=True)
def time_limit():
    """Each test's own limit: a test that hangs fails here, by itself."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        raise TimeoutError("test exceeded its 240 s limit")

    old = signal.signal(signal.SIGALRM, expired)
    signal.alarm(240)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# ----------------------------------------------------------------------
# the idle columns against QuiesceManager
# ----------------------------------------------------------------------

#: what can reach a replica between two ticks; None is nothing
KINDS = (
    None, MT.PROPOSE, MT.REPLICATE, MT.REPLICATE_RESP, MT.READ_INDEX,
    MT.READ_INDEX_RESP, MT.REQUEST_VOTE, MT.HEARTBEAT, MT.HEARTBEAT_RESP,
    MT.QUIESCE,
)
ELECTION = 2  # threshold 20 ticks: a trace holds many sleeps


def _feed(mgr: QuiesceManager, eng, cid: int, kind) -> None:
    """One message into the manager and, by ``Node._activity``'s rule, into
    the row: a heartbeat is no activity while awake, anything wakes a
    sleeping replica, a peer's QUIESCE puts an awake one to sleep."""
    if kind == MT.QUIESCE:
        if not mgr.quiesced():
            eng.quiesce_mark(cid, wake=False)
        mgr.try_enter_quiesce()
        return
    heartbeat = kind in (MT.HEARTBEAT, MT.HEARTBEAT_RESP)
    if mgr.quiesced() or not heartbeat:
        eng.quiesce_mark(cid, wake=True)
    mgr.record_activity(kind)


@pytest.mark.parametrize("seed", range(6))
def test_idle_columns_match_quiesce_manager_tick_for_tick(seed):
    rng = random.Random(seed)
    groups = list(range(1, 13))
    eng = BatchedQuorumEngine(16, 4, event_cap=64)
    eng.enable_quiesce()
    mgrs = {}
    for cid in groups:
        eng.add_group(
            cid, [1, 2, 3], 1, election_timeout=ELECTION, rand_timeout=3,
            quiesce_threshold=ELECTION * 10,
        )
        if cid % 2:
            eng.set_leader(cid, term=1, term_start=1, last_index=1)
        mgrs[cid] = QuiesceManager(cid, 1, ELECTION, True)
    # a group's own rate of events: busy ones never sleep, quiet ones do
    rate = {cid: rng.choice((0.0, 0.01, 0.03, 0.1, 0.5)) for cid in groups}
    rows = [eng.groups[cid].row for cid in groups]
    entries = {cid: 0 for cid in groups}
    fired = {cid: 0 for cid in groups}
    slept = woken = 0
    tick = 0
    while tick < 360:
        was = {cid: mgrs[cid].quiesced() for cid in groups}
        for cid in groups:
            if rng.random() < rate[cid]:
                kind = rng.choice(KINDS)
                if kind is not None:
                    # (a message to a follower row that resets its
                    # election clock besides: the marks ride beside it)
                    if kind == MT.HEARTBEAT and cid % 2 == 0:
                        eng.leader_contact(cid)
                    _feed(mgrs[cid], eng, cid, kind)
        woken += sum(was[c] and not mgrs[c].quiesced() for c in groups)
        # mostly one tick a round; now and then a round that does not tick
        # and a backlog replayed as one fused block
        k = rng.choice((1, 1, 1, 1, 0, 3))
        if k == 0:
            res = eng.step(do_tick=False)
        elif k == 1:
            res = eng.step(do_tick=True)
        else:
            eng.begin_round()
            res = eng.step_rounds(
                do_tick=True, pad_rounds_to=4, tick_rounds=k)
        own = set()
        for _ in range(k):
            tick += 1
            for cid in groups:
                m = mgrs[cid]
                before = m.quiesced()
                m.increase_quiesce_tick()
                if m.quiesced() and not before:
                    own.add(cid)
                    entries[cid] += 1
                # ``just_entered_quiesce`` fires once an own entry, on the
                # tick behind it; (a first sleep on a peer's word fires
                # it too: the device tells peers of own entries only)
                if m.just_entered_quiesce() and m.quiesced_since != 0:
                    fired[cid] += 1
        assert set(res.quiesce) == own, (tick, res.quiesce, own)
        slept += len(own)
        q = eng.read_rows("quiesced", rows)
        idle = eng.read_rows("idle_tick", rows)
        for i, cid in enumerate(groups):
            m = mgrs[cid]
            assert bool(q[i]) == m.quiesced(), (tick, cid)
            if not m.quiesced():
                assert idle[i] == m.current_tick - m.idle_since, (tick, cid)
        if k == 1:
            # a sleeping row fires nothing, the tick that put it to sleep
            # included; an awake leader heartbeats every tick
            # (heartbeat_timeout 1).  (A fused block ORs its rounds'
            # flags: a row that fired and then fell asleep shows both.)
            asleep = {c for c in groups if mgrs[c].quiesced()}
            assert not asleep & set(res.heartbeat), (tick, res.heartbeat)
            assert not asleep & set(res.elect), (tick, res.elect)
            awake_leaders = {c for c in groups if c % 2 and c not in asleep}
            assert awake_leaders <= set(res.heartbeat)
    assert slept > 10 and woken > 5, (slept, woken)  # the trace had both


def test_a_woken_rows_clocks_start_from_the_wake():
    """A follower row counts ``election_tick`` through its sleep
    (``Raft.quiesced_tick``) and raises no election-due flag; the wake
    puts the clock to zero, so the first flag comes a whole randomized
    timeout after it."""
    eng = BatchedQuorumEngine(4, 4, event_cap=16)
    eng.enable_quiesce()
    eng.add_group(7, [1, 2, 3], 2, election_timeout=4, rand_timeout=6,
                  quiesce_threshold=20)
    row = eng.groups[7].row
    flags = []
    for t in range(40):
        if t % 3 == 0:
            eng.leader_contact(7)  # a live leader's heartbeats: no activity
        res = eng.step(do_tick=True)
        flags.append((bool(res.quiesce), bool(res.elect)))
    assert flags[20] == (True, False) and sum(q for q, _e in flags) == 1
    assert not any(e for _q, e in flags)
    for _ in range(30):  # the leader falls silent too: nothing fires
        assert not eng.step(do_tick=True).elect
    assert eng.read_rows("election_tick", [row])[0] >= 30
    eng.quiesce_mark(7, wake=True)
    due = [bool(eng.step(do_tick=True).elect) for _ in range(7)]
    assert due == [False] * 5 + [True, False]  # rand_timeout 6 from the wake


def test_a_full_width_group_cannot_quiesce_on_the_device():
    eng = BatchedQuorumEngine(4, 3, event_cap=16)
    with pytest.raises(ValueError):  # the latch first
        eng.add_group(1, [1, 2], 1, quiesce_threshold=10)
    eng.enable_quiesce()
    with pytest.raises(ValueError):  # the last peer slot carries the marks
        eng.add_group(1, [1, 2, 3], 1, quiesce_threshold=10)
    eng.add_group(1, [1, 2, 3], 1)
    eng.add_group(2, [1, 2], 1, quiesce_threshold=10)


# ----------------------------------------------------------------------
# live trios
# ----------------------------------------------------------------------

RTT = 10
GROUPS = (1, 2, 3, 4)


class Trio:
    """Three chan-transport NodeHosts on the device engine, ``GROUPS`` of
    three replicas each with ``Config.quiesce`` (four groups on three
    hosts: some host leads two, so that more than one heartbeat is due a
    tick there: the block plane)."""

    def __init__(self, metrics=False, trace=0):
        self.addrs = {1: "d1:1", 2: "d2:1", 3: "d3:1"}
        self.router = ChanRouter()
        self.nhs = {}
        for i in self.addrs:
            self.nhs[i] = NodeHost(NodeHostConfig(
                node_host_dir=":memory:", rtt_millisecond=RTT,
                raft_address=self.addrs[i], enable_metrics=metrics,
                trace_sample_every=trace,
                raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                    src, rh, ch, router=self.router),
                expert=ExpertConfig(
                    quorum_engine="tpu", engine_block_groups=16,
                    engine_warm_fused=False),
            ))
        for cid in GROUPS:
            for i, nh in self.nhs.items():
                nh.start_cluster(
                    self.addrs, False, lambda c, n: KVSM(c, n),
                    Config(cluster_id=cid, node_id=i, election_rtt=10,
                           heartbeat_rtt=1, quiesce=True))
        for cid in GROUPS:
            self.nhs[1].get_node(cid).request_campaign()
        self.wait(lambda: all(self.leader(c) for c in GROUPS),
                  "a leader for every group")
        # no warm-up here: a host's first write and first read compile
        # inside a round, which can depose a leader at this rtt and lose
        # the request with it.  Spend those on requests nobody asserts.
        for cid in GROUPS:
            for nh in self.nhs.values():
                self.retry(lambda: nh.propose(
                    nh.get_noop_session(cid), b"w=0", timeout=5.0))
                self.retry(lambda: nh.read_index(cid, 5.0))

    def retry(self, submit, attempts=8):
        for _ in range(attempts):
            try:
                if submit().wait(6.0).completed:
                    return
            except Exception:
                time.sleep(0.05)
        raise AssertionError("no attempt completed")

    def wait(self, cond, what, timeout=60.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if cond():
                return
            time.sleep(0.02)
        raise AssertionError(f"timed out waiting for {what}")

    def leader(self, cid):
        for nh in self.nhs.values():
            lid, ok = nh.get_leader_id(cid)
            if ok:
                return lid
        return None

    def follower(self, cid):
        """A host that does not lead ``cid``."""
        return next(i for i in self.nhs if i != self.leader(cid))

    def nodes(self, cid=None):
        return [nh.get_node(c) for nh in self.nhs.values()
                for c in (GROUPS if cid is None else (cid,))]

    def asleep(self, cid=None):
        return [n.quiesced() for n in self.nodes(cid)]

    def wait_asleep(self, hosts=None):
        nodes = [n for n in self.nodes()
                 if hosts is None or n.node_id in hosts]
        self.wait(lambda: all(n.quiesced() for n in nodes),
                  "every replica asleep")

    def terms(self, cid):
        return [n.peer.raft.term for n in self.nodes(cid)]

    def stop(self):
        for nh in self.nhs.values():
            nh.stop()


@pytest.fixture
def trio():
    t = Trio()
    try:
        yield t
    finally:
        t.stop()


def test_quiesce_group_is_device_ticked_lite_and_on_the_block_plane(trio):
    for n in trio.nodes():
        assert n.dev_quiesce and n.peer.raft.device_ticks
        assert n.tick_lite() and not n.quiesce_mgr.enabled
    # awake, the heartbeats of a host that leads two groups go by the block
    coords = [nh.quorum_coordinator for nh in trio.nhs.values()]
    trio.wait(lambda: any(c.hb_block_rows > 0 for c in coords),
              "a heartbeat block")
    assert all(c.hb_single_causes["membership"] == 0 for c in coords)
    trio.wait_asleep()
    for nh in trio.nhs.values():
        c = nh.quorum_coordinator
        assert c.rows_quiesced == len(GROUPS)
        rows = [c.eng.groups[cid].row for cid in GROUPS]
        with c._mu:
            assert c.eng.read_rows("quiesced", rows).all()


def test_a_sleeping_trio_costs_no_tick_message_and_no_step_turn(trio):
    trio.wait_asleep()
    time.sleep(0.2)  # the QUIESCE exchange settles
    counts = {"ticks": 0}
    lock = threading.Lock()

    def counting(self_node):
        with lock:
            counts["ticks"] += 1

    nodes = trio.nodes()
    for n in nodes:
        n.request_tick = counting.__get__(n)
    def stepped():
        return [sum(w["groups_stepped"]
                    for w in nh.engine.stats()["step_workers"])
                for nh in trio.nhs.values()]

    stepped0 = stepped()
    blocks0 = [nh.quorum_coordinator.hb_block_rows for nh in trio.nhs.values()]
    ticks0 = [nh.tick_count for nh in trio.nhs.values()]
    time.sleep(1.0)  # a hundred ticks
    assert all(nh.tick_count - t0 >= 50
               for nh, t0 in zip(trio.nhs.values(), ticks0))
    assert counts["ticks"] == 0
    assert stepped() == stepped0
    assert [nh.quorum_coordinator.hb_block_rows
            for nh in trio.nhs.values()] == blocks0
    assert all(trio.asleep())
    assert all(not n.mq.get() for n in nodes)


@pytest.mark.parametrize("kind", ["write", "read"])
def test_a_request_wakes_its_group_without_an_election(trio, kind):
    """A write at the leader's host wakes the leader first, a read at a
    follower's host the follower: neither elects anybody, and the attempt
    that woke the group is the one that is answered."""
    nh = trio.nhs[trio.leader(2)]
    s = nh.get_noop_session(2)
    assert nh.propose(s, b"k=v0", timeout=10.0).wait(20.0).completed
    trio.wait_asleep()
    for cid in GROUPS:
        before = trio.terms(cid)
        leader = trio.leader(cid)
        host = leader if kind == "write" else trio.follower(cid)
        t0 = time.time()
        if kind == "write":
            rs = trio.nhs[host].propose(
                trio.nhs[host].get_noop_session(cid), b"k=v1", timeout=5.0)
        else:
            rs = trio.nhs[host].read_index(cid, 5.0)
        res = rs.wait(6.0)
        assert res.code == RequestResultCode.COMPLETED, (cid, res.code)
        assert time.time() - t0 < 2.0
        assert not trio.nhs[host].get_node(cid).quiesced()
        time.sleep(0.5)  # two election timeouts: nobody campaigns
        assert trio.terms(cid) == before, cid
        assert trio.leader(cid) == leader


def test_a_leader_that_died_asleep_is_replaced_after_the_wake(trio):
    trio.wait_asleep()
    term = max(trio.terms(1))
    dead = trio.leader(1)
    trio.nhs.pop(dead).stop()  # group 1's leader goes, unnoticed: all sleep
    time.sleep(0.6)            # three election timeouts: nobody campaigns
    a, b = trio.nhs
    survivors = [trio.nhs[a].get_node(1), trio.nhs[b].get_node(1)]
    assert all(n.quiesced() for n in survivors)
    assert [n.peer.raft.term for n in survivors] == [term, term]
    t0 = time.time()
    trio.nhs[a].read_index(1, 2.0)  # wakes the follower; nobody answers it
    # rand_timeout is under two election timeouts (20 ticks of 10 ms): a
    # new leader about that long after the wake, with room for a split vote
    trio.wait(lambda: trio.nhs[a].get_leader_id(1) in ((a, True), (b, True)),
              "a new leader", 10.0)
    elected = time.time() - t0
    assert elected < 2.0, elected
    assert max(n.peer.raft.term for n in survivors) > term
    # (a read before the new leader's first commit is DROPPED: retried)
    trio.retry(lambda: trio.nhs[a].read_index(1, 5.0))


def test_spans_and_counters_of_a_wake():
    """Tracer on: a sampled request that found its group asleep leaves one
    ``quiesce_wake`` span (``woke``, ``elected``, the wake to the commit
    or the confirmation), the rounds carry ``rows_quiesced`` /
    ``quiesce_enters`` / ``quiesce_wakes``, and scalar ticks are counted
    by the second (none: every replica is device-ticked)."""
    from dragonboat_tpu import obs

    t = Trio(trace=1)
    try:
        t.wait_asleep()
        lo = time.perf_counter()
        nh = t.nhs[t.leader(1)]
        reader = t.nhs[t.follower(2)]
        assert nh.propose(
            nh.get_noop_session(1), b"a=b", timeout=5.0).wait(6.0).completed
        assert reader.read_index(2, 5.0).wait(6.0).completed
        # the group's third replica wakes with the leader's next heartbeat
        t.wait(lambda: sum(h.quorum_coordinator.quiesce_wakes
                           for h in t.nhs.values()) >= 6, "six wakes", 10.0)
        time.sleep(0.3)
        spans = [s for s in obs.default_recorder().spans()
                 if s is not None and s["kind"] == "quiesce_wake"
                 and s["t0"] >= lo]
        by = {(s["op"], s["cluster_id"]): s for s in spans}
        w, r = by[("write", 1)], by[("read", 2)]
        assert (w["woke"], r["woke"]) == ("leader", "follower")
        assert not w["elected"] and not r["elected"]
        assert 0 < w["wake_ms"] < 2000 and 0 < r["wake_ms"] < 2000
        assert w["host"] == nh.nhconfig.raft_address
        assert r["host"] == reader.nhconfig.raft_address
        rounds = [s for s in obs.default_recorder().spans()
                  if s is not None and s["kind"] == "coord_round"
                  and s.get("rows_quiesced") is not None]
        assert rounds and max(s["rows_quiesced"] for s in rounds) == 4
        assert sum(s["quiesce_wakes"] for s in rounds if s["t0"] >= lo) >= 5
        assert sum(s["quiesce_enters"] for s in rounds) >= 4
        for h in t.nhs.values():
            assert h.replica_obs.window(lo, time.perf_counter() + 1).get(
                "scalar_ticks", 0) == 0
        import io

        out = io.StringIO()
        nh.write_health_metrics(out)
        text = out.getvalue()
        for name in ("dragonboat_coord_rows_quiesced",
                     "dragonboat_coord_quiesce_enters_total",
                     "dragonboat_coord_quiesce_wakes_total",
                     "dragonboat_node_scalar_ticks_total"):
            assert f"# HELP {name} " in text and f"# TYPE {name} " in text
    finally:
        t.stop()
