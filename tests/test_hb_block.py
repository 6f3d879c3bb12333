"""The batched heartbeat plane (tpuquorum.py) against the per-group path.

Three NodeHosts on the CPU backend with a test-driven virtual clock (the
wall tick worker never fires: ``rtt_millisecond`` is 1000 s), 128 groups,
driven tick by tick and host by host with every thread settled in between,
so what a tick costs and what it leaves behind is a count, not a timing:

(i)   a steady-state tick costs a host a bounded number of message events
      and step-worker turns, whatever the number of groups;
(ii)  one seeded sequence of ticks, elections, a term change mid-block, a
      lagging follower and a pending ReadIndex context leaves the same
      election clocks (scalar and device), check-quorum contacts, commit
      indexes and, row for row, the same messages on the wire whether the
      heartbeats go by the block or by today's per-group message (the
      scalar path is the oracle); a quarter of the groups are lease
      groups (``read_lease``), and the block legs leave their
      ``LeaderLease`` what the scalar handlers leave it: the ack bases,
      the send FIFOs, ``remaining()`` and the reads answered under it;
(iii) the tick deficit is counted (replayed, dropped) and a host that was
      away for an election timeout holds its elections instead of
      deposing sound leaders;
(iv)  a REPLICATE lost to a streaming remote is found by the response to
      the next heartbeat, by the block as by the per-group message, and
      ``Raft.tick_quiet`` counts what the ticks it stands for count.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from dragonboat_tpu import Config, NodeHost, NodeHostConfig, Result
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.transport import ChanRouter, ChanTransport
from dragonboat_tpu.wire import (
    Message, MessageType, pack_hb_rows, unpack_hb_rows,
)

MT = MessageType
GROUPS = 128
#: what ``World.state`` holds for a replica, in order
STATE_FIELDS = ("state", "term", "leader", "election_tick", "committed",
                "last_index", "remotes", "device_election_tick",
                "device_active", "lease")
#: request timeouts count TICKS of the virtual clock (a tick is 1000 s of
#: it): long enough that no tick the script drives can time a request out
FOREVER_S = 1e9
SENT = "dragonboat_transport_message_sent"
RECEIVED = "dragonboat_transport_message_received"


#: every World's transports count into the process's metrics registry
#: (one for all hosts), so the messages they took are counted here for
#: all Worlds too: ``handed`` (a transport took it) against "sent" (its
#: sender thread delivered it) since the registry was first seen
_TAPPED = {"handed": 0, "base": {}}


def _wait(pred, timeout_s: float, what: str) -> None:
    from tests.loadwait import wait_until

    wait_until(pred, timeout_s, interval=0.02, what=what)


def leased(cid: int) -> bool:
    """A quarter of the groups read under a leader lease, some led by
    every host (leaders sit on host ``cid % 3``)."""
    return cid % 4 == 1


def lease_state(r):
    """What a replica's ``LeaderLease`` holds, on the tick clock: the ack
    bases, the send FIFOs with their counts, the refused sends, who has a
    wall stamp (the instants are the wall's own), ceded, the ticks of
    validity left, and the reads it answered and turned away."""
    lease = r.lease
    if lease is None:
        return None
    return (
        tuple(sorted(lease.bases.items())),
        tuple(sorted((nid, tuple(tuple(e) for e in dq))
                     for nid, dq in lease._pending.items() if dq)),
        tuple(sorted((n, c) for n, c in lease._unrecorded.items() if c)),
        tuple(sorted(lease._ack_walls)),
        lease.ceded,
        lease.remaining(r.tick_count, r.quorum(), r.voting_members(),
                        r.node_id),
        lease.reads_local, lease.reads_fallback,
    )


class CountSM:
    def __init__(self, cluster_id, node_id):
        self.n = 0

    def update(self, cmd):
        self.n += 1
        return Result(value=self.n)

    def lookup(self, query):
        return self.n

    def save_snapshot(self, w, files, done):
        w.write(self.n.to_bytes(8, "little"))

    def recover_from_snapshot(self, r, files, done):
        self.n = int.from_bytes(r.read(8), "little")

    def close(self):
        pass


class World:
    """Three NodeHosts, ``groups`` groups, a virtual clock and a log of
    every raft message handed to a transport, block rows expanded into
    the per-group messages they stand for."""

    def __init__(self, tag: str, groups: int = GROUPS, block: bool = True):
        self.router = ChanRouter()
        self.block = block
        self.addrs = {i: f"hb-{tag}{i}:1" for i in (1, 2, 3)}
        self.cids = list(range(1, groups + 1))
        self.wire = []
        self.nhs = []
        for i in (1, 2, 3):
            nh = NodeHost(NodeHostConfig(
                node_host_dir=":memory:",
                rtt_millisecond=1_000_000,
                raft_address=self.addrs[i],
                raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                    s, rh, ch, router=self.router),
                expert=ExpertConfig(
                    quorum_engine="tpu", engine_block_groups=groups),
            ))
            self.nhs.append(nh)
            self._tap(nh, block)
        for cid in self.cids:
            for i, nh in enumerate(self.nhs, 1):
                nh.start_cluster(
                    self.addrs, False, CountSM,
                    Config(cluster_id=cid, node_id=i, election_rtt=10,
                           heartbeat_rtt=1, check_quorum=True,
                           read_lease=leased(cid), snapshot_entries=0),
                )
        self.coords = [nh.quorum_coordinator for nh in self.nhs]
        # what a World stopped before this one left undelivered stays so:
        # start even (a World being built beside this one has sent nothing)
        _TAPPED["handed"] = sum(
            m.value(SENT) - base for m, base in _TAPPED["base"].values())

    def _tap(self, nh, block: bool) -> None:
        inner = nh.transport.send_to_host

        def tapped(addr, m):
            if m.type in (MT.HEARTBEAT_BLOCK, MT.HEARTBEAT_RESP_BLOCK):
                kind = (MT.HEARTBEAT if m.type == MT.HEARTBEAT_BLOCK
                        else MT.HEARTBEAT_RESP)
                for cid, to, from_, term, commit in unpack_hb_rows(m):
                    self.wire.append(
                        (kind.name, cid, from_, to, term, commit, 0, 0))
            else:
                # a ReadIndex hint is a random context id: its presence
                hint = m.hint if m.type not in (
                    MT.HEARTBEAT, MT.HEARTBEAT_RESP, MT.READ_INDEX,
                    MT.READ_INDEX_RESP) else int(bool(m.hint))
                self.wire.append((m.type.name, m.cluster_id, m.from_, m.to,
                                  m.term, m.commit, hint, len(m.entries)))
            taken = inner(addr, m)
            _TAPPED["handed"] += bool(taken)
            return taken

        nh.transport.send_to_host = tapped
        metrics = nh.transport.metrics
        if id(metrics.registry) not in _TAPPED["base"]:
            _TAPPED["base"][id(metrics.registry)] = (
                metrics, metrics.value(SENT))
        coord = nh.quorum_coordinator
        if block:
            coord.attach_host_link(nh.node_registry.resolve, tapped)
        else:
            coord._hb_link = None  # today's per-group path, the oracle

    def inject_heartbeats(self, host: int, rows) -> None:
        """Heartbeats from node 1 arriving at ``host``: as one block where
        the world goes by the block, as the per-group messages the rows
        stand for where it does not."""
        nh = self.nhs[host - 1]
        if self.block:
            nh.quorum_coordinator.on_heartbeat_block(
                Message(type=MT.HEARTBEAT_BLOCK, entries=pack_hb_rows(rows)),
                self.addrs[1])
            return
        for cid, to, from_, term, commit in rows:
            nh.get_node(cid).handle_message_batch(Message(
                type=MT.HEARTBEAT, cluster_id=cid, to=to, from_=from_,
                term=term, commit=commit))

    def drops_1_to_3(self, batch) -> bool:
        """A drop hook: everything host 1 sends host 3 is lost (a batch
        goes to one host; node ids are host numbers here)."""
        if batch.source_address != self.addrs[1]:
            return False
        m = batch.requests[0]
        if m.type in (MT.HEARTBEAT_BLOCK, MT.HEARTBEAT_RESP_BLOCK):
            return unpack_hb_rows(m)[0][1] == 3
        return m.to == 3

    # ---- the clock and the settle -----------------------------------

    def _activity(self):
        """Per host: what has moved so far, and what is queued anywhere."""
        sig = []
        for nh in self.nhs:
            m = nh.transport.metrics
            st = nh.engine.stats()
            c = nh.quorum_coordinator
            nodes = list(nh._clusters.values())
            moved = (
                m.value(SENT), m.value(RECEIVED),
                sum(w["groups_stepped"] for w in st["step_workers"]),
                sum(k["cycles"] for k in st["committers"]),
                sum(n.sm.get_last_applied() for n in nodes),
                sum(n.peer.raft.term + n.peer.raft.log.committed
                    for n in nodes),
            )
            queued = (
                len(c._staged) + c._pending.is_set()
                + (c._tick_seq - c._tick_seen)
                + sum(n.commit_inflight + n._update_out for n in nodes)
                + sum(len(n.mq._left) + len(n.mq._right) for n in nodes)
                + sum(len(r) for r in nh.engine.step_ready.ready)
                + sum(len(r) for r in nh.engine.apply_ready.ready)
                + sum(len(sq._q) for sq in
                      list(nh.transport._queues.values()))
            )
            sig.append((moved, queued))
        # a message a sender thread has taken off its queue and not yet
        # delivered is in no queue: handed over, not yet counted as sent
        sent = sum(m.value(SENT) - base
                   for m, base in _TAPPED["base"].values())
        sig.append((sent, _TAPPED["handed"] - sent))
        return sig

    def settle(self, timeout_s: float = 120.0) -> None:
        """Until nothing has moved and nothing is queued for twenty polls
        in a row that each woke on time: every message delivered and
        stepped, every staged op drained and dispatched.  A poll that
        overslept says the interpreter (or the host: the suite runs six
        workers wide) was busy: a worker holding work it took off a queue
        may not have run either, so the count starts again; more polls
        are asked for the higher the host's load."""
        from tests.loadwait import scale

        deadline = time.time() + timeout_s * scale()
        last, same = None, 0
        while time.time() < deadline:
            sig = self._activity()
            quiet = all(queued == 0 for _moved, queued in sig)
            t0 = time.perf_counter()
            time.sleep(0.005)
            on_time = time.perf_counter() - t0 < 0.025
            same = same + 1 if (sig == last and quiet and on_time) else 0
            if same >= 20 * scale():
                return
            last = sig
        raise AssertionError(f"world did not settle: {self._activity()}")

    def tick(self, n: int = 1, hosts=None) -> None:
        """What the wall tick worker does for device-ticked groups: the
        host's tick count and one coordinator tick (no per-node wake-up).
        One host at a time, each settled before the next: a follower's
        device row that takes its tick and its leader's heartbeat in one
        round ends a tick later than one that takes them in two rounds
        (the kernel resets on contact, then ticks), and with the hosts
        ticking side by side the threads decide which; host by host every
        heartbeat lands in a round of its own, in both worlds."""
        for _ in range(n):
            for nh in (hosts or self.nhs):
                nh.tick_count += 1
                nh.quorum_coordinator.request_tick()
                self.settle()

    def elect(self) -> None:
        """One campaign a group, on host ``cid % 3``: every group ends at
        the same term in every world, however the threads fell.  A
        campaign asked for before the bootstrap entry was applied is
        skipped (no term spent) and asked for again; one under way is
        waited for."""
        self.settle()
        term0 = {c: self.nhs[c % 3].get_node(c).peer.raft.term
                 for c in self.cids}
        pending = set(self.cids)
        deadline = time.time() + 120.0
        while pending and time.time() < deadline:
            for cid in sorted(pending):
                node = self.nhs[cid % 3].get_node(cid)
                r = node.peer.raft
                if r.is_leader():
                    pending.discard(cid)
                elif r.term == term0[cid] and not r.is_candidate():
                    node.request_campaign()
            self.settle()
        assert not pending, sorted(pending)[:8]
        self.tick(3)
        for cid in self.cids:
            want = cid % 3 + 1
            for nh in self.nhs:
                r = nh.get_node(cid).peer.raft
                assert r.leader_id == want, (cid, r.node_id, r.leader_id)

    # ---- what a run leaves behind -----------------------------------

    def counts(self):
        """(message events, step-worker turns) of the whole world so far:
        messages handed to and taken from the transports (the hosts may
        share one metrics registry: each is read once), groups stepped."""
        regs = {id(nh.transport.metrics.registry): nh.transport.metrics
                for nh in self.nhs}
        events = sum(m.value(SENT) + m.value(RECEIVED) for m in regs.values())
        turns = sum(w["groups_stepped"] for nh in self.nhs
                    for w in nh.engine.stats()["step_workers"])
        return events, turns

    def state(self):
        """Per replica: scalar election clock, term, state, leader, commit
        index, the remotes' check-quorum contacts; per device row: the
        election clock and the activity bits."""
        out = {}
        for h, nh in enumerate(self.nhs, 1):
            c = nh.quorum_coordinator
            with c._mu:
                c.eng._upload_dirty()
                etick = np.asarray(c.eng.dev.election_tick)
                active = np.asarray(c.eng.dev.active)
                rows = {cid: gi.row for cid, gi in c.eng.groups.items()}
            for cid in self.cids:
                r = nh.get_node(cid).peer.raft
                out[(cid, h)] = (
                    r.state.name, r.term, r.leader_id, r.election_tick,
                    r.log.committed, r.log.last_index(),
                    tuple(sorted((nid, rp.active, rp.match)
                                 for nid, rp in r.remotes.items())),
                    int(etick[rows[cid]]),
                    tuple(active[rows[cid]].tolist()),
                    lease_state(r),
                )
        return out

    def take_wire(self):
        got, self.wire = sorted(self.wire), []
        return got

    def stop(self) -> None:
        for nh in self.nhs:
            nh.stop()


# ------------------------------------------------------------------ (i)


def test_steady_tick_costs_a_host_a_bounded_number_of_events():
    """128 groups x 3, led and idle: one tick costs each host at most one
    block and one response block to and from each peer host (8 message
    events) and no step-worker turn.  Spent a group at a time (the parent)
    a tick costs a host some 340 message events and 210 turns."""
    w = World("i")
    try:
        w.elect()
        w.tick(2)
        before = w.counts()
        ticks = 5
        w.tick(ticks)
        after = w.counts()
        hosts = len(w.nhs)
        events = (after[0] - before[0]) / ticks / hosts
        turns = (after[1] - before[1]) / ticks / hosts
        # hosts, not groups: 2 peers x (block out, block in, response out,
        # response in)
        assert events <= 8, (events, turns)
        assert turns <= 2, (events, turns)
        for c in w.coords:
            assert c.hb_block_rows > 0
            assert sum(c.hb_single_causes.values()) < GROUPS
    finally:
        w.stop()


# ----------------------------------------------------------------- (ii)


def _script(w: World):
    """The seeded sequence, the same calls in both worlds; yields after
    each step so the caller can compare what it left behind."""
    rng = np.random.default_rng(29)
    w.elect()
    yield "elected"
    w.tick(3)
    yield "steady"
    # writes on a third of the groups, all acknowledged
    some = [int(c) for c in rng.choice(w.cids, GROUPS // 3, replace=False)]
    futs = []
    for cid in some:
        nh = w.nhs[cid % 3]
        futs.append(nh.propose(
            nh.get_noop_session(cid), b"w%d" % cid, FOREVER_S))
    for f in futs:
        assert f.wait(60.0).completed
    w.settle()
    w.tick(2)
    yield "written"
    # a lagging follower: what host 1 sends host 3 is lost while host 1's
    # groups take a write; once it arrives again, host 3's heartbeat
    # responses find match < last_index
    w.router.set_drop_hook(w.drops_1_to_3)
    led_by_1 = [c for c in w.cids if c % 3 == 0][:12]
    futs = []
    for cid in led_by_1:
        nh = w.nhs[0]
        futs.append(nh.propose(nh.get_noop_session(cid), b"lag", FOREVER_S))
    for f in futs:
        assert f.wait(60.0).completed
    w.settle()
    w.tick(1)
    yield "partitioned"
    w.router.set_drop_hook(None)
    w.tick(3)
    yield "healed"
    # a pending ReadIndex context rides the heartbeat as its hint: a
    # confirmed one is released in the turn the fan-out wakes (ISSUE 40),
    # so a context is still pending when its leader's heartbeat falls due
    # only while no echo comes back
    w.router.set_drop_hook(lambda batch: True)
    reads = [w.nhs[cid % 3].read_index(cid, FOREVER_S) for cid in some[:10]]
    w.settle()
    w.tick(1)
    w.router.set_drop_hook(None)
    w.tick(2)
    for f in reads:
        assert f.wait(60.0).completed
    yield "read"
    # reads at the leaders of the lease groups: a valid lease answers in
    # the step that takes the read, with no message at all; the next
    # tick's heartbeats find no context pending
    lease_reads = [w.nhs[cid % 3].read_index(cid, FOREVER_S)
                   for cid in w.cids if leased(cid)]
    for f in lease_reads:
        assert f.wait(60.0).completed
    w.settle()
    w.tick(1)
    yield "lease_read"
    # a term change mid-block: host 1 hands a dozen groups to host 2, and
    # a block it stamped BEFORE (old term, old leader) reaches host 3
    # after, next to rows that are sound and rows for groups that host 3
    # has never heard this leader of
    moved = [c for c in w.cids if c % 3 == 0][12:24]
    stale = {c: w.nhs[0].get_node(c).peer.raft.term for c in moved}
    for cid in moved:
        w.nhs[0].request_leader_transfer(cid, 2)
    w.settle()
    w.tick(2)
    for cid in moved:
        assert w.nhs[2].get_node(cid).peer.raft.leader_id == 2, cid
    yield "transferred"
    rows = [(cid, 3, 1, stale[cid], 0) for cid in moved]
    for cid in [c for c in w.cids if c % 3 == 0][24:30]:
        r = w.nhs[0].get_node(cid).peer.raft
        rows.append((cid, 3, 1, r.term,
                     min(r.remotes[3].match, r.log.committed)))
    for cid in [c for c in w.cids if c % 3 == 1][:6]:  # led by host 2
        r = w.nhs[1].get_node(cid).peer.raft
        rows.append((cid, 3, 1, r.term, 0))  # same term, not the leader
    w.inject_heartbeats(3, rows)
    w.settle()
    yield "stale_block"
    w.tick(2)
    yield "term_changed"
    w.tick(12)  # through a check-quorum window
    yield "windowed"


def test_block_path_leaves_what_the_per_group_path_leaves():
    block, single = World("b", block=True), World("s", block=False)
    try:
        for step_b, step_s in zip(_script(block), _script(single)):
            assert step_b == step_s
            wire_b, wire_s = block.take_wire(), single.take_wire()
            assert wire_b == wire_s, (
                step_b, len(wire_b), len(wire_s),
                sorted(set(wire_b) ^ set(wire_s))[:6])
            state_b, state_s = block.state(), single.state()
            diff = {k: [(f, b, s) for f, b, s in
                        zip(STATE_FIELDS, state_b[k], state_s[k]) if b != s]
                    for k in state_b if state_b[k] != state_s[k]}
            assert not diff, (step_b, len(diff), list(diff.items())[:4])
        # the block world did go by the block, the oracle never
        assert all(c.hb_block_rows > 0 for c in block.coords)
        assert all(c.hb_block_rows == 0 for c in single.coords)
        causes = {k: sum(c.hb_single_causes[k] for c in block.coords)
                  for k in block.coords[0].hb_single_causes}
        assert causes["lagging"] > 0 and causes["read_ctx"] > 0
        assert causes["term"] + causes["state"] + causes["unknown_leader"] > 0
        # a lease keeps no group off the block plane, and the lease the
        # block legs fed answered reads in both worlds
        assert causes["membership"] == 0
        for w in (block, single):
            served = [w.nhs[c % 3].get_node(c).peer.raft.lease.reads_local
                      for c in w.cids if leased(c)]
            assert min(served) > 0, served
    finally:
        block.stop()
        single.stop()


# ---------------------------------------------------------------- (iii)


def test_tick_deficit_is_counted_and_a_stalled_host_holds_its_elections():
    w = World("d", groups=32)
    try:
        w.elect()
        w.tick(2)
        terms = {cid: w.nhs[0].get_node(cid).peer.raft.term for cid in w.cids}
        h3 = w.nhs[2]
        c3 = h3.quorum_coordinator
        # the cap is the largest warmed K: wait for the background
        # warm-up, or it moves under the script
        _wait(lambda: c3.eng.fused_ready, 600.0, "fused warm-up on host 3")
        cap = c3.fused_k_max
        r0, d0, held0 = c3.ticks_replayed, c3.ticks_dropped, c3.elections_held
        # a short stall: 3 ticks late, all replayed, nothing dropped
        h3.tick_count += 3
        c3._tick_seq += 3
        c3._pending.set()
        w.settle()
        assert c3.ticks_replayed - r0 == 2 and c3.ticks_dropped == d0
        # host 3 away for 40 ticks (two election timeouts and more) while
        # its peers ran on: it replays what it has programs for, drops
        # the rest, and deposes nobody
        for nh in w.nhs[:2]:
            for _ in range(2):
                nh.tick_count += 1
                nh.quorum_coordinator.request_tick()
        w.settle()
        r1 = c3.ticks_replayed
        h3.tick_count += 40
        c3._tick_seq += 40
        c3._pending.set()
        w.settle()
        assert c3.ticks_replayed - r1 == cap - 1
        assert c3.ticks_dropped - d0 == 40 - cap
        w.tick(6)
        for cid in w.cids:
            for nh in w.nhs:
                r = nh.get_node(cid).peer.raft
                assert r.term == terms[cid], (cid, r.node_id, r.term)
                assert r.leader_id == cid % 3 + 1
        if cap >= 10:
            assert c3.elections_held > held0
    finally:
        w.stop()



# ----------------------------------------------------------------- (iv)


@pytest.mark.parametrize("block", [True, False], ids=["block", "per-group"])
def test_a_lost_replicate_is_found_at_the_next_heartbeat(block):
    """A REPLICATE lost on its way to a streaming remote (REPLICATE state,
    ``next`` run ahead) is found by the response to the next heartbeat:
    ``match < last_index`` hands the row to the scalar handler, whose
    probe draws the rejection that rewinds the remote.  Within two ticks
    the follower holds the log again, by the block as by the per-group
    message (upstream's rule; nothing thins the probes)."""
    from dragonboat_tpu.raft.remote import RemoteState

    w = World("l" + "bs"[not block], groups=12, block=block)
    try:
        w.elect()
        w.tick(2)
        led = [c for c in w.cids if c % 3 == 0]  # led by host 1
        h1 = w.nhs[0]

        def write(tag):
            futs = [h1.propose(h1.get_noop_session(c), tag, FOREVER_S)
                    for c in led]
            for f in futs:
                assert f.wait(60.0).completed
            w.settle()

        write(b"first")
        w.tick(1)
        for cid in led:
            r = h1.get_node(cid).peer.raft
            assert r.remotes[3].state == RemoteState.REPLICATE
            assert r.remotes[3].match == r.log.last_index()

        def loses_replicates_to_3(batch):
            return batch.source_address == w.addrs[1] and any(
                m.type == MT.REPLICATE and m.to == 3 for m in batch.requests)

        w.router.set_drop_hook(loses_replicates_to_3)
        write(b"lost")  # committed through host 2
        w.router.set_drop_hook(None)
        behind = 0
        for cid in led:
            r = h1.get_node(cid).peer.raft
            f = w.nhs[2].get_node(cid).peer.raft
            behind += f.log.last_index() < r.log.last_index()
            assert r.remotes[3].match < r.log.last_index()
        assert behind == len(led)
        w.tick(2)
        for cid in led:
            r = h1.get_node(cid).peer.raft
            f = w.nhs[2].get_node(cid).peer.raft
            assert f.log.last_index() == r.log.last_index(), cid
            assert f.log.committed == r.log.committed, cid
            assert r.remotes[3].match == r.log.last_index(), cid
        if block:
            assert sum(c.hb_single_causes["lagging"] for c in w.coords) > 0
    finally:
        w.stop()


@pytest.mark.parametrize("leader", [True, False], ids=["leader", "follower"])
@pytest.mark.parametrize("start", [0, 3, 9])
@pytest.mark.parametrize("n", [1, 7, 10, 23])
def test_tick_quiet_counts_what_n_ticks_count(leader, start, n):
    """``Raft.tick_quiet(n)`` against ``n`` calls of ``tick()`` on a
    device-ticked raft (no fire site of its own): the same clocks, the
    check-quorum window's wrap included."""
    from raft_harness import Network, campaign

    def make():
        net = Network(None, None, None)
        net.send(campaign(net.raft(1)))
        r = net.raft(1 if leader else 2)
        assert r.is_leader() == leader
        r.device_ticks = True
        r.msgs.clear()
        r.election_tick = start
        return r

    a, b = make(), make()
    assert a.tick_quiet(n)
    for _ in range(n):
        b.tick()
    assert not a.msgs and not b.msgs
    assert a.is_leader() == b.is_leader() == leader
    assert (a.election_tick, a.heartbeat_tick, a.tick_count) == (
        b.election_tick, b.heartbeat_tick, b.tick_count)
