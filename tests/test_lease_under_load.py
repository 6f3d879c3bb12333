"""The lease-read deployment (``upstream48x3lease``, ISSUE 41) on the CPU at
a small size: ``check_quorum`` and ``read_lease`` on, every read submitted at
the host that leads its group.

Four groups on three chan-transport NodeHosts (the benchmark's own
``LiveCluster``: ``quorum_engine="tpu"``, durable directories, every request
sampled) take a few seconds of the cell's own traffic through the
benchmark's ``run``.  One run of that story is shared by the cases below, so
that each assertion counts as a case:

* the replicas and every read hold what the plain reference holds (the six
  comparisons of ``correct``, ``wrong_reads`` among them), and every leader
  answered reads under its lease;
* the lease groups' heartbeats went by the block, and the lease those block
  legs fed is what answered;
* a sampled leased read left its one ``read_ctx`` span (``path: lease``,
  the ticks of validity left, none of the chain's legs);
* a leader cut off from both followers stops answering under its lease
  within the lease's duration, the reads that follow fall back and are not
  answered, the majority elects and writes meanwhile, and no read the
  cut-off leader did answer was stale;
* the same cut with leases that never end (the control: a duration of a
  million ticks, no wall guard, no check of the quorum) is caught by the
  reference's ``wrong_reads``: the check has teeth where the benchmark's
  ``program:stale_read`` control has none (a read at its own leader is never
  stale while no leader changes);
* the wall guard is what ``start_cluster`` gave the group (no knob is set
  anywhere here): a clock that runs on while the ticks stand still expires
  the lease, and a fresh quorum of acks re-arms it.

The benchmark can cut no link and hold back no clock inside a window, so
the restated ``guarantees.read`` is held here as far as a fault shows it.
"""
from __future__ import annotations

import threading
import time

import pytest

jax = pytest.importorskip("jax")

from benchmark import run as harness  # noqa: E402
from benchmark.cluster import LiveCluster  # noqa: E402
from benchmark.reference import kv as reference  # noqa: E402

pytestmark = pytest.mark.xdist_group("heavy-multiprocess")

GROUPS = 4
RTT_MS = 20
ELECTION_RTT = 10
DURATION = ELECTION_RTT - ELECTION_RTT // 5  # the lease, in ticks
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
COMPARED = ("lost_acked_writes", "foreign_keys", "divergent_groups",
            "wrong_reads", "bad_apply_seq", "device_commit_out_of_range")


def _wait(pred, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"{what} not reached in {timeout_s}s")


def _cell():
    cell = harness.Cell("upstream48x3lease.lease91")
    cell.config = dict(cell.config, groups=GROUPS)
    cell.config["assumed"] = dict(
        cell.config["assumed"], rtt_millisecond=RTT_MS,
        engine_block_groups=8)
    cell.traffic = dict(cell.traffic, rate_ops_per_s=150.0, warmup_s=1.0)
    return cell


def _lease(cluster, host, cid):
    return cluster.nhs[host].get_node(cid).peer.raft.lease


def _held(cluster, host, cids):
    return all((cluster.nhs[host].lease_status(c) or {}).get("held")
               for c in cids)


def _settled_leaders(cluster, quiet_s=0.6):
    """After a heal: every host names the same leader for every group,
    and has for ``quiet_s`` (the host that was away comes back with terms
    of its own and may depose a leader or two); the generator's leader
    map then says what the hosts say."""
    state = {"seen": None, "since": time.time()}

    def quiet():
        now = tuple(tuple(nh.get_leader_id(c) for nh in cluster.nhs)
                    for c in cluster.cids)
        agreed = all(len(set(row)) == 1 and row[0][1] for row in now)
        if now != state["seen"] or not agreed:
            state["seen"], state["since"] = now, time.time()
        return agreed and time.time() - state["since"] >= quiet_s

    _wait(quiet, 60.0, "leaders settled")
    for c in cluster.cids:
        cluster.refresh_leader(c)


def _break_leases(cluster, host, led):
    """The control: the leaders on ``host`` hold a lease that never ends
    (a duration of a million ticks, no wall guard) and never check their
    quorum; returns what undoes it."""
    undo = []
    for c in led:
        node = cluster.nhs[host].get_node(c)
        with node.raft_mu:
            r = node.peer.raft
            undo.append((node, r, r.lease.duration, r.lease.tick_interval_s))
            r.lease.duration = 10 ** 6
            r.lease.tick_interval_s = None
            r.check_quorum = False

    def restore():
        for node, r, duration, interval in undo:
            with node.raft_mu:
                r.check_quorum = True
                r.lease.duration = duration
                r.lease.tick_interval_s = interval
    return restore


def _cut_off(cluster, facts, name="cut", broken=False):
    """The host that leads most groups loses both links for four election
    timeouts while it is asked for reads and the other two take writes.
    ``broken``: its leases are the control's (``_break_leases``)."""
    _settled_leaders(cluster)
    leads = [cluster.leader_host(c) for c in cluster.cids]
    host = max(set(leads), key=leads.count)
    nh = cluster.nhs[host]
    led = [c for c in cluster.cids if cluster.leader_host(c) == host]
    others = [i for i in range(len(cluster.nhs)) if i != host]
    router = nh.transport.rpc.router
    addr = {i: f"bench{i + 1}:1" for i in range(len(cluster.nhs))}
    key = {c: b"cut%05d" % c for c in led}
    acked, unacked, reads = [], [], []

    def write(at, cid, serial):
        cmd_val = serial.to_bytes(8, "little")
        first = time.perf_counter()
        try:
            h = cluster.nhs[at]
            fut = h.propose(h.get_noop_session(cid), key[cid] + cmd_val, 1.0)
            res = fut.wait(1.5)
        except Exception:
            res = None
        if res is not None and res.completed:
            acked.append((cid, key[cid], cmd_val, res.result.value, first,
                          time.perf_counter()))
            return True
        unacked.append((cid, key[cid], cmd_val, first))
        return False

    for c in led:  # the value the cut-off leader will be asked for
        assert write(host, c, 1)
    _wait(lambda: _held(cluster, host, led), 30.0, "leases held")
    local0 = {c: _lease(cluster, host, c).reads_local for c in led}
    fallback0 = {c: _lease(cluster, host, c).reads_fallback for c in led}
    restore = _break_leases(cluster, host, led) if broken else None
    for i in others:
        router.partition(addr[host], addr[i])
    tick0 = nh.tick_count
    t_cut = time.perf_counter()
    stop = threading.Event()
    elected = {}

    def majority():
        serial = 1
        while not stop.is_set():
            for c in led:
                lid, ok = cluster.nhs[others[0]].get_leader_id(c)
                if ok and lid - 1 in others:
                    elected.setdefault(c, nh.tick_count - tick0)
                    serial += 1
                    write(lid - 1, c, serial)
            time.sleep(0.01)

    writer = threading.Thread(target=majority, name="lease-majority")
    writer.start()
    served_at, flying = [], []  # ticks after the cut of each answered read
    try:
        # four election timeouts, and on until the majority has written
        # (six at most): a follower campaigns one to two timeouts after
        # its last contact
        while nh.tick_count < tick0 + 4 * ELECTION_RTT or (
                nh.tick_count < tick0 + 6 * ELECTION_RTT
                and not any(int.from_bytes(w[2], "little") > 1
                            for w in acked)):
            for c in led:
                try:
                    flying.append(
                        (c, time.perf_counter(), nh.read_index(c, 0.3)))
                except Exception:
                    pass  # busy, or no longer a leader: not answered
            still = []
            for c, submitted, fut in flying:
                if not fut.done():
                    still.append((c, submitted, fut))
                elif fut.result.completed:
                    got = cluster.lookup(host, c, key[c])
                    reads.append((c, key[c], got, host, submitted,
                                  time.perf_counter()))
                    # in tick periods after the cut, by the instant the
                    # read completed (the poll may see it later)
                    served_at.append(
                        (fut.completed_at - t_cut) * 1e3 / RTT_MS)
            flying = still
            time.sleep(0.003)
    finally:
        stop.set()
        writer.join(10.0)
        router.heal()
        if restore is not None:
            restore()
    facts[name] = {
        "served_at": served_at,
        "answered": sum(_lease(cluster, host, c).reads_local - local0[c]
                        for c in led),
        "fell_back": sum(_lease(cluster, host, c).reads_fallback
                         - fallback0[c] for c in led),
        "elected_at": dict(elected), "led": led,
        "majority_writes": sum(
            1 for w in acked if int.from_bytes(w[2], "little") > 1),
    }
    # healed: the old leader's host reads the newest value again
    for c in led:
        cluster.refresh_leader(c)

        def reads_newest(c=c):
            cluster.refresh_leader(c)
            host = cluster.leader_host(c)
            submitted = time.perf_counter()
            try:
                res = cluster.nhs[host].read_index(c, 1.0).wait(1.5)
            except Exception:
                return False
            if res is None or not res.completed:
                return False
            reads.append((c, key[c], cluster.lookup(host, c, key[c]), host,
                          submitted, time.perf_counter()))
            return True

        _wait(reads_newest, 30.0, f"group {c} answering after the heal")
    facts[name]["wrong_reads"] = reference.wrong_reads(acked, unacked, reads)
    facts[name]["reads"] = len(reads)


def _wall_guard(cluster, facts):
    """A leader's lease under an injected wall clock, ticks standing
    still (the group's lock is held: nothing steps, no ack lands)."""
    cid = cluster.cids[0]
    _settled_leaders(cluster)
    _wait(lambda: cluster.refresh_leader(cid) or _held(
        cluster, cluster.leader_host(cid), [cid]), 30.0, "a lease held")
    host = cluster.leader_host(cid)
    node = cluster.nhs[host].get_node(cid)
    lease = node.peer.raft.lease
    out = facts["wall"] = {"tick_interval_s": lease.tick_interval_s}
    ahead = [0.0]
    real = lease.wall_clock
    with node.raft_mu:
        r = node.peer.raft

        def left():
            return lease.remaining(r.tick_count, r.quorum(),
                                   r.voting_members(), r.node_id)

        lease.wall_clock = lambda: real() + ahead[0]
        try:
            out["before"] = left()
            ahead[0] = DURATION * RTT_MS / 2000.0
            out["just_inside"] = left()
            ahead[0] = DURATION * RTT_MS / 1000.0 + 0.05
            out["held_back"] = left()
        finally:
            lease.wall_clock = real
    _wait(lambda: _held(cluster, host, [cid]), 30.0, "the lease re-armed")
    local = lease.reads_local
    res = cluster.nhs[host].read_index(cid, 2.0).wait(3.0)
    out["answered_after"] = bool(res is not None and res.completed)
    out["leased_after"] = lease.reads_local - local


@pytest.fixture(scope="module")
def story():
    from dragonboat_tpu.obs import default_recorder

    cell = _cell()
    cluster = LiveCluster(cell.config, "", trace_sample_every=1)
    facts = {}
    try:
        seq0 = default_recorder().to_json(limit=1)["count"]
        facts["result"] = harness.run(
            cell, cluster, 41, 4.0, False, DEVICE, True,
            setup_clock=lambda: 0.0)
        facts["leases"] = {
            cid: cluster.nhs[cluster.leader_host(cid)].lease_status(cid)
            for cid in cluster.cids}
        facts["coords"] = [
            (c.hb_block_rows, dict(c.hb_single_causes))
            for c in cluster.coords]
        spans = default_recorder().spans()
        facts["spans"] = [
            s for s in spans[-(default_recorder().to_json(limit=1)["count"]
                               - seq0):]
            if s is not None and s["kind"] == "read_ctx"]
        facts["traces"] = [
            t for nh in cluster.nhs for t in nh.tracer.finished()
            if t.kind == "read" and t.done and t.outcome == "completed"]
        _cut_off(cluster, facts)
        _wall_guard(cluster, facts)
        # the control the benchmark cannot run (a read at its own leader
        # is never stale while no leader changes): the same story with
        # leases that never end, on a host that still leads
        _cut_off(cluster, facts, "broken", True)
        yield facts
    finally:
        cluster.stop()


@pytest.mark.parametrize("name", COMPARED)
def test_the_run_holds_the_plain_reference(story, name):
    result = story["result"]
    assert result["compared"][name] == {"value": 0, "limit": 0}
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 500


@pytest.mark.parametrize("cid", range(1, GROUPS + 1))
def test_every_leader_answered_under_its_lease(story, cid):
    status = story["leases"][cid]
    assert status is not None and status["reads_local"] > 0, status
    # the reads of a group (a quarter of ~540) but for the first before a
    # quorum of acks: the lease path is the cell's path
    assert status["reads_local"] >= 10 * status["reads_fallback"], status


def test_lease_groups_heartbeat_by_the_block(story):
    for rows, causes in story["coords"]:
        assert rows > 0
        assert causes["membership"] == 0, causes


@pytest.mark.parametrize("what", ["path", "remaining_ticks", "no_chain",
                                  "joined"])
def test_a_leased_read_leaves_its_span(story, what):
    leased = [s for s in story["spans"] if s.get("path") == "lease"]
    assert len(leased) >= 100
    if what == "path":
        assert len(leased) >= 0.9 * len(story["spans"])
        assert {s["origin"] for s in leased} == {"local"}
        rest = [s for s in story["spans"] if s.get("path") != "lease"]
        assert all(s.get("lease_fallback") for s in rest), rest[:2]
    elif what == "remaining_ticks":
        assert all(1 <= s["remaining_ticks"] <= DURATION for s in leased)
    elif what == "no_chain":
        for s in leased:
            assert s["leader_ms"] >= 0 and s["t1"] >= s["t0"]
            assert not {"echo_trip_ms", "echo_wait_ms", "confirm_ms",
                        "release_ms", "rounds"} & set(s)
    else:
        # every finished sampled read names a context whose span exists
        by_ctx = {(s["cluster_id"], s["low"], s["high"])
                  for s in story["spans"]}
        reads = [t for t in story["traces"] if t.read_ctx]
        assert len(reads) >= 100
        missing = [t for t in reads
                   if (t.cluster_id,) + tuple(t.read_ctx) not in by_ctx]
        assert not missing, len(missing)
        assert any(stage == "lease_read" for t in reads
                   for stage, _ts, _th in t.events)


@pytest.mark.parametrize("what", ["stops_in_time", "falls_back",
                                  "majority_moves_on", "no_stale_read"])
def test_a_cut_off_leader_stops_answering_under_its_lease(story, what):
    cut = story["cut"]
    if what == "stops_in_time":
        # the newest quorum ack answered a heartbeat sent at or before the
        # cut, so the lease ends DURATION tick periods later, by the ticks
        # and by the wall guard (two more for the ack's own age and for the
        # answer's way from the step that checked to the caller)
        assert cut["answered"] > 0 and cut["served_at"], cut
        assert max(cut["served_at"]) <= DURATION + 2, cut["served_at"][-5:]
    elif what == "falls_back":
        # asked on for four election timeouts, answered only while the
        # lease held: the rest found it not valid and took the ReadIndex
        # path, which no quorum confirms
        # (one context may cover several requests of a step's batch)
        assert cut["fell_back"] > 0, cut
        assert 0 < cut["answered"] <= len(cut["served_at"]), cut
    elif what == "majority_moves_on":
        assert set(cut["elected_at"]) == set(cut["led"]), cut
        # no follower campaigns inside the election timeout of a contact
        assert min(cut["elected_at"].values()) >= DURATION, cut
        assert cut["majority_writes"] > 0, cut
    else:
        assert cut["reads"] > len(cut["led"])
        assert cut["wrong_reads"] == 0, cut


def test_the_same_story_with_a_broken_lease_is_caught(story):
    """What ``benchmark/control.py``'s ``program:stale_read`` cannot show
    under this cell's traffic (it reads the leader's own state machine,
    which is never behind what that leader acknowledged): a cut-off
    leader whose lease never ends answers on after the majority has
    moved, and the reference's check counts it."""
    broken = story["broken"]
    assert broken["majority_writes"] > 0, broken
    assert max(broken["served_at"]) > 2 * ELECTION_RTT, broken["served_at"][-5:]
    assert broken["wrong_reads"] > 0, broken


@pytest.mark.parametrize("what", ["set_by_start_cluster", "expires",
                                  "rearms"])
def test_the_wall_guard_is_what_a_lease_group_has(story, what):
    wall = story["wall"]
    if what == "set_by_start_cluster":
        assert wall["tick_interval_s"] == RTT_MS / 1000.0
    elif what == "expires":
        assert wall["before"] > 0 and wall["just_inside"] > 0, wall
        assert wall["held_back"] == 0, wall
    else:
        assert wall["answered_after"] and wall["leased_after"] == 1, wall
