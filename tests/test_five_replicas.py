"""The five-replica deployment (``ladder512x5``, ISSUE 34) on the CPU at a
small size: a majority of three of five on the normal path.

* the device engine against the scalar ``raft.py`` leader: for seeded ack
  orders over five peers with every subset of one, two and three followers
  silent, the commit index the engine holds equals the scalar leader's
  after every step, and a ReadIndex context confirms on the device exactly
  when the scalar ``ReadIndex.confirm`` releases it: with one or two silent
  commits and confirmations go on, with three nothing commits and no read
  confirms;
* the system against the plain reference in its place: five NodeHosts
  (``quorum_engine="tpu"``) under the cell's own traffic, reads submitted
  at each of the five hosts, all six comparisons 0; each control of
  ``correct`` fails on every seed alone;
* live through NodeHost: with two of five hosts stopped writes and
  ReadIndex reads complete at the three left; with three stopped none is
  acknowledged or answered before its timeout; restarted, all five
  replicas converge to one hash.  The benchmark can stop no host, so the
  deployment's reason for being is held here.
"""
from __future__ import annotations

import itertools
import random
import shutil
import tempfile
import time

import pytest

jax = pytest.importorskip("jax")

from benchmark import control, run as harness  # noqa: E402
from benchmark.cluster import KV, LiveCluster  # noqa: E402
from dragonboat_tpu.ops import BatchedQuorumEngine  # noqa: E402
from dragonboat_tpu.raft.readindex import ReadIndex  # noqa: E402
from dragonboat_tpu.wire import Entry, Message, MessageType, SystemCtx  # noqa: E402
from raft_harness import new_test_raft  # noqa: E402

MT = MessageType
PEERS = [1, 2, 3, 4, 5]
FOLLOWERS = PEERS[1:]
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
SILENT = [s for n in (1, 2, 3) for s in itertools.combinations(FOLLOWERS, n)]


# ----------------------------------------------------------------------
# the majority of five against the scalar path
# ----------------------------------------------------------------------


def _scalar_leader():
    r = new_test_raft(1, PEERS)
    r.handle(Message(from_=1, to=1, type=MT.ELECTION))
    for p in FOLLOWERS:
        r.handle(Message(from_=p, to=1, term=r.term,
                         type=MT.REQUEST_VOTE_RESP))
        if r.is_leader():
            break
    assert r.is_leader() and r.quorum() == 3
    return r


@pytest.mark.parametrize(
    "silent", SILENT, ids=["-".join(map(str, s)) for s in SILENT])
def test_device_commit_index_equals_the_scalar_leaders(silent):
    r = _scalar_leader()
    eng = BatchedQuorumEngine(n_groups=4, n_peers=8)  # the live width
    eng.add_group(1, node_ids=PEERS, self_id=1)
    eng.set_leader(1, term=r.term, term_start=r.log.last_index(),
                   last_index=r.log.last_index())
    rng = random.Random(int("".join(map(str, silent))))
    live = [p for p in FOLLOWERS if p not in silent]
    orc = ReadIndex()
    confirmed = released = 0
    for step in range(24):
        for _ in range(rng.randrange(0, 3)):
            r.handle(Message(from_=1, to=1, type=MT.PROPOSE,
                             entries=[Entry(cmd=b"x")]))
            eng.ack(1, 1, r.log.last_index())
        last = r.log.last_index()
        # seeded ack order: stale, repeated and out-of-order indices
        for _ in range(rng.randrange(0, 7)):
            p = rng.choice(live)
            idx = rng.randrange(0, last + 1)
            r.handle(Message(from_=p, to=1, term=r.term,
                             type=MT.REPLICATE_RESP, log_index=idx))
            eng.ack(1, p, idx)
        # one ReadIndex context a step, echoed by a seeded subset
        ctx = SystemCtx(low=step + 1, high=0)
        orc.add_request(r.log.committed, ctx, 0)
        slot = eng.stage_read(1, count=1, index=r.log.committed)
        for p in rng.sample(live, rng.randrange(0, len(live) + 1)):
            released += len(orc.confirm(ctx, p, r.quorum()))
            eng.read_ack(1, p, slot)
        res = eng.step(do_tick=False)
        assert eng.committed_index(1) == r.log.committed, step
        if 1 in res.commit:
            assert res.commit[1] == r.log.committed
        confirmed += sum(n for _c, _s, _i, n in res.reads)
        if ctx in orc.pending:  # not released: free the slot, as a purge
            eng.cancel_read(1, slot)
            orc = ReadIndex()
        assert confirmed == released, step
    # every live follower catches up: with a majority left all commits
    for p in live:
        r.handle(Message(from_=p, to=1, term=r.term, type=MT.REPLICATE_RESP,
                         log_index=r.log.last_index()))
        eng.ack(1, p, r.log.last_index())
    eng.step(do_tick=False)
    assert eng.committed_index(1) == r.log.committed
    if len(silent) <= 2:
        assert r.log.committed == r.log.last_index() > 0
        assert confirmed > 0
    else:  # one follower and the leader are no majority of five
        assert r.log.committed == 0 and r.log.last_index() > 0
        assert confirmed == released == 0


# ----------------------------------------------------------------------
# one confirmation releases several followers' contexts: each hears of its
# own
# ----------------------------------------------------------------------


def _committed_leader():
    """A scalar leader of five with its term's no-op committed (a leader
    serves no ReadIndex before that)."""
    r = _scalar_leader()
    for p in (2, 3):
        r.handle(Message(from_=p, to=1, term=r.term, type=MT.REPLICATE_RESP,
                         log_index=r.log.last_index()))
    assert r.has_committed_entry_at_current_term()
    r.msgs.clear()
    return r


@pytest.mark.parametrize("release", ["scalar_confirm", "device_egress"])
def test_one_confirmation_answers_each_forwarder_with_its_own_context(
        release):
    """Followers 2 and 3 each forward a ReadIndex of their own clients and
    the leader's own client reads too; the confirmation of the NEWEST
    context releases all three (``ReadIndex.release``'s prefix pop).  Each
    follower's response carries the context it asked with (a departure
    from upstream, which echoes the confirming one), so both requesters
    complete; through the scalar confirm and through the device plane's
    confirmed egress alike."""
    r = _committed_leader()
    asked = {2: SystemCtx(low=21, high=2), 3: SystemCtx(low=31, high=3),
             1: SystemCtx(low=11, high=1)}
    for origin, ctx in asked.items():
        r.handle(Message(from_=origin, to=1, term=r.term, type=MT.READ_INDEX,
                         hint=ctx.low, hint_high=ctx.high))
    assert list(r.read_index.queue) == list(asked.values())
    r.msgs.clear()
    newest = asked[1]
    if release == "scalar_confirm":  # two echoes of four: three of five
        for p in (4, 5):
            assert not r.ready_to_read
            r.handle(Message(from_=p, to=1, term=r.term,
                             type=MT.HEARTBEAT_RESP, hint=newest.low,
                             hint_high=newest.high))
    else:  # what node._apply_offload_effects does with a confirmed slot
        r.apply_read_releases(r.read_index.release(newest))
    assert not r.read_index.pending
    assert [(x.system_ctx, x.index) for x in r.ready_to_read] == [
        (newest, r.log.committed)]
    resps = {m.to: m for m in r.msgs if m.type == MT.READ_INDEX_RESP}
    assert set(resps) == {2, 3}
    for origin, m in resps.items():
        assert SystemCtx(low=m.hint, high=m.hint_high) == asked[origin]
        assert m.log_index == r.log.committed
        # the requester completes: its raft hands its own context on
        f = new_test_raft(origin, PEERS)
        f.become_follower(r.term, 1)
        f.handle(Message(from_=1, to=origin, term=r.term,
                         type=MT.READ_INDEX_RESP, log_index=m.log_index,
                         hint=m.hint, hint_high=m.hint_high))
        assert [(x.system_ctx, x.index) for x in f.ready_to_read] == [
            (asked[origin], r.log.committed)]


# ----------------------------------------------------------------------
# the system against the plain reference in its place
# ----------------------------------------------------------------------


def _cell(groups=8, rate=120.0):
    cell = harness.Cell("ladder512x5.mixed91")
    cell.config = dict(cell.config, groups=groups)
    cell.config["assumed"] = dict(cell.config["assumed"],
                                  engine_block_groups=groups)
    cell.traffic = dict(cell.traffic, rate_ops_per_s=rate, warmup_s=0.5)
    return cell


@pytest.mark.xdist_group("heavy-multiprocess")
def test_five_hosts_hold_the_reference_under_the_cells_traffic():
    cell = _cell()
    cluster = LiveCluster(cell.config, "")
    read_hosts = set()
    submit = cluster.submit_read

    def counted(host, cid, timeout_s):
        read_hosts.add(host)
        return submit(host, cid, timeout_s)

    cluster.submit_read = counted
    try:
        assert len(cluster.nhs) == 5
        assert {c.eng.n_peers for c in cluster.coords} == {8}
        result = harness.run(cell, cluster, 34, 4.0, False, DEVICE, True,
                             setup_clock=lambda: 0.0)
        # the widest majority the kernel computes, off the masks it
        # computes with
        voters = set()
        for c in cluster.coords:
            a = c.eng.mirror.arrays
            voters.add(int((a["voting"].sum(axis=1) * a["live"]).max()))
    finally:
        cluster.stop()
    assert result["correct"] is True, result["compared"]
    assert len(result["compared"]) == 6
    assert all(c["value"] == 0 for c in result["compared"].values())
    assert result["failed"] == 0 and result["attempted"] == 480
    assert read_hosts == {0, 1, 2, 3, 4}
    assert voters == {5}


@pytest.mark.parametrize("seed", [1, 2, 2147483659])
@pytest.mark.parametrize("broken,by", [
    ("ack_before_quorum", "lost_acked_writes"), ("stale_read", "wrong_reads"),
    (None, None)])
def test_each_control_fails_alone_on_every_seed(broken, by, seed):
    """The reference in the program's place at five replicas: sound, every
    comparison is 0; with one guarantee given up (an acknowledgement that a
    replica never got; a read from a replica that lags), ``correct`` is
    false by that comparison."""
    cell = _cell(groups=16, rate=400.0)
    name = "reference" + (":" + broken if broken else "")
    result = harness.run(cell, control.build(name, cell, seed), seed, 2.0,
                         False, DEVICE, True, setup_clock=lambda: 0.0)
    assert result["failed"] == 0
    if broken is None:
        assert result["correct"] is True
        assert all(c["value"] == 0 for c in result["compared"].values())
    else:
        assert result["correct"] is False
        assert result["compared"][by]["value"] > 0
        assert result["compared"]["device_commit_out_of_range"]["value"] == 0


# ----------------------------------------------------------------------
# live: the deployment survives two failures, and not three
# ----------------------------------------------------------------------


class _Hosts:
    """Five chan-transport NodeHosts on durable directories, built as
    ``benchmark/cluster.py`` builds them, that can be stopped and started
    one by one."""

    GROUPS = (1, 2)

    def __init__(self):
        from dragonboat_tpu.transport import ChanRouter

        self.base = tempfile.mkdtemp(prefix="five-nh-")
        self.router = ChanRouter()
        self.addrs = {i: f"five{i}:1" for i in PEERS}
        self.nhs = {}
        self.sms = {}
        for i in PEERS:
            self.start(i)

    def start(self, i):
        from dragonboat_tpu import Config, NodeHostConfig
        from dragonboat_tpu.config import ExpertConfig
        from dragonboat_tpu.nodehost import NodeHost
        from dragonboat_tpu.transport import ChanTransport

        def make_sm(cid, nid):
            sm = self.sms[(cid, nid)] = KV(cid, nid)
            return sm

        nh = self.nhs[i] = NodeHost(NodeHostConfig(
            node_host_dir=f"{self.base}/nh{i}", rtt_millisecond=20,
            raft_address=self.addrs[i],
            raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                src, rh, ch, router=self.router),
            expert=ExpertConfig(quorum_engine="tpu", fast_lane=False,
                                engine_block_groups=8),
        ))
        for cid in self.GROUPS:
            nh.start_cluster(self.addrs, False, make_sm, Config(
                cluster_id=cid, node_id=i, election_rtt=10, heartbeat_rtt=1))
        return nh

    def stop(self, *ids):
        for i in ids:
            self.nhs.pop(i).stop()

    def close(self):
        self.stop(*list(self.nhs))
        shutil.rmtree(self.base, ignore_errors=True)

    def leader(self, cid, timeout_s=30.0):
        """A running host that knows a running leader of ``cid``."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            for nh in self.nhs.values():
                lid, ok = nh.get_leader_id(cid)
                if ok and lid in self.nhs:
                    return lid
            time.sleep(0.05)
        raise AssertionError(f"no leader for group {cid}")

    def put(self, cid, serial, timeout_s=3.0, tries=20):
        """Retry to the then-leader, as the generator does."""
        cmd = cid.to_bytes(8, "little") + serial.to_bytes(8, "little")
        for _ in range(tries):
            nh = self.nhs[self.leader(cid)]
            try:
                fut = nh.propose(nh.get_noop_session(cid), cmd, timeout_s)
                if fut.wait(timeout_s + 1).code.name == "COMPLETED":
                    return True
            except Exception:
                pass
            time.sleep(0.1)
        return False

    def read(self, host, cid, timeout_s=3.0, tries=20):
        for _ in range(tries):
            try:
                fut = self.nhs[host].read_index(cid, timeout_s)
                if fut.wait(timeout_s + 1).code.name == "COMPLETED":
                    return self.nhs[host].get_node(cid).sm.lookup(
                        cid.to_bytes(8, "little"))
            except Exception:
                pass
            time.sleep(0.1)
        return None


@pytest.mark.xdist_group("heavy-multiprocess")
def test_two_hosts_stopped_serves_and_three_stopped_does_not():
    hosts = _Hosts()
    try:
        for cid in hosts.GROUPS:
            assert hosts.put(cid, 1)
        # two of five down: the three left are a majority
        hosts.stop(4, 5)
        for cid in hosts.GROUPS:
            assert hosts.put(cid, 2)
            for host in (1, 2, 3):  # at the leader and, forwarded, beside it
                assert hosts.read(host, cid) == (2).to_bytes(8, "little")
        # three of five down: two are none; nothing is acknowledged and no
        # read is answered before its timeout
        hosts.stop(3)
        for cid in hosts.GROUPS:
            for host in (1, 2):
                nh = hosts.nhs[host]
                w = nh.propose(nh.get_noop_session(cid),
                               cid.to_bytes(8, "little") + b"lost....", 1.0)
                rd = nh.read_index(cid, 1.0)
                assert w.wait(3.0).code.name != "COMPLETED"
                assert rd.wait(3.0).code.name != "COMPLETED"
        # restarted, the five converge (what was never acknowledged may or
        # may not have been applied, but on all five alike)
        for i in (3, 4, 5):
            hosts.start(i)
        for cid in hosts.GROUPS:
            assert hosts.put(cid, 3)
        def hashes(cid):
            return {hosts.nhs[i].get_node(cid).sm.get_hash() for i in PEERS}

        deadline = time.time() + 60
        while time.time() < deadline and any(
                len(hashes(cid)) != 1 for cid in hosts.GROUPS):
            time.sleep(0.1)
        for cid in hosts.GROUPS:
            assert len(hashes(cid)) == 1, (cid, hashes(cid))
            got = [hosts.sms[(cid, i)].kv for i in PEERS]
            assert all(kv == got[0] for kv in got)
            assert got[0][cid.to_bytes(8, "little")] == (3).to_bytes(
                8, "little")
    finally:
        hosts.close()
