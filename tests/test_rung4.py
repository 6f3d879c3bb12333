"""Rung 4 of the config ladder: 64k groups × 5 peer slots at correctness
scale (BASELINE.md; reference scaling claim README.md Performance §).

Round-3 verdict: 64k appeared only in kernel micro-benches; nothing drove
the COORDINATOR at that scale with churn.  This test runs the live
TpuQuorumCoordinator (CPU backend) over 65,536 registered groups:

- sustained bulk load (every group commits every round via the
  vectorized ack_block ingest) with a 9:1 read:write interleave
  (committed_index queries against staged commits);
- a 256-group sampled differential: full scalar Raft oracles driven in
  lockstep, commitIndex asserted bit-identical every round;
- rolling membership churn: row recycling (unregister/re-register
  thousands of groups mid-load) plus add/remove-node membership resyncs
  on sampled oracles;
- leader transfers on sampled groups (step down, re-elect at a higher
  term, commit again).

Marked slow: one full run is a few minutes on the 8-vCPU CI box.
"""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np
import pytest

from dragonboat_tpu.raft import InMemLogDB
from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator
from dragonboat_tpu.wire import Entry, Message, MessageType as MT

from tests.raft_harness import new_test_raft

# heavy multi-NodeHost tests never overlap each other (the lock in
# tests/conftest.py): side by side they starve each other on an 8-vCPU box
pytestmark = [pytest.mark.slow, pytest.mark.xdist_group("heavy-multiprocess")]

N = 65_536
SAMPLE = 256
PEERS = [1, 2, 3, 4, 5]


class FakeNode:
    """Minimal node shim (same contract as test_device_ticks)."""

    def __init__(self, cid, raft):
        self.cluster_id = cid
        self.raft_mu = threading.RLock()

        class _P:
            pass

        self.peer = _P()
        self.peer.raft = raft
        self.commits = []

    def offload_commit(self, q):
        r = self.peer.raft
        with self.raft_mu:
            if r.is_leader() and r.log.try_commit(q, r.term):
                self.commits.append(q)

    def offload_election(self, won, term):
        # twin of Node.offload_election: the device tallies votes, the
        # host applies the outcome under raftMu, term-pinned
        r = self.peer.raft
        with self.raft_mu:
            if r.is_candidate() and r.term == term:
                if won:
                    r.become_leader()
                else:
                    r.become_follower(r.term, 0)

    def offload_tick_elect(self):
        pass

    def offload_tick_heartbeat(self):
        pass

    def offload_tick_demote(self):
        pass


def _assert_parity(eng, oracles, cids, tag, timeout=8.0, mu=None):
    """commitIndex bit-identity with callback-timing tolerance: the
    coordinator's background round thread delivers offload_commit OUTSIDE
    its lock, so the oracle may trail the engine by one callback for a
    moment — the VALUES still must match exactly at quiescence.

    ``mu`` (the coordinator lock) guards the device reads: a concurrent
    step() donates the previous device state, so an unlocked
    ``committed_index`` could touch a deleted buffer mid-dispatch."""
    deadline = time.time() + timeout
    while True:
        bad = []
        with (mu if mu is not None else contextlib.nullcontext()):
            for cid in cids:
                got = eng.committed_index(cid)
                want = oracles[cid].peer.raft.log.committed
                if got != want:
                    bad.append((cid, got, want))
        if not bad:
            return
        if time.time() > deadline:
            raise AssertionError((tag, bad[:4]))
        time.sleep(0.01)


def _mk_oracle(cid):
    r = new_test_raft(1, PEERS, 10, 1, InMemLogDB())
    r.cluster_id = cid
    r.become_candidate()
    r.become_leader()
    return r


@pytest.mark.slow
def test_rung4_64k_groups_mixed_load_with_churn():
    coord = TpuQuorumCoordinator(capacity=N, n_peers=5, drive_ticks=False)
    try:
        eng = coord.eng
        # --- sampled groups: real scalar oracles through the coordinator
        oracles = {}
        for g in range(SAMPLE):
            cid = 1 + g
            r = _mk_oracle(cid)
            n = FakeNode(cid, r)
            r.offload = coord
            oracles[cid] = n
            coord._nodes[cid] = n
            with coord._mu:
                coord._sync_row_locked(n)
        # --- bulk groups: engine rows driven by the block-ingest path
        with coord._mu:
            for g in range(SAMPLE, N):
                cid = 1 + g
                eng.add_group(cid, node_ids=PEERS, self_id=1)
                eng.set_leader(cid, term=1, term_start=1, last_index=1)
            eng._upload_dirty()
        bulk_rows = np.array(
            [eng.groups[1 + g].row for g in range(SAMPLE, N)], np.int32
        )
        n_bulk = bulk_rows.size

        reads = writes = 0
        t0 = time.perf_counter()
        rounds = 8
        for rnd in range(1, rounds + 1):
            # writes: every bulk group appends one entry (rel index rnd+1,
            # base 1) acked by self + 2 followers (quorum of 5)
            rows3 = np.concatenate([bulk_rows, bulk_rows, bulk_rows])
            slots = np.concatenate([
                np.zeros(n_bulk, np.int32),
                np.ones(n_bulk, np.int32),
                np.full(n_bulk, 2, np.int32),
            ])
            rels = np.full(3 * n_bulk, rnd + 1, np.int32)
            with coord._mu:
                eng.ack_block(rows3, slots, rels)
            # sampled: oracle in lockstep through the coordinator's
            # staging API (ack -> _drain -> step)
            for cid, node in oracles.items():
                r = node.peer.raft
                r.handle(Message(
                    from_=1, to=1, type=MT.PROPOSE, entries=[Entry(cmd=b"x")]
                ))
                idx = r.log.last_index()
                coord.ack(cid, 2, idx)
                coord.ack(cid, 3, idx)
            coord.flush()
            writes += n_bulk + SAMPLE
            # mixed 9:1: reads are commit-watermark queries (the
            # coordinator's read-side role); sample across the space —
            # under coord._mu (step() donates the previous device state)
            with coord._mu:
                for cid in range(1, N + 1, max(1, N // (9 * 64))):
                    eng.committed_index(cid)
                    reads += 1
            # bit-identity on every sampled group, every round
            _assert_parity(
                eng, oracles, list(oracles), f"round {rnd}", mu=coord._mu
            )
        elapsed = time.perf_counter() - t0
        # every bulk group committed every round
        with coord._mu:
            for g in (SAMPLE, SAMPLE + n_bulk // 2, N - 1):
                cid = 1 + g
                assert eng.committed_index(cid) == 1 + rounds, cid
        print(
            f"\nrung4: {N} groups x {rounds} rounds: "
            f"{writes / elapsed:.0f} writes/s {reads / elapsed:.0f} reads/s "
            f"(coordinator path, CPU backend)"
        )

        # --- rolling membership churn: recycle 4,096 bulk rows mid-life
        churn = [1 + g for g in range(SAMPLE, SAMPLE + 4096)]
        with coord._mu:
            for cid in churn:
                eng.remove_group(cid)
            for i, _ in enumerate(churn):
                cid = 200_000 + i
                eng.add_group(cid, node_ids=PEERS, self_id=1)
                eng.set_leader(cid, term=1, term_start=1, last_index=1)
            eng._upload_dirty()
        fresh_rows = np.array(
            [eng.groups[200_000 + i].row for i in range(4096)], np.int32
        )
        with coord._mu:
            eng.ack_block(
                np.concatenate([fresh_rows, fresh_rows, fresh_rows]),
                np.concatenate([
                    np.zeros(4096, np.int32), np.ones(4096, np.int32),
                    np.full(4096, 2, np.int32),
                ]),
                np.full(3 * 4096, 2, np.int32),
            )
        coord.flush()
        with coord._mu:
            for i in (0, 2048, 4095):
                assert eng.committed_index(200_000 + i) == 2
            # survivors untouched by the recycling
            assert eng.committed_index(1 + SAMPLE + 4096) == 1 + rounds

        # --- membership change on sampled oracles: 5 -> 4 voters, commit
        # quorum math must follow (resync via membership_changed)
        changed = list(oracles)[:32]
        for cid in changed:
            node = oracles[cid]
            r = node.peer.raft
            with node.raft_mu:
                r.remove_node(5)
            coord.membership_changed(cid)
        coord.flush()
        for cid in changed:
            node = oracles[cid]
            r = node.peer.raft
            r.handle(Message(
                from_=1, to=1, type=MT.PROPOSE, entries=[Entry(cmd=b"y")]
            ))
            idx = r.log.last_index()
            # 4 voters: quorum 3 = self + 2 acks
            coord.ack(cid, 2, idx)
            coord.ack(cid, 3, idx)
        coord.flush()
        _assert_parity(eng, oracles, changed, "membership-change", mu=coord._mu)
        for cid in changed:
            assert oracles[cid].peer.raft.log.committed >= 1 + rounds + 1

        # --- leader transfer on sampled groups: step down, win a new
        # election at a higher term, commit again
        transferred = list(oracles)[32:64]
        for cid in transferred:
            node = oracles[cid]
            r = node.peer.raft
            with node.raft_mu:
                r.become_follower(r.term + 1, 2)
            coord.set_follower(cid, r.term)
        coord.flush()
        for cid in transferred:
            node = oracles[cid]
            r = node.peer.raft
            with node.raft_mu:
                # campaign (includes the self-vote, raft.go:1098)
                r.handle(Message(from_=1, to=1, type=MT.ELECTION))
            assert r.is_candidate(), cid
            coord.set_candidate(cid, r.term)
            coord.vote(cid, 1, True)
            for p in (2, 3):
                r.handle(Message(
                    from_=p, to=1, term=r.term, type=MT.REQUEST_VOTE_RESP
                ))
                coord.vote(cid, p, True)
        coord.flush()
        deadline = time.time() + 8
        for cid in transferred:
            node = oracles[cid]
            r = node.peer.raft
            # the won-flag callback (offload_election) is delivered outside
            # the coordinator lock; poll briefly like _assert_parity
            while not r.is_leader() and time.time() < deadline:
                time.sleep(0.01)
            assert r.is_leader(), cid
            coord.set_leader(
                cid, term=r.term, term_start=r.log.last_index(),
                last_index=r.log.last_index(),
            )
            r.handle(Message(
                from_=1, to=1, type=MT.PROPOSE, entries=[Entry(cmd=b"z")]
            ))
            idx = r.log.last_index()
            for p in (2, 3):
                r.handle(Message(
                    from_=p, to=1, term=r.term, type=MT.REPLICATE_RESP,
                    log_index=idx,
                ))
                coord.ack(cid, p, idx)
        coord.flush()
        _assert_parity(eng, oracles, transferred, "leader-transfer", mu=coord._mu)
    finally:
        coord.stop()
