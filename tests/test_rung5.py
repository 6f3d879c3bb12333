"""Rung 5 of the config ladder: 100k groups × 5 peer slots with
membership-change and leader-transfer churn ROLLING THROUGH the load
(BASELINE.md ladder, final rung; reference scaling claim README.md
Performance § / `docs/nodes.png`).

Rung 4 (tests/test_rung4.py) runs churn phases after the load phase; the
rung-5 ladder row asks for churn *during* sustained load — thousands of
idle-group recycles, membership changes, and leader transfers per round
while every surviving group keeps committing, with commitIndex asserted
bit-identical to full scalar Raft oracles on a sampled subset every
round (the "bit-identical to pure-scalar path under Jepsen/Knossos"
clause — the linearizability harness proper runs in test_chaos_tcp.py;
here the differential oracle plays that role at scale).

Marked slow: one run is a few minutes on the 8-vCPU CI box.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator
from dragonboat_tpu.wire import Entry, Message, MessageType as MT

from tests.test_rung4 import FakeNode, _assert_parity, _mk_oracle

# heavy multi-NodeHost tests never overlap each other (the lock in
# tests/conftest.py): side by side they starve each other on an 8-vCPU box
pytestmark = [pytest.mark.slow, pytest.mark.xdist_group("heavy-multiprocess")]

N = 100_000
SAMPLE = 128
PEERS = [1, 2, 3, 4, 5]
CHURN_BLOCK = 2_048  # rows recycled per round, mid-load


@pytest.mark.slow
def test_rung5_100k_groups_churn_under_load():
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

        # per-group relative commit progress (churned groups restart at 1)
        base = {1 + g: 1 for g in range(SAMPLE, N)}
        next_fresh_cid = 1_000_000
        reads = writes = recycled = 0
        rounds = 6
        t0 = time.perf_counter()
        for rnd in range(1, rounds + 1):
            # --- rolling membership churn DURING load: recycle a block of
            # bulk rows (remove_group frees the row; add_group reuses it)
            victims = sorted(base)[(rnd - 1) * CHURN_BLOCK:rnd * CHURN_BLOCK]
            with coord._mu:
                for cid in victims:
                    eng.remove_group(cid)
                    del base[cid]
                for _ in victims:
                    cid = next_fresh_cid
                    next_fresh_cid += 1
                    eng.add_group(cid, node_ids=PEERS, self_id=1)
                    eng.set_leader(cid, term=1, term_start=1, last_index=1)
                    base[cid] = 1
                eng._upload_dirty()
            recycled += len(victims)

            # --- bulk writes: every live bulk group appends one entry,
            # acked by self + 2 followers (quorum of 5)
            cids = np.fromiter(base.keys(), np.int64, len(base))
            rows = np.array([eng.groups[c].row for c in base], np.int32)
            rels = np.array(
                [base[c] + 1 for c in base], np.int32
            )
            nb = rows.size
            with coord._mu:
                eng.ack_block(
                    np.concatenate([rows, rows, rows]),
                    np.concatenate([
                        np.zeros(nb, np.int32), np.ones(nb, np.int32),
                        np.full(nb, 2, np.int32),
                    ]),
                    np.concatenate([rels, rels, rels]),
                )
            for c in base:
                base[c] += 1

            # --- sampled oracles in lockstep through the staging API
            for cid, node in oracles.items():
                r = node.peer.raft
                if not r.is_leader():
                    continue
                r.handle(Message(
                    from_=1, to=1, type=MT.PROPOSE, entries=[Entry(cmd=b"x")]
                ))
                idx = r.log.last_index()
                for p in (2, 3):
                    r.handle(Message(
                        from_=p, to=1, term=r.term, type=MT.REPLICATE_RESP,
                        log_index=idx,
                    ))
                    coord.ack(cid, p, idx)
            coord.flush()
            writes += nb + SAMPLE

            # --- mixed 9:1 read-side probe: commit-watermark queries.
            # Under coord._mu: the background round thread's step()
            # donates the previous device state (donate_argnums), so an
            # unlocked read could touch a deleted buffer mid-dispatch.
            step = max(1, len(cids) // (9 * 64))
            with coord._mu:
                for c in cids[::step]:
                    eng.committed_index(int(c))
                    reads += 1

            # --- membership change on a rotating oracle slice, mid-load:
            # 5 -> 4 voters (round odd) or back 4 -> 5 (round even)
            lo = ((rnd - 1) * 16) % SAMPLE
            for cid in list(oracles)[lo:lo + 16]:
                node = oracles[cid]
                r = node.peer.raft
                with node.raft_mu:
                    if 5 in r.remotes:
                        r.remove_node(5)
                    else:
                        r.add_node(5)
                coord.membership_changed(cid)

            # --- leader transfer on a different rotating slice, mid-load:
            # step down, win a fresh election at a higher term
            lo = (16 + (rnd - 1) * 16) % SAMPLE
            for cid in list(oracles)[lo:lo + 8]:
                node = oracles[cid]
                r = node.peer.raft
                with node.raft_mu:
                    r.become_follower(r.term + 1, 2)
                coord.set_follower(cid, r.term)
                with node.raft_mu:
                    r.handle(Message(from_=1, to=1, type=MT.ELECTION))
                assert r.is_candidate(), cid
                coord.set_candidate(cid, r.term)
                coord.vote(cid, 1, True)
                for p in (2, 3):
                    r.handle(Message(
                        from_=p, to=1, term=r.term,
                        type=MT.REQUEST_VOTE_RESP,
                    ))
                    coord.vote(cid, p, True)
            coord.flush()
            # the election outcome lands via offload_election outside the
            # coordinator lock; re-seat each new leader's row watermarks
            deadline = time.time() + 8
            for cid in list(oracles)[lo:lo + 8]:
                r = oracles[cid].peer.raft
                while not r.is_leader() and time.time() < deadline:
                    time.sleep(0.01)
                assert r.is_leader(), cid
                coord.set_leader(
                    cid, term=r.term, term_start=r.log.last_index(),
                    last_index=r.log.last_index(),
                )

            # --- bit-identity on every sampled group, every round
            _assert_parity(
                eng, oracles, list(oracles), f"round {rnd}", mu=coord._mu
            )

        elapsed = time.perf_counter() - t0
        # spot-check bulk commit progress: survivors advanced every round
        # they were alive; freshly recycled groups advanced since rebirth
        with coord._mu:
            for c in (sorted(base)[len(base) // 2], max(base)):
                assert eng.committed_index(c) == base[c], c
        assert recycled == rounds * CHURN_BLOCK
        print(
            f"\nrung5: {N} groups x {rounds} rounds, "
            f"{recycled} recycled, {writes / elapsed:.0f} writes/s "
            f"{reads / elapsed:.0f} reads/s (coordinator path, CPU backend)"
        )
    finally:
        coord.stop()
