"""Differential tests: batched quorum kernels vs the scalar raft oracle.

The north star demands the batched engine's commitIndex outputs be
bit-identical to the scalar path (SURVEY.md §6); these tests replay the
exact same event streams through both and compare watermarks after every
round.  This is the conformance-gate analog of the reference's etcd-ported
suite (``internal/raft/raft_etcd_test.go``) applied to the tensor path.
"""
from __future__ import annotations

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dragonboat_tpu.ops import BatchedQuorumEngine, commit_quorum, vote_tally
from dragonboat_tpu.ops.kernels import check_quorum
from dragonboat_tpu.wire import Message, MessageType
from raft_harness import new_test_raft

MT = MessageType


# ----------------------------------------------------------------------
# kernel-level randomized differential tests
# ----------------------------------------------------------------------


def scalar_quorum_index(matches, quorum):
    """The reference's tryCommit pick: sort ascending, take [n - quorum]
    (raft.go:888-909)."""
    s = sorted(matches)
    return s[len(s) - quorum]


def test_commit_quorum_matches_scalar_sort():
    rng = random.Random(7)
    G, P = 128, 7
    match = np.zeros((G, P), np.int32)
    voting = np.zeros((G, P), bool)
    quorum = np.zeros((G,), np.int32)
    expected = np.zeros((G,), np.int32)
    for g in range(G):
        n = rng.choice([1, 3, 5, 7])
        slots = rng.sample(range(P), n)
        vals = [rng.randrange(0, 1000) for _ in range(n)]
        for s, v in zip(slots, vals):
            voting[g, s] = True
            match[g, s] = v
            # noise in non-voting slots must not affect the result
        for s in range(P):
            if not voting[g, s]:
                match[g, s] = rng.randrange(0, 2000)
        quorum[g] = n // 2 + 1
        expected[g] = scalar_quorum_index(vals, int(quorum[g]))
    got = np.asarray(
        commit_quorum(jnp.asarray(match), jnp.asarray(voting), jnp.asarray(quorum))
    )
    np.testing.assert_array_equal(got, expected)


def test_vote_tally_matches_scalar_count():
    rng = random.Random(11)
    G, P = 64, 5
    votes = np.full((G, P), -1, np.int8)
    voting = np.zeros((G, P), bool)
    quorum = np.zeros((G,), np.int32)
    exp_granted = np.zeros((G,), np.int32)
    exp_rejected = np.zeros((G,), np.int32)
    for g in range(G):
        n = rng.choice([3, 5])
        for s in range(n):
            voting[g, s] = True
            v = rng.choice([-1, 0, 1])
            votes[g, s] = v
            if v == 1:
                exp_granted[g] += 1
            elif v == 0:
                exp_rejected[g] += 1
        quorum[g] = n // 2 + 1
    granted, rejected = vote_tally(
        jnp.asarray(votes), jnp.asarray(voting), jnp.asarray(quorum)
    )
    np.testing.assert_array_equal(np.asarray(granted), exp_granted)
    np.testing.assert_array_equal(np.asarray(rejected), exp_rejected)


def test_check_quorum_matches_leader_has_quorum():
    # scalar twin: raft.go:380-390 — count self + active voters, clear flags
    G, P = 8, 5
    active = np.zeros((G, P), bool)
    voting = np.zeros((G, P), bool)
    voting[:, :3] = True
    self_slot = np.zeros((G,), np.int32)
    quorum = np.full((G,), 2, np.int32)
    active[0, 1] = True          # self + 1 active  -> quorum
    active[1, 1] = active[1, 2] = True  # 3          -> quorum
    # row 2: only self active                        -> no quorum
    active[3, 4] = True          # non-voting activity doesn't count
    has_q, cleared = check_quorum(
        jnp.asarray(active),
        jnp.asarray(voting),
        jnp.asarray(self_slot),
        jnp.asarray(quorum),
    )
    np.testing.assert_array_equal(
        np.asarray(has_q), [True, True, False, False, False, False, False, False]
    )
    # voting members' activity consumed, non-voting preserved
    assert not np.asarray(cleared)[1, 1]
    assert np.asarray(cleared)[3, 4]


# ----------------------------------------------------------------------
# engine-level differential: scalar Raft leader vs BatchedQuorumEngine
# ----------------------------------------------------------------------


def make_scalar_leader(peers):
    """Elect node 1 leader of a fresh group and return the Raft."""
    r = new_test_raft(1, peers)
    r.handle(Message(from_=1, to=1, type=MT.ELECTION))
    for p in peers:
        if p != 1:
            r.handle(
                Message(from_=p, to=1, term=r.term, type=MT.REQUEST_VOTE_RESP)
            )
        if r.is_leader():
            break
    assert r.is_leader()
    return r


def mirror_leader(eng, cid, r, peers):
    """Mirror freshly-elected scalar leader state into the engine."""
    # term_start = the noop appended at promotion (become_leader)
    eng.set_leader(
        cid,
        term=r.term,
        term_start=r.log.last_index(),
        last_index=r.log.last_index(),
    )


@pytest.mark.parametrize("peers", [[1, 2, 3], [1, 2, 3, 4, 5]])
def test_commit_differential_ordered_acks(peers):
    r = make_scalar_leader(peers)
    eng = BatchedQuorumEngine(n_groups=4, n_peers=len(peers))
    eng.add_group(1, node_ids=peers, self_id=1)
    mirror_leader(eng, 1, r, peers)
    assert eng.committed_index(1) == r.log.committed == 0

    # propose 10 entries, acking each from a rotating quorum subset
    rng = random.Random(3)
    for i in range(10):
        r.handle(
            Message(from_=1, to=1, type=MT.PROPOSE, entries=[__propose_entry()])
        )
        eng.ack(1, 1, r.log.last_index())  # self append
        followers = [p for p in peers if p != 1]
        rng.shuffle(followers)
        for p in followers[: len(peers) // 2 + rng.randrange(0, 2)]:
            r.handle(
                Message(
                    from_=p,
                    to=1,
                    term=r.term,
                    type=MT.REPLICATE_RESP,
                    log_index=r.log.last_index(),
                )
            )
            eng.ack(1, p, r.log.last_index())
        out = eng.step(do_tick=False)
        assert eng.committed_index(1) == r.log.committed
        if 1 in out.commit:
            assert out.commit[1] == r.log.committed


def __propose_entry():
    from dragonboat_tpu.wire import Entry

    return Entry(cmd=b"x")


def test_commit_differential_random_stale_acks():
    """Stale, duplicate, and out-of-order acks must commit identically."""
    peers = [1, 2, 3, 4, 5]
    r = make_scalar_leader(peers)
    eng = BatchedQuorumEngine(n_groups=2, n_peers=5)
    eng.add_group(1, node_ids=peers, self_id=1)
    mirror_leader(eng, 1, r, peers)

    rng = random.Random(99)
    for _ in range(40):
        for _ in range(rng.randrange(0, 3)):
            r.handle(
                Message(from_=1, to=1, type=MT.PROPOSE, entries=[__propose_entry()])
            )
            eng.ack(1, 1, r.log.last_index())
        last = r.log.last_index()
        for _ in range(rng.randrange(0, 6)):
            p = rng.choice(peers[1:])
            idx = rng.randrange(0, last + 1)  # may be stale
            r.handle(
                Message(
                    from_=p,
                    to=1,
                    term=r.term,
                    type=MT.REPLICATE_RESP,
                    log_index=idx,
                )
            )
            eng.ack(1, p, idx)
        eng.step(do_tick=False)
        assert eng.committed_index(1) == r.log.committed


def test_commit_differential_many_groups():
    """64 independent groups with interleaved random ack streams."""
    G = 64
    rng = random.Random(42)
    eng = BatchedQuorumEngine(n_groups=G, n_peers=5)
    scalars = {}
    for cid in range(1, G + 1):
        peers = [1, 2, 3] if cid % 2 else [1, 2, 3, 4, 5]
        r = make_scalar_leader(peers)
        scalars[cid] = (r, peers)
        eng.add_group(cid, node_ids=peers, self_id=1)
        mirror_leader(eng, cid, r, peers)

    for _ in range(10):
        for cid, (r, peers) in scalars.items():
            if rng.random() < 0.7:
                r.handle(
                    Message(
                        from_=1, to=1, type=MT.PROPOSE, entries=[__propose_entry()]
                    )
                )
                eng.ack(cid, 1, r.log.last_index())
            for p in peers[1:]:
                if rng.random() < 0.6:
                    idx = rng.randrange(0, r.log.last_index() + 1)
                    r.handle(
                        Message(
                            from_=p,
                            to=1,
                            term=r.term,
                            type=MT.REPLICATE_RESP,
                            log_index=idx,
                        )
                    )
                    eng.ack(cid, p, idx)
        eng.step(do_tick=False)
        for cid, (r, _) in scalars.items():
            assert eng.committed_index(cid) == r.log.committed, f"group {cid}"


def test_election_differential():
    """Vote quorum flags fire exactly when the scalar candidate wins."""
    peers = [1, 2, 3, 4, 5]
    r = new_test_raft(1, peers)
    eng = BatchedQuorumEngine(n_groups=2, n_peers=5)
    eng.add_group(1, node_ids=peers, self_id=1)

    r.handle(Message(from_=1, to=1, type=MT.ELECTION))
    assert r.is_candidate()
    eng.set_candidate(1, term=r.term)
    eng.vote(1, 1, granted=True)  # campaign self-vote (raft.go:1098)

    out = eng.step(do_tick=False)
    assert not out.won and not out.lost

    r.handle(Message(from_=2, to=1, term=r.term, type=MT.REQUEST_VOTE_RESP))
    eng.vote(1, 2, granted=True)
    out = eng.step(do_tick=False)
    assert not r.is_leader() and not out.won  # 2 of 5: no quorum yet

    r.handle(Message(from_=3, to=1, term=r.term, type=MT.REQUEST_VOTE_RESP))
    eng.vote(1, 3, granted=True)
    out = eng.step(do_tick=False)
    assert r.is_leader()
    assert out.won == [1]


def test_election_rejection_differential():
    peers = [1, 2, 3]
    r = new_test_raft(1, peers)
    eng = BatchedQuorumEngine(n_groups=1, n_peers=3)
    eng.add_group(1, node_ids=peers, self_id=1)
    r.handle(Message(from_=1, to=1, type=MT.ELECTION))
    eng.set_candidate(1, term=r.term)
    eng.vote(1, 1, granted=True)
    for p in (2, 3):
        r.handle(
            Message(
                from_=p, to=1, term=r.term, type=MT.REQUEST_VOTE_RESP, reject=True
            )
        )
        eng.vote(1, p, granted=False)
    out = eng.step(do_tick=False)
    assert r.is_follower()
    assert out.lost == [1]


def test_tick_election_due_matches_scalar_timing():
    """elect_due fires on exactly the tick the scalar oracle campaigns."""
    peers = [1, 2, 3]
    r = new_test_raft(1, peers)
    eng = BatchedQuorumEngine(n_groups=1, n_peers=3)
    eng.add_group(
        1,
        node_ids=peers,
        self_id=1,
        election_timeout=10,
        rand_timeout=r.randomized_election_timeout,
    )
    fired_scalar = None
    fired_batched = None
    for tick in range(1, 30):
        was_candidate = r.is_candidate()
        r.tick()
        if fired_scalar is None and r.is_candidate() and not was_candidate:
            fired_scalar = tick
        out = eng.step(do_tick=True)
        if fired_batched is None and out.elect:
            fired_batched = tick
        if fired_scalar is not None:
            break
    assert fired_scalar is not None
    assert fired_batched == fired_scalar


def test_heartbeat_due_matches_scalar_timing():
    peers = [1, 2, 3]
    r = make_scalar_leader(peers)
    eng = BatchedQuorumEngine(n_groups=1, n_peers=3)
    eng.add_group(1, node_ids=peers, self_id=1, heartbeat_timeout=3)
    mirror_leader(eng, 1, r, peers)
    # scalar heartbeat_timeout from config: election=10, heartbeat=1; use
    # a dedicated engine row with timeout 3 and check periodicity instead
    fires = []
    for tick in range(1, 10):
        out = eng.step(do_tick=True)
        if out.heartbeat:
            fires.append(tick)
    assert fires == [3, 6, 9]


def test_rebase_preserves_commit_semantics():
    peers = [1, 2, 3]
    r = make_scalar_leader(peers)
    eng = BatchedQuorumEngine(n_groups=1, n_peers=3)
    eng.add_group(1, node_ids=peers, self_id=1)
    mirror_leader(eng, 1, r, peers)
    for i in range(5):
        r.handle(Message(from_=1, to=1, type=MT.PROPOSE, entries=[__propose_entry()]))
        eng.ack(1, 1, r.log.last_index())
        for p in (2, 3):
            r.handle(
                Message(
                    from_=p,
                    to=1,
                    term=r.term,
                    type=MT.REPLICATE_RESP,
                    log_index=r.log.last_index(),
                )
            )
            eng.ack(1, p, r.log.last_index())
    eng.step(do_tick=False)
    assert eng.committed_index(1) == r.log.committed == 6  # noop + 5

    eng.rebase(1)
    assert eng.committed_index(1) == r.log.committed
    assert eng.groups[1].base == 6

    # progress continues identically post-rebase
    r.handle(Message(from_=1, to=1, type=MT.PROPOSE, entries=[__propose_entry()]))
    eng.ack(1, 1, r.log.last_index())
    for p in (2, 3):
        r.handle(
            Message(
                from_=p,
                to=1,
                term=r.term,
                type=MT.REPLICATE_RESP,
                log_index=r.log.last_index(),
            )
        )
        eng.ack(1, p, r.log.last_index())
    eng.step(do_tick=False)
    assert eng.committed_index(1) == r.log.committed == 7


def test_group_lifecycle_row_reuse():
    eng = BatchedQuorumEngine(n_groups=2, n_peers=3)
    eng.add_group(1, node_ids=[1, 2, 3], self_id=1)
    eng.add_group(2, node_ids=[1, 2, 3], self_id=1)
    with pytest.raises(RuntimeError):
        eng.add_group(3, node_ids=[1, 2, 3], self_id=1)
    eng.remove_group(1)
    eng.add_group(3, node_ids=[1, 2, 3], self_id=1)
    eng.set_leader(3, term=1, term_start=1, last_index=1)
    eng.ack(3, 1, 1)
    eng.ack(3, 2, 1)
    eng.step(do_tick=False)
    assert eng.committed_index(3) == 1


def test_stale_queued_votes_purged_on_new_campaign():
    """Votes queued before a state transition belong to the old term and
    must not count toward the new term's tally (scalar twin drops
    mismatched-term responses, raft.go:1062-1080)."""
    peers = [1, 2, 3, 4, 5]
    eng = BatchedQuorumEngine(n_groups=1, n_peers=5)
    eng.add_group(1, node_ids=peers, self_id=1)
    eng.set_candidate(1, term=1)
    eng.vote(1, 2, granted=True)  # queued, never stepped — term-1 vote
    # campaign restarts at term 2 before the engine ever dispatched
    eng.set_candidate(1, term=2)
    eng.vote(1, 1, granted=True)
    eng.vote(1, 3, granted=True)
    out = eng.step(do_tick=False)
    # only 2 of quorum-3 granted in term 2: must NOT have won
    assert out.won == []
    # peer 2's real term-2 vote still lands (first-vote guard was purged)
    eng.vote(1, 2, granted=True)
    out = eng.step(do_tick=False)
    assert out.won == [1]


def test_stale_queued_acks_purged_on_leader_transition():
    peers = [1, 2, 3]
    eng = BatchedQuorumEngine(n_groups=1, n_peers=3)
    eng.add_group(1, node_ids=peers, self_id=1)
    eng.set_leader(1, term=1, term_start=1, last_index=4)
    eng.ack(1, 2, 3)  # queued old-term ack, never stepped
    eng.set_follower(1, term=2)
    eng.set_leader(1, term=3, term_start=5, last_index=5)
    eng.ack(1, 1, 5)
    out = eng.step(do_tick=False)
    # without peer 2's (purged) stale ack nothing past term_start commits
    assert eng.committed_index(1) == 0
    eng.ack(1, 2, 5)
    eng.step(do_tick=False)
    assert eng.committed_index(1) == 5


def test_ack_block_equivalent_to_per_event_acks():
    """The vectorized bulk-ingest path (ack_block) must produce exactly the
    same commit outcomes as per-event ack() staging."""
    import numpy as np

    from dragonboat_tpu.ops.engine import BatchedQuorumEngine

    def build():
        eng = BatchedQuorumEngine(8, 3, event_cap=64)
        for cid in range(1, 9):
            eng.add_group(cid, node_ids=[1, 2, 3], self_id=1)
            eng.set_leader(cid, term=1, term_start=1, last_index=1)
        return eng

    a, b = build(), build()
    # per-event staging on a
    for cid in range(1, 9):
        a.ack(cid, 1, 5)
        a.ack(cid, 2, 5)
    ra = a.step(do_tick=False)
    # block staging on b (same rows/slots/rels)
    rows = np.tile(np.arange(8, dtype=np.int32), 2)
    slots = np.concatenate([np.zeros(8, np.int32), np.ones(8, np.int32)])
    rels = np.full(16, 5, np.int32)  # base is 0 for fresh groups
    b.ack_block(rows, slots, rels)
    rb = b.step(do_tick=False)
    assert ra.commit == rb.commit
    for cid in range(1, 9):
        assert a.committed_index(cid) == b.committed_index(cid) == 5

    # oversized blocks chunk without recompilation or loss
    c = build()
    big_rows = np.tile(np.arange(8, dtype=np.int32), 40)  # 320 > cap 64
    big_slots = np.tile(slots, 20)
    big_rels = np.tile(np.arange(1, 41, dtype=np.int32).repeat(8), 1)[:320]
    c.ack_block(big_rows, np.resize(big_slots, 320), np.sort(big_rels))
    c.step(do_tick=False)  # must not raise

    # bounds are validated
    import pytest

    with pytest.raises(ValueError):
        a.ack_block(np.array([99], np.int32), np.array([0], np.int32),
                    np.array([1], np.int32))


# ----------------------------------------------------------------------
# dense-ingestion kernel: bit-identity with the sparse scatter kernel
# ----------------------------------------------------------------------


def _random_engine(rng, n_groups=24, n_peers=3, cap=256):
    eng = BatchedQuorumEngine(n_groups, n_peers, event_cap=cap)
    for cid in range(1, n_groups + 1):
        peers = list(range(1, n_peers + 1))
        eng.add_group(cid, node_ids=peers, self_id=1)
        role = rng.random()
        if role < 0.6:
            eng.set_leader(cid, term=2, term_start=3, last_index=3 + rng.randrange(4))
        elif role < 0.8:
            eng.set_candidate(cid, term=2)
        # else: stays follower
    eng._upload_dirty()
    return eng


def _state_equal(a, b):
    for name, va in a._asdict().items():
        vb = getattr(b, name)
        assert np.array_equal(np.asarray(va), np.asarray(vb)), name


@pytest.mark.parametrize("do_tick", [False, True])
def test_dense_kernel_matches_sparse_kernel(do_tick):
    """quorum_step_dense(aggregated batch) ≡ quorum_step(sparse batch).

    Scatter-max aggregation is order-independent, so collapsing a round's
    events into per-cell maxima must leave every state field and output
    flag bit-identical — including duplicate acks, stale (lower) acks,
    zero-value heartbeat acks, and first-wins-deduped votes.
    """
    from dragonboat_tpu.ops.kernels import quorum_step, quorum_step_dense

    rng = random.Random(1234 + do_tick)
    g, p, cap = 24, 3, 256
    sparse_eng = _random_engine(rng, g, p, cap)
    dense_eng = _random_engine(random.Random(1234 + do_tick), g, p, cap)
    _state_equal(sparse_eng.dev, dense_eng.dev)

    for round_no in range(6):
        # random ack batch: duplicates, stale values, heartbeat zero-acks
        n_acks = rng.randrange(0, 64)
        acks = [
            (rng.randrange(g), rng.randrange(p), rng.choice([0, 1, 2, 5, 9]))
            for _ in range(n_acks)
        ]
        # votes: first-wins per cell (the engine dedups within a batch;
        # duplicate sparse vote scatters would be scatter-order-defined)
        vote_cells = {}
        for _ in range(rng.randrange(0, 8)):
            cell = (rng.randrange(g), rng.randrange(p))
            vote_cells.setdefault(cell, rng.choice([0, 1]))
        votes = [(r, s, v) for (r, s), v in vote_cells.items()]

        # sparse dispatch
        ag = np.zeros((cap,), np.int32)
        ap = np.zeros((cap,), np.int32)
        av = np.zeros((cap,), np.int32)
        avalid = np.zeros((cap,), bool)
        for i, (r, s, v) in enumerate(acks):
            ag[i], ap[i], av[i], avalid[i] = r, s, v, True
        vg = np.zeros((cap,), np.int32)
        vp = np.zeros((cap,), np.int32)
        vv = np.zeros((cap,), np.int8)
        vvalid = np.zeros((cap,), bool)
        for i, (r, s, v) in enumerate(votes):
            vg[i], vp[i], vv[i], vvalid[i] = r, s, v, True
        out_s = quorum_step(
            sparse_eng.dev,
            jnp.asarray(ag), jnp.asarray(ap), jnp.asarray(av),
            jnp.asarray(avalid), jnp.asarray(vg), jnp.asarray(vp),
            jnp.asarray(vv), jnp.asarray(vvalid),
            do_tick=do_tick, track_contact=True, has_votes=True,
        )
        sparse_eng.dev = out_s.state

        # dense dispatch of the SAME events, host-aggregated
        ack_max = np.zeros((g, p), np.int32)
        touched = np.zeros((g, p), bool)
        for r, s, v in acks:
            ack_max[r, s] = max(ack_max[r, s], v)
            touched[r, s] = True
        vote_new = np.full((g, p), -1, np.int8)
        for r, s, v in votes:
            vote_new[r, s] = v
        out_d = quorum_step_dense(
            dense_eng.dev,
            jnp.asarray(ack_max), jnp.asarray(touched), jnp.asarray(vote_new),
            do_tick=do_tick, track_contact=True, has_votes=True,
        )
        dense_eng.dev = out_d.state

        _state_equal(out_s.state, out_d.state)
        for field_ in ("committed", "won", "lost"):
            assert np.array_equal(
                np.asarray(getattr(out_s, field_)),
                np.asarray(getattr(out_d, field_)),
            ), (field_, round_no)
        for i, fname in enumerate(("elect_due", "hb_due", "checkq_demote")):
            assert np.array_equal(
                np.asarray(out_s.flags[i]), np.asarray(out_d.flags[i])
            ), (fname, round_no)


def test_engine_dense_ingest_matches_sparse():
    """The engine's dense auto-path must be observationally identical to
    the sparse path across multi-round workloads with ticks."""
    rng_seed = 77

    def run(dense):
        rng = random.Random(rng_seed)
        eng = BatchedQuorumEngine(16, 3, event_cap=128, dense_ingest=dense)
        for cid in range(1, 17):
            eng.add_group(cid, node_ids=[1, 2, 3], self_id=1)
            eng.set_leader(cid, term=1, term_start=1, last_index=1)
        results = []
        idx = {cid: 1 for cid in range(1, 17)}
        for _ in range(8):
            for _ in range(rng.randrange(4, 40)):
                cid = rng.randrange(1, 17)
                idx[cid] += 1
                eng.ack(cid, 1, idx[cid])
                if rng.random() < 0.8:
                    eng.ack(cid, 2, idx[cid])
                if rng.random() < 0.2:
                    eng.heartbeat_resp(cid, 3)
            res = eng.step(do_tick=True)
            results.append((dict(res.commit), list(res.heartbeat)))
        return results, {cid: eng.committed_index(cid) for cid in range(1, 17)}

    res_sparse, final_sparse = run(False)
    res_dense, final_dense = run(True)
    assert res_sparse == res_dense
    assert final_sparse == final_dense


def test_has_votes_false_matches_empty_vote_batch():
    """has_votes=False (compiled-out vote ingest) ≡ an empty vote batch."""
    from dragonboat_tpu.ops.kernels import quorum_step

    eng_a = _random_engine(random.Random(9), 12, 3, 64)
    eng_b = _random_engine(random.Random(9), 12, 3, 64)
    cap = 64
    ag = np.array([0, 1, 2, 5] + [0] * (cap - 4), np.int32)
    ap = np.array([1, 2, 0, 1] + [0] * (cap - 4), np.int32)
    av = np.array([4, 5, 6, 7] + [0] * (cap - 4), np.int32)
    avalid = np.array([True] * 4 + [False] * (cap - 4))
    zero_votes = (
        jnp.zeros((cap,), jnp.int32), jnp.zeros((cap,), jnp.int32),
        jnp.zeros((cap,), jnp.int8), jnp.zeros((cap,), bool),
    )
    dummy_votes = (
        jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.int8), jnp.zeros((1,), bool),
    )
    out_a = quorum_step(
        eng_a.dev, jnp.asarray(ag), jnp.asarray(ap), jnp.asarray(av),
        jnp.asarray(avalid), *zero_votes, do_tick=True, has_votes=True,
    )
    out_b = quorum_step(
        eng_b.dev, jnp.asarray(ag), jnp.asarray(ap), jnp.asarray(av),
        jnp.asarray(avalid), *dummy_votes, do_tick=True, has_votes=False,
    )
    _state_equal(out_a.state, out_b.state)
    assert np.array_equal(np.asarray(out_a.committed), np.asarray(out_b.committed))


def test_engine_dense_ingest_validation():
    with pytest.raises(ValueError):
        BatchedQuorumEngine(4, 3, dense_ingest=1)
    with pytest.raises(ValueError):
        BatchedQuorumEngine(4, 3, dense_ingest="always")


def test_kth_largest_network_all_widths():
    """_kth_largest across every specialized width (P=1..8 use the
    elementwise compare-exchange network; P=9 exercises the (G,P,P)
    rank-select fallback) against a NumPy sort oracle, including
    all-masked rows, ties, and every valid k."""
    from dragonboat_tpu.ops.kernels import _kth_largest
    from dragonboat_tpu.ops.state import INDEX_MIN

    rng = random.Random(23)
    for P in range(1, 10):
        G = 160
        vals = np.zeros((G, P), np.int32)
        mask = np.zeros((G, P), bool)
        k = np.ones((G,), np.int32)
        expected = np.zeros((G,), np.int32)
        for g in range(G):
            n = rng.randrange(0, P + 1)
            slots = rng.sample(range(P), n)
            # small value range forces ties; non-masked slots hold noise
            for s in range(P):
                vals[g, s] = rng.randrange(0, 6)
            for s in slots:
                mask[g, s] = True
            masked = sorted(
                (vals[g, s] for s in slots), reverse=True
            )
            if n == 0:
                k[g] = 1
                expected[g] = INDEX_MIN  # all-masked row: min sentinel
            else:
                k[g] = rng.randrange(1, n + 1)
                expected[g] = masked[k[g] - 1]
        got = np.asarray(
            _kth_largest(jnp.asarray(vals), jnp.asarray(mask), jnp.asarray(k))
        )
        np.testing.assert_array_equal(got, expected, err_msg=f"P={P}")
