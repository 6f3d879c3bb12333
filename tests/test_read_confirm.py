"""Differential tests for the device ReadIndex plane (ISSUE 3 tentpole).

The fused read plane (``kernels.read_confirm`` / ``_read_plane``, the
``has_reads`` variants of ``quorum_step_dense`` and ``quorum_multiround``,
and ``BatchedQuorumEngine.stage_read``/``read_ack``) must be
observationally identical to K single-round dispatches — and, through
them, to the scalar ``ReadIndex.confirm`` oracle (``raft/readindex.py``,
reference ``readindex.go:77-116``): same confirmed batches, same release
indices, bit-identical device state.  Includes the ISSUE acceptance
corners — a membership recycle and a leader change with pending read
ctxs mid-block — plus the live coordinator path (reads batched per
round, released through the scalar prefix pop).
"""
from __future__ import annotations

import random
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dragonboat_tpu.ops.engine import BatchedQuorumEngine
from dragonboat_tpu.raft.readindex import ReadIndex
from dragonboat_tpu.wire import SystemCtx


def _state_equal(a, b, tag=""):
    for name, va in a._asdict().items():
        vb = getattr(b, name)
        assert np.array_equal(np.asarray(va), np.asarray(vb)), (tag, name)


def _build(n_groups=8, n_peers=3, cap=256, read_slots=None, observer=False):
    """Groups 1..n led by node 1; with ``observer`` the highest node id
    is a non-voting member."""
    kw = {} if read_slots is None else {"n_read_slots": read_slots}
    eng = BatchedQuorumEngine(n_groups, n_peers, event_cap=cap, **kw)
    ids = list(range(1, n_peers + 1))
    members = (
        {"node_ids": ids[:-1], "observers": (ids[-1],)} if observer
        else {"node_ids": ids}
    )
    for cid in range(1, n_groups + 1):
        eng.add_group(cid, self_id=1, **members)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
    eng._upload_dirty()
    return eng


# ----------------------------------------------------------------------
# kernel level: fused scan ≡ K sequential dense read dispatches
# ----------------------------------------------------------------------


def test_read_multiround_kernel_matches_dense_rounds():
    from dragonboat_tpu.ops.kernels import quorum_multiround, quorum_step_dense

    rng = random.Random(611)
    g, p, k = 12, 3, 6
    eng_a, eng_b = _build(g, p), _build(g, p)
    s = eng_a.n_read_slots

    ack = np.full((k, g, p), -1, np.int32)
    stage_idx = np.full((k, g, s), -1, np.int32)
    stage_cnt = np.zeros((k, g, s), np.int32)
    echo = np.zeros((k, g, s, p), bool)
    for r in range(k):
        for _ in range(rng.randrange(0, 12)):
            ack[r, rng.randrange(g), rng.randrange(p)] = rng.choice([1, 2, 5])
        for _ in range(rng.randrange(0, 6)):
            gi, sl = rng.randrange(g), rng.randrange(s)
            stage_idx[r, gi, sl] = rng.randrange(0, 6)
            stage_cnt[r, gi, sl] = rng.randrange(1, 9)
        for _ in range(rng.randrange(0, 10)):
            echo[r, rng.randrange(g), rng.randrange(s), rng.randrange(p)] = True

    z = jnp.zeros((1, 1), jnp.int32)
    out_f = quorum_multiround(
        eng_a.dev,
        jnp.asarray(ack),
        jnp.zeros((1, 1, 1), jnp.int8),
        z, z, z, z,
        jnp.zeros((k,), bool),
        jnp.asarray(stage_idx),
        jnp.asarray(stage_cnt),
        jnp.asarray(echo),
        do_tick=False,
        track_contact=True,
        has_votes=False,
        has_churn=False,
        has_reads=True,
    )

    st = eng_b.dev
    cnt_acc = np.zeros((g, s), np.int64)
    idx_acc = np.full((g, s), -1, np.int64)
    for r in range(k):
        am = ack[r]
        out = quorum_step_dense(
            st,
            jnp.asarray(np.maximum(am, 0)),
            jnp.asarray(am >= 0),
            jnp.zeros((1, 1), jnp.int8),
            jnp.asarray(stage_idx[r]),
            jnp.asarray(stage_cnt[r]),
            jnp.asarray(echo[r]),
            do_tick=False,
            track_contact=True,
            has_votes=False,
            has_reads=True,
        )
        st = out.state
        cnt_acc += np.asarray(out.read_done_count)
        idx_acc = np.maximum(idx_acc, np.asarray(out.read_done_index))

    _state_equal(out_f.state, st, "read-kernel")
    assert np.array_equal(np.asarray(out_f.read_done_count), cnt_acc)
    assert np.array_equal(np.asarray(out_f.read_done_index), idx_acc)


# ----------------------------------------------------------------------
# engine level: fused ≡ per-round step() ≡ scalar ReadIndex oracle
# ----------------------------------------------------------------------


class _Oracle:
    """Scalar ReadIndex twin of one engine group.  Each engine pending-
    read SLOT confirms independently by its own echo quorum, so its twin
    is one ``ReadIndex`` instance per staged batch (a batch of count N =
    one ctx carrying N reads).  The scalar queue's PREFIX release is a
    batching optimization the coordinator layer reconstitutes
    (``tpuquorum._collect_read_confirms`` + ``read_index.release``);
    the quorum arithmetic and release indices pinned here are the same
    ``confirm`` code path either way."""

    def __init__(self, quorum):
        self.quorum = quorum
        self.next_ctx = 1
        self.released = []  # (index, count)

    def stage(self, index, count):
        ctx = SystemCtx(low=self.next_ctx, high=0)
        self.next_ctx += 1
        ri = ReadIndex()
        ri.add_request(index, ctx, from_=0)
        return (ri, ctx, count)

    def echo(self, batch, peer):
        ri, ctx, count = batch
        for s_ in ri.confirm(ctx, peer, self.quorum):
            self.released.append((s_.index, count))


def _drive(eng, oracles, seed, fused, rounds=6):
    """Random read workload, identical for every backend: per round some
    groups stage a batch at their current committed rel, then random
    follower echoes land for the newest UNCONFIRMED batch (the
    heartbeat-hint protocol).  The driver tracks echo quorums itself —
    deterministically, independent of harvest timing — so fused and
    per-round runs generate the identical event stream."""
    rng = random.Random(seed)
    # driver-side pending: (slot, ctx_count, echoed_peers)
    pending = {cid: [] for cid in oracles}
    released = {cid: [] for cid in oracles}

    def harvest(res):
        if res is None or res.read_cids is None:
            return
        for cid, _slot, idx, count in res.reads:
            released[cid].append((idx, count))

    for _ in range(rounds):
        for cid, orc in oracles.items():
            if rng.random() < 0.7 and eng.read_slots_free(cid) > 0:
                count = rng.randrange(1, 5)
                idx = eng.committed_index(cid)
                slot = eng.stage_read(cid, count=count, index=idx)
                pending[cid].append((slot, orc.stage(idx, count), set()))
            if pending[cid] and rng.random() < 0.8:
                slot, cc, echoed = pending[cid][-1]
                for peer in (2, 3):
                    if rng.random() < 0.7:
                        eng.read_ack(cid, peer, slot)
                        orc.echo(cc, peer)
                        echoed.add(peer)
                if len(echoed) + 1 >= 2:  # quorum reached: batch done
                    pending[cid].pop()
        if fused:
            eng.begin_round()
        else:
            harvest(eng.step(do_tick=False))
    if fused:
        harvest(eng.step_rounds(do_tick=False))
    else:
        harvest(eng.step(do_tick=False))
    return released


def test_read_engine_matches_scalar_oracle_and_per_round():
    # 8 slots so no slot is reused within the fused block: a same-slot
    # re-confirm merges (count-sum / index-max) in the block accumulators
    # by design — distinct slots keep the comparison per-batch exact
    # (the merge itself is pinned by the kernel-level test above)
    seed = 77
    n = 6
    eng_f, eng_s = _build(n, read_slots=8), _build(n, read_slots=8)
    orc_f = {cid: _Oracle(2) for cid in range(1, n + 1)}
    orc_s = {cid: _Oracle(2) for cid in range(1, n + 1)}
    rel_f = _drive(eng_f, orc_f, seed, fused=True)
    rel_s = _drive(eng_s, orc_s, seed, fused=False)
    _state_equal(eng_f.dev, eng_s.dev, "engine-read")
    for cid in range(1, n + 1):
        # scalar oracle releases == engine releases, for BOTH backends:
        # same batches, same (bit-identical) confirmation indices.
        # Sorted: a fused block egresses confirmed slots in slot order,
        # the oracle records them in echo order — same multiset.
        assert sorted(rel_f[cid]) == sorted(orc_f[cid].released), cid
        assert sorted(rel_s[cid]) == sorted(orc_s[cid].released), cid
        assert sorted(rel_f[cid]) == sorted(rel_s[cid]), cid
    # the workload actually confirmed something
    assert sum(len(v) for v in rel_f.values()) > 0


def test_read_single_round_dense_matches_fused_single():
    """step() (single-round dense kernel) ≡ step_rounds with one round —
    the two read-capable dispatch shapes."""
    a, b = _build(4), _build(4)
    for eng in (a, b):
        eng.ack(1, 2, 4)
        sl = eng.stage_read(1, count=5)
        eng.read_ack(1, 2, sl)
        eng.read_ack(1, 3, sl)
    ra = a.step(do_tick=False)
    b.begin_round()
    rb = b.step_rounds(do_tick=False)
    _state_equal(a.dev, b.dev, "single-vs-fused")
    assert ra.reads == rb.reads
    assert ra.reads[0][3] == 5


# ----------------------------------------------------------------------
# ISSUE acceptance corners: recycle / leader change with pending ctxs
# ----------------------------------------------------------------------


def test_read_membership_recycle_mid_block_purges_pending():
    """A recycle mid-block kills the old tenant's pending read ctxs (the
    scalar twin builds a fresh ReadIndex): batches sealed into closed
    pre-recycle rounds are DROPPED — even with quorum echoes staged — a
    confirmation there could only egress misattributed to the new
    tenant, and reads are droppable by contract.  The NEW tenant's reads
    staged in the same block confirm normally."""
    eng = _build(6)
    s_old = eng.stage_read(3, count=7)   # old tenant
    eng.read_ack(3, 2, s_old)            # even a full echo quorum...
    eng.read_ack(3, 3, s_old)
    eng.begin_round()
    eng.stage_recycle(3, 103, term=2, term_start=1, last_index=1)
    s_new = eng.stage_read(103, count=2)
    eng.read_ack(103, 2, s_new)
    eng.begin_round()
    res = eng.step_rounds(do_tick=False)
    # ...yields no release for the dead tenant, and no misattribution
    assert res.reads == [(103, s_new, 0, 2)]
    # device slots of the new tenant's row carry no leftovers
    row = eng.groups[103].row
    assert int(np.asarray(eng.dev.read_count)[row].sum()) == 0
    assert eng.read_slots_free(103) == eng.n_read_slots


def test_read_pending_from_earlier_dispatch_dies_with_recycle():
    """A batch staged and DISPATCHED (unconfirmed) in block i must not
    confirm after a block i+1 recycle: the in-program row reset clears
    the carried read slots."""
    eng = _build(6)
    s_old = eng.stage_read(4, count=3)
    eng.step(do_tick=False)              # dispatched, still pending
    assert int(np.asarray(eng.dev.read_count)[eng.groups[4].row].sum()) == 3
    eng.stage_recycle(4, 104, term=2, term_start=1, last_index=1)
    s_new = eng.stage_read(104, count=1)
    eng.read_ack(104, 2, s_new)
    eng.begin_round()
    res = eng.step_rounds(do_tick=False)
    assert res.reads == [(104, s_new, 0, 1)]
    row = eng.groups[104].row
    assert int(np.asarray(eng.dev.read_count)[row].sum()) == 0
    del s_old


def test_read_leader_change_with_pending_ctxs():
    """Leader changes with pending read ctxs: the reads die with the
    leadership, exactly like the scalar path's fresh ReadIndex — even
    when the echoes that would have confirmed them are already staged
    (same open round: the epoch purge drops them), and even when the
    batch already DISPATCHED and sits pending on the device (the
    transition's row upload clears the slots)."""
    # (a) stage + quorum echoes in the OPEN round, then the transition:
    # every staged event dies with the epoch bump (single-round-path
    # semantics; mid-block host transitions are out of contract and must
    # split the block — engine.step_rounds docstring)
    eng = _build(6)
    orc = ReadIndex()
    ctx = SystemCtx(low=9, high=0)
    orc.add_request(5, ctx, 0)
    sl = eng.stage_read(2, count=3, index=5)
    eng.read_ack(2, 2, sl)
    eng.read_ack(2, 3, sl)     # quorum echoes staged...
    eng.set_follower(2, term=3)
    orc2 = ReadIndex()         # scalar twin: become_follower resets
    eng.begin_round()
    res = eng.step_rounds(do_tick=False)
    assert res.reads == []
    assert orc2.confirm(ctx, 2, 2) == []   # oracle agrees: nothing pending
    row = eng.groups[2].row
    assert int(np.asarray(eng.dev.read_count)[row].sum()) == 0
    # a fresh leader term serves new reads again
    eng.set_leader(2, term=4, term_start=6, last_index=6)
    sl = eng.stage_read(2, count=1, index=6)
    eng.read_ack(2, 2, sl)
    res = eng.step(do_tick=False)
    assert res.reads == [(2, sl, 6, 1)]

    # (b) batch dispatched and pending on device, THEN the leader falls:
    # the transition clears the device slots; later echoes confirm nothing
    sl = eng.stage_read(3, count=4)
    eng.step(do_tick=False)    # pending on device now
    assert int(np.asarray(eng.dev.read_count)[eng.groups[3].row].sum()) == 4
    eng.set_follower(3, term=5)
    eng.read_ack(3, 2, sl)     # stale echo after the fall
    eng.read_ack(3, 3, sl)
    res = eng.step(do_tick=False)
    assert res.reads == []
    assert int(np.asarray(eng.dev.read_count)[eng.groups[3].row].sum()) == 0


def test_read_slot_backpressure_and_cancel():
    eng = _build(4)
    slots = [eng.stage_read(1) for _ in range(eng.n_read_slots)]
    with pytest.raises(RuntimeError):
        eng.stage_read(1)
    assert eng.read_slots_free(1) == 0
    # cancelling one frees it for the NEXT round (not the current one)
    eng.cancel_read(1, slots[0])
    with pytest.raises(RuntimeError):
        eng.stage_read(1)
    eng.begin_round()
    s2 = eng.stage_read(1)
    assert s2 == slots[0]
    res = eng.step(do_tick=False)
    assert res.reads == []  # nothing echoed, nothing confirmed
    # unconfirmed batches survive the dispatch and confirm LATER
    eng.read_ack(1, 2, slots[1])
    res = eng.step(do_tick=False)
    assert [(c, s, n) for c, s, _i, n in res.reads] == [(1, slots[1], 1)]


def test_read_pipelined_step_rounds_equivalent():
    """Read egress through pipelined double-buffering == synchronous,
    one block late."""
    a, b = _build(4), _build(4)
    got_a, got_b = [], []
    for blk in range(3):
        for eng, got in ((a, got_a), (b, got_b)):
            sl = eng.stage_read(1, count=blk + 1)
            eng.read_ack(1, 2, sl)
            eng.begin_round()
        got_a.append(a.step_rounds(do_tick=False).reads)
        rb = b.step_rounds(do_tick=False, pipelined=True)
        if rb is not None:
            got_b.append(rb.reads)
    final = b.harvest()
    got_b.append(final.reads)
    _state_equal(a.dev, b.dev, "read-pipelined")
    assert got_a == got_b


def test_read_rebase_shifts_pending_watermark():
    """rebase with a batch PENDING ON DEVICE: the slot's rel watermark
    shifts with the base (clamped at the new floor — the release index
    may only move UP, which ReadIndex permits) so the eventual absolute
    release index is preserved.  Like staged acks, events still in the
    staging buffers at rebase time are the caller's contract to avoid —
    the rare-path callers purge or drain first."""
    eng = _build(4)
    eng.ack(1, 1, 9)
    eng.ack(1, 2, 9)
    eng.step(do_tick=False)
    assert eng.committed_index(1) == 9
    sl = eng.stage_read(1, count=1)  # captured at abs 9 (rel 9)
    eng.step(do_tick=False)          # batch now pending on device
    eng.rebase(1)                    # base -> 9, pending rel 9 -> 0
    eng.read_ack(1, 2, sl)
    res = eng.step(do_tick=False)
    assert res.reads == [(1, sl, 9, 1)]  # abs index preserved


# ----------------------------------------------------------------------
# one rule written twice: single-op scalar path == vectorized block path
# ----------------------------------------------------------------------


def _read_host_state(eng):
    return {
        name: getattr(eng, name).copy()
        for name in ("_read_busy", "_read_freed_round", "_read_next_slot",
                     "_read_echo_host")
    }


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("observer", [False, True],
                         ids=["voters", "observer"])
@pytest.mark.parametrize("n_peers", [3, 5])
@pytest.mark.parametrize("read_slots", [1, 4])
def test_read_single_op_path_equals_block_path(
    read_slots, n_peers, observer, seed
):
    """``stage_read`` / ``read_ack`` pick slots and predict confirmations
    on scalars (the live drain stages one op at a time and must not hand
    the interpreter away per op); ``stage_read_block`` /
    ``read_ack_block`` do it vectorized.  The same random sequence of
    stage / echo / cancel / round-advance / leader-change ops through
    both leaves identical host bookkeeping after EVERY op, refuses the
    same stages, and confirms the same reads at every step."""
    g = 6
    one = _build(g, n_peers, read_slots=read_slots, observer=observer)
    blk = _build(g, n_peers, read_slots=read_slots, observer=observer)
    rng = random.Random(1000 * seed + 10 * n_peers + read_slots + observer)
    peers = list(range(2, n_peers + 1))  # the observer, if any, echoes too
    term = {cid: 1 for cid in range(1, g + 1)}
    rnd = 0
    # driver view of slots a stage returned: cid -> {slot: round staged}
    staged = {cid: {} for cid in range(1, g + 1)}
    refusals = confirmed = cancels = 0

    def same(tag):
        a, b = _read_host_state(one), _read_host_state(blk)
        for name in a:
            assert np.array_equal(a[name], b[name]), (tag, name)

    def stage_blk(cids):
        rows = [blk.groups[c].row for c in cids]
        rels = [blk._rel(blk.groups[c], blk.committed_index(c))
                for c in cids]
        return blk.stage_read_block(
            np.array(rows, np.int32), np.array(rels, np.int32),
            np.array([1 + c % 3 for c in cids], np.int32),
        )

    for step in range(160):
        op = rng.random()
        cid = rng.randrange(1, g + 1)
        if op < 0.30:                                   # one stage
            idx = one.committed_index(cid)
            assert idx == blk.committed_index(cid)
            try:
                slot = one.stage_read(cid, count=1 + cid % 3, index=idx)
            except RuntimeError:
                slot = None
            try:
                got = int(stage_blk([cid])[0])
            except RuntimeError:
                got = None
            assert got == slot, (step, cid)
            if slot is None:
                refusals += 1
            else:
                staged[cid][slot] = rnd
        elif op < 0.38:                  # stages across rows, one block
            cids = [c for c in rng.sample(range(1, g + 1), 3)
                    if one.read_slots_free(c) > 0]
            if cids:
                slots = [one.stage_read(c, count=1 + c % 3,
                                        index=one.committed_index(c))
                         for c in cids]
                assert [int(x) for x in stage_blk(cids)] == slots
                for c, sl in zip(cids, slots):
                    staged[c][sl] = rnd
        elif op < 0.68:                  # echoes, one or several a call
            picks = []
            for _ in range(rng.choice((1, 1, 2, 4))):
                c = rng.randrange(1, g + 1)
                # any slot: echoes for free slots must be harmless too
                picks.append((c, rng.randrange(read_slots),
                              rng.choice(peers)))
            for c, sl, nid in picks:
                one.read_ack(c, nid, sl)
            blk.read_ack_block(
                np.array([blk.groups[c].row for c, _s, _n in picks]),
                np.array([sl for _c, sl, _n in picks]),
                np.array([blk.groups[c].slots[nid] for c, _s, nid in picks]),
            )
        elif op < 0.76:   # cancel a batch of an EARLIER round (the live
            # coordinator cancels after a dispatch; a block stage and a
            # single cancel of one slot in one round have no order)
            old = [sl for sl, r in staged[cid].items() if r < rnd]
            if old:
                sl = rng.choice(old)
                one.cancel_read(cid, sl)
                blk.cancel_read(cid, sl)
                del staged[cid][sl]
                cancels += 1
        elif op < 0.82:                                 # leader change
            term[cid] += 2
            last = one.committed_index(cid) + 1
            for eng in (one, blk):
                eng.set_follower(cid, term=term[cid] - 1)
                eng.set_leader(cid, term=term[cid], term_start=last,
                               last_index=last)
            staged[cid].clear()
        else:                                           # round advance
            ra = one.step(do_tick=False)
            rb = blk.step(do_tick=False)
            assert ra.reads == rb.reads, step
            confirmed += len(ra.reads)
            for c, sl, _idx, _n in ra.reads:
                staged[c].pop(sl, None)
            rnd += 1
        same((step, op))
    assert one.step(do_tick=False).reads == blk.step(do_tick=False).reads
    _state_equal(one.dev, blk.dev, "single-vs-block")
    # the sequence exercised what it claims to
    assert confirmed and refusals and cancels


# ----------------------------------------------------------------------
# live coordinator: reads batched per round, device-confirmed
# ----------------------------------------------------------------------


def _coord_leading(cid):
    """A tick-less coordinator whose one group is led by a scalar raft
    behind a fake node; returns ``(coord, raft, confirms, echoes)`` —
    the lists the node's read-confirm / scalar read-echo offloads fill.
    Registration dirt is absorbed: the next round is the caller's."""
    from dragonboat_tpu.raft import InMemLogDB
    from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator
    from tests.raft_harness import new_test_raft

    coord = TpuQuorumCoordinator(capacity=8, n_peers=4, drive_ticks=False)
    r = new_test_raft(1, [1, 2, 3], 10, 1, InMemLogDB())
    r.cluster_id = cid
    r.become_candidate()
    r.become_leader()
    confirms, echoes = [], []

    class _Node:
        cluster_id = cid

        class peer:
            raft = r

        def offload_read_confirm(self, low, high, term):
            confirms.append((low, high, term))

        def offload_read_echo(self, node_id, low, high):
            echoes.append((node_id, low, high))

    n = _Node()
    coord._nodes[cid] = n
    with coord._mu:
        coord._sync_row_locked(n)
    coord.flush()
    return coord, r, confirms, echoes


def test_read_only_round_dispatches_without_ticks():
    """A staged ReadIndex ctx plus its echoes must trigger a dispatch on
    their own: with ticks off and no queued write/vote events the round
    gate has nothing else to fire on, and a gate that ignores the read
    plane leaves the ctx pending until the client times out."""
    cid = 7
    coord, r, confirms, _echoes = _coord_leading(cid)
    try:
        coord.read_stage(cid, r.log.committed, low=1, high=1, term=r.term)
        coord.read_ack_hint(cid, 2, low=1, high=1)
        coord.flush()
        assert confirms == [(1, 1, r.term)]
        assert coord.read_confirms == 1
    finally:
        coord.stop()


def _vectorized(*_a, **_k):
    raise AssertionError("vectorized read helper on the live round")


def _singly_staged_rounds():
    """A live coordinator stages, overflows, falls back, confirms,
    prefix-releases (cancel) and restages reads, one op at a time."""
    cid = 7
    coord, r, confirms, scalar_echoes = _coord_leading(cid)
    # every round below is a flush() of this thread: the round thread
    # would race it for the staged ops and fan out behind its back
    coord.stop()
    try:
        s = coord.eng.n_read_slots
        # S ctxs take the S slots, the next one overflows to the scalar
        # side; its echo is handed to the node
        for low in range(1, s + 2):
            coord.read_stage(cid, r.log.committed, low=low, high=0,
                             term=r.term)
        coord.read_ack_hint(cid, 2, low=s + 1, high=0)
        coord.flush()
        assert (coord.reads_staged, coord.reads_refused) == (s, 1)
        assert scalar_echoes == [(2, s + 1, 0)]
        assert coord.read_fallback_causes["slot_overflow"] == 1
        assert coord.eng.read_slots_free(cid) == 0
        # ctx S-1 confirms (self + one echo of three voters): the ctxs
        # before it are prefix-released scalar-side, their slots cancelled
        coord.read_ack_hint(cid, 2, low=s - 1, high=0)
        coord.flush()
        assert confirms == [(s - 1, 0, r.term)]
        assert coord.read_confirms == 1 and coord.read_acks == 1
        assert [e[1] for e in coord._read_pending[cid]] == [s]
        # the other follower's echo for the confirmed ctx: scalar no-op
        coord.read_ack_hint(cid, 3, low=s - 1, high=0)
        coord.flush()  # dispatches the cancels; their slots reusable now
        assert coord.read_fallback_causes["after_confirm"] == 1
        assert coord.eng.read_slots_free(cid) == s - 1
        # a freed slot is staged again and confirms, releasing ctx S too
        coord.read_stage(cid, r.log.committed, low=s + 2, high=0,
                         term=r.term)
        coord.read_ack_hint(cid, 3, low=s + 2, high=0)
        coord.flush()
        assert confirms == [(s - 1, 0, r.term), (s + 2, 0, r.term)]
        assert coord.reads_staged == s + 1
        assert not coord._read_pending[cid]
    finally:
        coord.stop()
    return coord


def test_live_drain_stays_off_vectorized_read_helpers(monkeypatch):
    """The coordinator's drain stages ONE read op at a time, on a round
    thread that shares the interpreter with every raft worker; the
    vectorized helpers' index-array calls hand the interpreter away per
    op there (tens of ms of every round, PERF.md PR 27).  With both
    helpers raising, a live coordinator still stages, overflows, falls
    back, confirms, prefix-releases (cancel) and restages."""
    monkeypatch.setattr(BatchedQuorumEngine, "_free_read_slot", _vectorized)
    monkeypatch.setattr(
        BatchedQuorumEngine, "_predict_read_confirm", _vectorized
    )
    _singly_staged_rounds()


def test_live_step_stays_off_vectorized_read_helpers(monkeypatch):
    """The same rounds between the drain and the fan-out (PERF.md PR 35):
    singly staged reads are gathered, stored into the ingress block and
    decoded off the egress block on scalars, for the (row, slot) pairs
    the round's events name.  With the block-arity gather, the
    index-array stores and the whole-plane decode raising too, the
    rounds run as before, and the ingress reset refills no read section
    whole."""
    from dragonboat_tpu.ops import packed

    for name in ("_free_read_slot", "_predict_read_confirm", "_live_blocks",
                 "_gather_reads", "_stage_read_blocks", "_translate_reads"):
        monkeypatch.setattr(BatchedQuorumEngine, name, _vectorized)
    by_cells, whole = [], []
    reset = packed.Ingress.reset

    def noting(self):
        for name, _view, _fill in self._fills:
            (by_cells if name in self.cells else whole).append(name)
        reset(self)

    monkeypatch.setattr(packed.Ingress, "reset", noting)
    _singly_staged_rounds()
    assert "read_echo" in by_cells and "ack" in by_cells
    assert not [n for n in whole if n.startswith("read_")]


def test_live_coordinator_batches_read_confirmations():
    """3-replica cluster on the tpu engine: linearizable reads flow
    through the device read plane (staged ctxs, per-round fused echo
    quorum, scalar prefix release) and return correct values; the
    coordinator's confirm counter proves the device — not the scalar
    fallback — served them."""
    from dragonboat_tpu import Config, NodeHostConfig, Result
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.statemachine import IStateMachine
    from dragonboat_tpu.transport import ChanRouter, ChanTransport

    CID = 31

    class KVSM(IStateMachine):
        def __init__(self, cluster_id, node_id):
            self.kv = {}

        def update(self, cmd):
            k, v = cmd.decode().split("=", 1)
            self.kv[k] = v
            return Result(value=len(self.kv))

        def lookup(self, query):
            return self.kv.get(query)

        def save_snapshot(self, w, files, done):
            w.write(repr(sorted(self.kv.items())).encode())

        def recover_from_snapshot(self, r, files, done):
            import ast

            self.kv = dict(ast.literal_eval(r.read(-1).decode()))

    router = ChanRouter()
    addrs = {i: f"rc{i}:1" for i in range(1, 4)}
    nhs = [
        NodeHost(
            NodeHostConfig(
                node_host_dir=":memory:",
                rtt_millisecond=5,
                raft_address=addrs[i],
                raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                    src, rh, ch, router=router
                ),
                expert=ExpertConfig(quorum_engine="tpu", engine_block_groups=64),
            )
        )
        for i in range(1, 4)
    ]
    try:
        for i, nh in enumerate(nhs, start=1):
            nh.start_cluster(
                addrs, False, KVSM,
                Config(
                    cluster_id=CID, node_id=i,
                    election_rtt=10, heartbeat_rtt=1,
                ),
            )
        deadline = time.time() + 15
        while time.time() < deadline:
            if any(nh.get_leader_id(CID)[1] for nh in nhs):
                break
            time.sleep(0.01)
        s = nhs[0].get_noop_session(CID)
        for i in range(8):
            nhs[0].sync_propose(s, f"k{i}=v{i}".encode(), timeout=30.0)
        for i in range(8):
            assert nhs[0].sync_read(CID, f"k{i}", timeout=30.0) == f"v{i}"
        # the device plane (not the scalar fallback) confirmed reads on
        # whichever host leads the group
        confirms = sum(
            nh.quorum_coordinator.read_confirms for nh in nhs
        )
        assert confirms > 0, [
            (nh.quorum_coordinator.read_confirms,
             nh.quorum_coordinator.read_fallbacks)
            for nh in nhs
        ]
        # and the leader's raft is wired into the read plane
        assert any(
            n.peer.raft.device_reads
            for nh in nhs
            for n in [nh._clusters.get(CID)]
            if n is not None and n.peer is not None
        )
    finally:
        for nh in nhs:
            nh.stop()


def test_a_prompt_release_keeps_the_guards_and_answers_once(monkeypatch):
    """ISSUE 40: a confirmed context is released in the turn the fan-out
    wakes, no longer at the group's next tick.  Three hosts under a clock
    that never ticks (so nothing but the flagged confirmation opens the
    leader's gate), the leader's echoes swallowed so every context stays
    pending until the test confirms it as the coordinator does:

    - a confirmation releases its context and every one queued before it,
      each requester answered ONCE with its OWN context (the benchmark's
      ``correct`` would not see a second READ_INDEX_RESP); a repeated
      confirmation and one of another term release nothing;
    - a confirmation flagged at term ``t``, the leadership gone to ``t + 1``
      before the woken turn runs: the turn opens the gate (the effect is
      counted as delivered), releases nothing and answers no requester.
    """
    from dragonboat_tpu import Config, NodeHostConfig
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.testing import CounterSM
    from dragonboat_tpu.transport import ChanRouter, ChanTransport
    from dragonboat_tpu.wire import MessageType
    from tests.loadwait import wait_until

    CID = 4031
    FOREVER_S = 1e9  # request clocks count ticks, and none comes

    router = ChanRouter()
    addrs = {i: f"rg{i}:1" for i in (1, 2, 3)}
    nhs = {
        i: NodeHost(NodeHostConfig(
            node_host_dir=":memory:",
            rtt_millisecond=1_000_000,
            raft_address=addrs[i],
            enable_metrics=True,
            raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                src, rh, ch, router=router),
            expert=ExpertConfig(quorum_engine="tpu", engine_block_groups=8,
                                engine_warm_fused=False),
        ))
        for i in (1, 2, 3)
    }
    try:
        for i, nh in nhs.items():
            nh.start_cluster(
                addrs, False, CounterSM,
                Config(cluster_id=CID, node_id=i, election_rtt=10,
                       heartbeat_rtt=1))
        node = nhs[1].get_node(CID)

        def led_by_1():
            if all(nh.get_leader_id(CID) == (1, True)
                   for nh in nhs.values()):
                return True
            node.request_campaign()
            return False

        wait_until(led_by_1, timeout=60.0, interval=0.2, what="host 1 leads")
        nhs[1].sync_propose(
            nhs[1].get_noop_session(CID), b"w", timeout=FOREVER_S)
        r = node.peer.raft
        term = r.term
        monkeypatch.setattr(
            nhs[1].quorum_coordinator, "read_ack_hint", lambda *a, **k: None)
        answered = []  # every READ_INDEX_RESP that leaves the leader
        send = r.send

        def noting(m):
            if m.type == MessageType.READ_INDEX_RESP:
                answered.append((m.to, m.hint, m.hint_high))
            send(m)

        monkeypatch.setattr(r, "send", noting)

        def pend(host):
            """One read at ``host``, accepted by the leader and pending."""
            have = len(r.read_index.queue)
            rs = nhs[host].get_node(CID).read(FOREVER_S)
            wait_until(lambda: len(r.read_index.queue) == have + 1,
                       timeout=30.0, interval=0.01,
                       what=f"host {host}'s context at the leader")
            return rs, r.read_index.queue[-1]

        def delivered():
            return nhs[1].metrics_registry.counter_value(
                "dragonboat_node_offload_applied_total",
                {"kind": "read_confirm"})

        def confirm(ctx, at_term):
            """What the coordinator's fan-out does, and the woken turn."""
            before = delivered()
            node.offload_read_confirm(ctx.low, ctx.high, at_term)
            wait_until(lambda: delivered() == before + 1, timeout=30.0,
                       interval=0.01, what="the woken turn")
            with node.raft_mu:  # the turn has left the group
                assert not node._off_reads

        (rs1, c1), (rs2, c2), (rs3, c3), (rs4, c4) = (
            pend(1), pend(2), pend(3), pend(2))
        assert r.read_index.queue == [c1, c2, c3, c4]
        confirm(c3, term)
        assert all(rs.wait(30.0).completed for rs in (rs1, rs2, rs3))
        assert answered == [(2, c2.low, c2.high), (3, c3.low, c3.high)]
        assert r.read_index.queue == [c4]
        confirm(c3, term)        # the echo that raced: nothing left of it
        confirm(c4, term - 1)    # tallied under another term: refused
        assert len(answered) == 2 and r.read_index.queue == [c4]
        # leadership moves between the flag and the turn it wakes
        before = delivered()
        with node.raft_mu:
            node.offload_read_confirm(c4.low, c4.high, term)
            r.become_follower(term + 1, 0)
        wait_until(lambda: delivered() == before + 1, timeout=30.0,
                   interval=0.01, what="the woken turn")
        with node.raft_mu:
            assert not node._off_reads and not r.is_leader()
            assert not r.ready_to_read
        assert len(answered) == 2
        assert not rs4.wait(0.2).completed
    finally:
        for nh in nhs.values():
            nh.stop()
