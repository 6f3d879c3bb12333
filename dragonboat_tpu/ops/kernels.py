"""Pure jit kernels for the batched quorum engine.

Each kernel is the tensorized twin of a scalar hot loop in
:mod:`dragonboat_tpu.raft.raft`; the differential tests in
``tests/test_ops_quorum.py`` (and the live-path suites
``tests/test_tpuquorum.py``, ``tests/test_raft_etcd_tpu.py``,
``tests/test_device_ticks.py``) assert bit-identical outputs against it.

Scalar twin map:

===================  ==================================================
kernel               scalar twin (reference location)
===================  ==================================================
``commit_quorum``    ``Raft.try_commit`` (``raft.go:861-909``)
``vote_tally``       ``Raft.handle_vote_resp`` (``raft.go:1062-1080``)
``check_quorum``     ``Raft.leader_has_quorum`` (``raft.go:380-390``)
``tick_step``        ``Raft.tick`` (``raft.go:553-623``)
``quorum_step``      one whole ``processSteps`` round (``execengine.go:923``)
===================  ==================================================

All shapes are static: ``G`` groups × ``P`` peer slots, event batches
padded to a fixed ``K`` with a validity mask (invalid rows scatter out of
bounds with ``mode='drop'``).  Everything fuses into one XLA program; on
TPU the sort/scatter work sits in VMEM with no host round-trips.

Every kernel is also PLACEMENT-AGNOSTIC by construction: no collective
primitive appears anywhere in this module, because no per-group update
ever reads another group's row — the group axis is embarrassingly
parallel.  That property is what the mesh dispatch plane
(``ops/mesh.py``, ISSUE 16) builds on: instead of one GSPMD-partitioned
program whose compiled collectives forced a global dispatch mutex, each
mesh shard launches these SAME kernels as ordinary single-device
programs over its group partition, from its own stream, with no
cross-shard rendezvous to deadlock and therefore no lock to serialize
behind.  A kernel change here is automatically a change on every shard;
keep the no-collectives invariant or the mesh plane's concurrency story
breaks.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .state import (
    QUIESCE_MARK_SLEEP, QUIESCE_MARK_WAKE,
    CANDIDATE,
    INDEX_MIN,
    LEADER,
    QuorumState,
    VOTE_NONE,
    I32,
)


# Optimal compare-exchange networks (Knuth TAOCP v3 §5.3.4) per width;
# each pair (i, j) with i < j exchanges so the LARGER value lands at i —
# after the full network the columns are sorted descending.  A comparator
# network sorts under either orientation as long as every comparator uses
# the same one.
_SORT_NETWORKS = {
    1: [],
    2: [(0, 1)],
    3: [(0, 1), (1, 2), (0, 1)],
    4: [(0, 1), (2, 3), (0, 2), (1, 3), (1, 2)],
    5: [(0, 1), (3, 4), (2, 4), (2, 3), (1, 4), (0, 3), (0, 2), (1, 3),
        (1, 2)],
    6: [(1, 2), (4, 5), (0, 2), (3, 5), (0, 1), (3, 4), (2, 5), (0, 3),
        (1, 4), (2, 4), (1, 3), (2, 3)],
    7: [(1, 2), (3, 4), (5, 6), (0, 2), (3, 5), (4, 6), (0, 1), (4, 5),
        (2, 6), (0, 4), (1, 5), (0, 3), (2, 5), (1, 3), (2, 4), (2, 3)],
    8: [(0, 1), (2, 3), (4, 5), (6, 7), (0, 2), (1, 3), (4, 6), (5, 7),
        (1, 2), (5, 6), (0, 4), (3, 7), (1, 5), (2, 6), (1, 4), (3, 6),
        (2, 4), (3, 5), (3, 4)],
}


def _kth_largest(values: jax.Array, mask: jax.Array, k: jax.Array) -> jax.Array:
    """Row-wise k-th largest of masked values; k is 1-based, (G,).

    For the practical peer widths (P ≤ 8) this unrolls an optimal
    compare-exchange sorting network over the P columns — pure
    elementwise ``maximum``/``minimum`` on (G,) vectors that the VPU
    streams, with no sort HLO and no (G,P,P) intermediate.  At the
    131k-group × P=3 headline shape this measured ~3× cheaper than the
    previous (G,P,P) rank-select, which itself was ~5× cheaper than
    ``jnp.sort``'s padded bitonic lowering.  Wider P falls back to the
    rank form: each element's descending rank is the count of elements
    that beat it (value, then slot index as the tie-break); ranks are a
    permutation of 0..P-1, so exactly one element has rank k-1 and a
    masked sum selects it.  Both forms return the identical *value*
    (ties share the value); only selection strategy differs.

    Precondition: ``1 <= k <= P`` per row (the only caller,
    ``commit_quorum``, passes ``quorum = voters//2 + 1`` which the
    engine keeps in range — ``engine.py`` add_group/membership paths).
    Out-of-range k is unspecified and the two forms disagree on it.
    """
    masked = jnp.where(mask, values, INDEX_MIN)
    p = masked.shape[1]
    ksel = k - 1
    if p in _SORT_NETWORKS:
        cols = [masked[:, i] for i in range(p)]
        for i, j in _SORT_NETWORKS[p]:
            hi = jnp.maximum(cols[i], cols[j])
            cols[j] = jnp.minimum(cols[i], cols[j])
            cols[i] = hi
        out = cols[0]
        for i in range(1, p):  # cols sorted descending; pick column k-1
            out = jnp.where(ksel == i, cols[i], out)
        return out
    v_i = masked[:, :, None]  # candidate
    v_j = masked[:, None, :]  # competitor
    slot = jnp.arange(p, dtype=I32)
    beats = (v_j > v_i) | (
        (v_j == v_i) & (slot[None, None, :] < slot[None, :, None])
    )
    rank = jnp.sum(beats, axis=2).astype(I32)  # 0-based, descending, unique
    sel = rank == ksel[:, None]
    return jnp.sum(jnp.where(sel, masked, 0), axis=1)


def _self_column(match: jax.Array, self_slot: jax.Array) -> jax.Array:
    """``match[g, self_slot[g]]`` for every group, as an elementwise
    one-hot masked sum.  The obvious ``take_along_axis`` compiles to a
    TPU gather that measured 1.42 ms/round at the 131k-group headline
    shape — 5× the cost of everything else in the round combined; this
    form is free (fuses into the surrounding elementwise ops).  Rows
    whose ``self_slot`` is out of range (dead rows) contribute 0, which
    the ``max`` against ``last_index`` ignores — same net effect as the
    gather's clamp.  match values are rel indexes ≥ 0, so 0 is the
    identity."""
    p = match.shape[1]
    sel = jax.nn.one_hot(self_slot, p, dtype=jnp.bool_)
    return jnp.sum(jnp.where(sel, match, 0), axis=1)


def commit_quorum(
    match: jax.Array, voting: jax.Array, quorum: jax.Array
) -> jax.Array:
    """Quorum match index per group (scalar twin: ``Raft.try_commit``).

    The reference sorts each group's match array and picks
    ``matched[n - quorum]`` (``raft.go:888-909``); that is exactly the
    quorum-th largest, computed here for all groups at once.
    """
    return _kth_largest(match, voting, quorum)


def vote_tally(
    votes: jax.Array, voting: jax.Array, quorum: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """(granted, rejected) counts per group (twin: ``handle_vote_resp``)."""
    granted = jnp.sum((votes == 1) & voting, axis=1).astype(I32)
    rejected = jnp.sum((votes == 0) & voting, axis=1).astype(I32)
    return granted, rejected


def check_quorum(
    active: jax.Array,
    voting: jax.Array,
    self_slot: jax.Array,
    quorum: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """(has_quorum, cleared_active) per group (twin: ``leader_has_quorum``).

    Counts self plus recently-active voters, clearing activity flags as the
    reference does (``raft.go:380-390``).
    """
    p = active.shape[1]
    self_onehot = jax.nn.one_hot(self_slot, p, dtype=jnp.bool_)
    count = jnp.sum((active | self_onehot) & voting, axis=1).astype(I32)
    cleared = active & ~voting  # voting members' activity is consumed
    return count >= quorum, cleared


class TickFlags(NamedTuple):
    elect_due: jax.Array    # (G,) bool — non-leader election timeout fired
    hb_due: jax.Array       # (G,) bool — leader heartbeat due
    checkq_demote: jax.Array  # (G,) bool — CheckQuorum failed, leader must step down
    # (G,) bool — the row's idle clock crossed its threshold on this tick
    # and the row went to sleep (all False without ``has_quiesce``)
    quiesce_enter: jax.Array


# Device telemetry fold (ISSUE 20).  Every aggregate shape is STATIC, so
# the telemetry egress per dispatch is fixed-size no matter how many
# groups the shard holds — the property that lets the health plane watch
# a million groups at O(shards) host cost instead of an O(G) Python walk.
TELEM_LAG_BUCKETS = 16
TELEM_STATES = 5   # FOLLOWER..WITNESS (state.py raft states)
TELEM_TOPK = 8


class TelemAggregate(NamedTuple):
    """Fixed-size per-shard health aggregate (:func:`telem_fold`).

    ``lag`` throughout is the DEVICE-visible commit lag
    ``last_index - committed`` — entries appended but not yet quorum-
    committed.  The host-side committed−applied apply lag remains a
    per-group host signal: the aggregate sampler reads it only for the
    drill-down set this aggregate names (top-K worst rows plus
    non-device groups), which is the point of the fold.
    """

    lag_hist: jax.Array      # (B,) i32 — live groups per log2 lag bucket
    state_counts: jax.Array  # (TELEM_STATES,) i32 — live groups per raft state
    stalled: jax.Array       # () i32 — live, lag > 0, committed flat since last fold
    read_slots: jax.Array    # () i32 — occupied ReadIndex slots (read_count > 0)
    kv_ents: jax.Array       # () i32 — occupied devsm entry slots (index >= 0)
    topk_row: jax.Array      # (K,) i32 — worst rows by lag; -1 = fewer than K live
    topk_lag: jax.Array      # (K,) i32 — their lag values


def telem_fold(
    st: QuorumState, k: int = TELEM_TOPK,
    count_reads: bool = True, count_kv: bool = True,
) -> tuple[QuorumState, TelemAggregate]:
    """Reduce per-group health signals into one :class:`TelemAggregate`.

    Pure masked reductions over the group axis — no collectives (the
    module invariant), no new input tensors, so the fold rides any
    dispatch for a handful of VPU passes over state already in HBM.
    Also advances ``telem_prev_committed`` to this fold's commit
    watermark: the stalled predicate compares against the PREVIOUS
    fold, giving "commitIndex flat across a whole dispatch window with
    pending work" rather than a noisy within-round flatline.
    """
    live = st.live
    lag = jnp.where(live, jnp.maximum(st.last_index - st.committed, 0), 0)
    # Exact integer log2 bucketing: bucket = #{i < B-1 : lag >= 2^i}
    # (0→0, 1→1, 2..3→2, …, ≥2^(B-2)→B-1).  Float log2 would disagree
    # with the integer host oracle near power-of-two boundaries
    # (float32 rounds 2^25 − 1 up across the bucket edge).
    # searchsorted(side="right") counts thresholds <= lag — identical to
    # summing (lag >= 2^i) but a binary search per element instead of a
    # (G, B-1) compare matrix.
    thresholds = jnp.asarray(
        [1 << i for i in range(TELEM_LAG_BUCKETS - 1)], I32
    )
    bucket = jnp.searchsorted(thresholds, lag, side="right").astype(I32)
    # Counting via (G, buckets) compare-matrix column sums — NOT
    # scatter-add and NOT one-hot matmul.  Scatter lowers to a
    # serialized per-update loop on the cpu backend (~0.1 ms per
    # scatter at G=1024, dominating the fold) and one-hot matmuls
    # materialize float intermediates; a bool compare plus integer
    # column reduction is a handful of fully-vectorized passes over
    # G×16 / G×5 elements.
    bucket_ids = jnp.arange(TELEM_LAG_BUCKETS, dtype=I32)
    lag_hist = jnp.sum(
        (bucket[:, None] == bucket_ids[None, :]) & live[:, None],
        axis=0, dtype=I32,
    )
    state_ids = jnp.arange(TELEM_STATES, dtype=I32)
    state_counts = jnp.sum(
        (st.node_state.astype(I32)[:, None] == state_ids[None, :])
        & live[:, None],
        axis=0, dtype=I32,
    )
    stalled = jnp.sum(
        live & (st.committed == st.telem_prev_committed) & (lag > 0)
    ).astype(I32)
    # Slot-occupancy reductions gate on the caller's plane latches: when
    # a plane has never been used its arrays are provably all-idle, so
    # the count is the constant 0 and the (G, S)/(G, E) sweeps vanish
    # from the program entirely.
    zero = jnp.asarray(0, I32)
    read_slots = (
        jnp.sum(st.read_count > 0).astype(I32) if count_reads else zero
    )
    kv_ents = (
        jnp.sum(st.kv_ent_index >= 0).astype(I32) if count_kv else zero
    )
    # Top-K worst rows by lag; dead rows mask to -1, sorting below any
    # live lag (≥ 0).  K sequential argmax passes, not lax.top_k: the
    # full sort top_k lowers to costs ~0.2ms at G=1024 on the cpu
    # backend (most of the fold's dispatch overhead), while K masked
    # argmax sweeps are linear in G.  argmax returns the FIRST maximal
    # index, so ties break toward the LOWER row — the host oracle sorts
    # by (-lag, row) to match bit-for-bit.
    masked = jnp.where(live, lag, -1).astype(I32)
    # an engine smaller than K egresses its whole group axis
    k = min(int(k), masked.shape[0])
    rows, lags = [], []
    for _ in range(k):  # unrolled — k is static; no while-loop overhead
        i = jnp.argmax(masked).astype(I32)
        rows.append(i)
        lags.append(masked[i])
        masked = masked.at[i].set(jnp.iinfo(jnp.int32).min)
    topk_row = jnp.stack(rows)
    topk_lag = jnp.stack(lags)
    topk_row = jnp.where(topk_lag >= 0, topk_row, -1).astype(I32)
    st = st._replace(telem_prev_committed=st.committed)
    return st, TelemAggregate(
        lag_hist, state_counts, stalled, read_slots, kv_ents,
        topk_row, topk_lag,
    )


class StepOutputs(NamedTuple):
    state: QuorumState
    committed: jax.Array    # (G,) i32 rel — post-step commit watermark
    won: jax.Array          # (G,) bool — candidate reached vote quorum
    lost: jax.Array         # (G,) bool — candidate rejected by quorum
    flags: TickFlags
    # device read plane egress (None unless has_reads): per pending-read
    # slot, the client reads confirmed this dispatch and the rel index
    # each batch was released at.  Multi-round dispatches ACCUMULATE
    # (count-sum / index-max) across their scanned rounds — safe because
    # a ReadIndex release index may only be rewritten UP (serving at a
    # higher watermark is strictly more conservative; the scalar twin's
    # prefix release does the same rewrite, readindex.py:70-74).
    read_done_count: jax.Array | None = None  # (G,S) i32
    read_done_index: jax.Array | None = None  # (G,S) i32 rel, -1 = none
    # devsm egress (None unless has_kv): per staged KV read slot, the
    # captured value and the commit watermark it was captured at (-1 =
    # slot not staged this dispatch).  The engine never restages a read
    # slot within one block, so a multi-round scan's per-round captures
    # merge by simple overwrite-where-staged.  ``kv_applied`` counts ops
    # the apply fold consumed (per group; summed across a block).
    kv_read_val: jax.Array | None = None      # (G,R) i32
    kv_read_index: jax.Array | None = None    # (G,R) i32 rel, -1 = none
    kv_applied: jax.Array | None = None       # (G,) i32
    # device telemetry egress (None unless has_telem, ISSUE 20): the
    # fixed-size aggregate telem_fold computed over the POST-step state.
    # A multi-round dispatch folds ONCE on the final scanned state — the
    # aggregate is a snapshot of where the block left the shard, not a
    # per-round accumulation (commit watermarks are monotone, so the
    # final fold is exactly the aggregate a fresh dispatch would see).
    telem: TelemAggregate | None = None


def read_confirm(
    read_acks: jax.Array,   # (G,S,P) bool — heartbeat-echo acks per slot
    read_count: jax.Array,  # (G,S) i32 — reads batched per slot (0 = free)
    voting: jax.Array,      # (G,P) bool
    self_slot: jax.Array,   # (G,) i32
    quorum: jax.Array,      # (G,) i32
    node_state: jax.Array,  # (G,) i8
    live: jax.Array,        # (G,) bool
) -> jax.Array:
    """(G,S) bool — pending-read slots whose echo quorum is reached.

    Scalar twin: ``ReadIndex.confirm`` (``raft/readindex.py:51``,
    reference ``readindex.go:77-90``): ``len(p.confirmed) + 1 >= quorum``
    — the ``+1`` is the leader counting itself, expressed here as the
    same elementwise one-hot self-column trick as :func:`_self_column`
    (a gather-free OR into the ack matrix).  The row-sum is masked by
    ``voting`` exactly like :func:`vote_tally`/:func:`check_quorum`, so
    observer echoes never count toward the quorum.  Only live LEADER
    rows confirm: a row that lost leadership keeps its (about-to-be-
    purged) slots unconfirmed, matching the scalar path dropping pending
    reads on every state transition (``raft.py become_*`` builds a fresh
    ``ReadIndex``).
    """
    p = voting.shape[1]
    self_onehot = jax.nn.one_hot(self_slot, p, dtype=jnp.bool_)  # (G,P)
    acked = (read_acks | self_onehot[:, None, :]) & voting[:, None, :]
    count = jnp.sum(acked, axis=2).astype(I32)  # (G,S)
    is_leader = (node_state == LEADER) & live
    return (count >= quorum[:, None]) & (read_count > 0) & is_leader[:, None]


def _read_plane(
    st: QuorumState,
    stage_idx: jax.Array,  # (G,S) i32 — new batch index per slot; -1 = no stage
    stage_cnt: jax.Array,  # (G,S) i32 — reads in the new batch
    ack: jax.Array,        # (G,S,P) bool — this round's heartbeat echoes
) -> tuple[QuorumState, jax.Array, jax.Array]:
    """One round of the device read plane: stage → echo ingest → confirm
    → release.  Returns ``(state, done_count, done_index)`` where the
    done arrays describe the batches released THIS round ((G,S) i32;
    index -1 where nothing confirmed).

    Staging a slot overwrites it and RESETS its acks: an echo proves
    leadership only at a time >= its own ctx's capture, so echoes of an
    older tenant of the slot must never count toward a newer batch (the
    engine's host-side slot bookkeeping avoids overwriting unconfirmed
    batches; the reset makes a violation conservative, not unsafe).
    Echoes staged in the same round as the batch DO count — the host
    sequences them after the stage, mirroring a heartbeat response
    arriving after ``add_request`` in the scalar path.
    """
    staged = stage_idx >= 0                                   # (G,S)
    read_index = jnp.where(staged, stage_idx, st.read_index)
    read_count = jnp.where(staged, stage_cnt, st.read_count)
    read_acks = jnp.where(staged[:, :, None], ack, st.read_acks | ack)
    confirmed = read_confirm(
        read_acks, read_count, st.voting, st.self_slot, st.quorum,
        st.node_state, st.live,
    )
    done_count = jnp.where(confirmed, read_count, 0)
    done_index = jnp.where(confirmed, read_index, -1)
    # release: confirmed slots free (count 0) with acks cleared; the
    # captured index is left in place (harmless — count gates everything)
    read_count = jnp.where(confirmed, 0, read_count)
    read_acks = read_acks & ~confirmed[:, :, None]
    st = st._replace(
        read_index=read_index, read_count=read_count, read_acks=read_acks
    )
    return st, done_count, done_index


def _kv_plane(
    st: QuorumState,
    ent_idx: jax.Array,   # (G,E) i32 — staged op log index per buffer slot; -1 = no stage
    ent_key: jax.Array,   # (G,E) i32 — staged op key slot
    ent_val: jax.Array,   # (G,E) i32 — staged op value
    read_key: jax.Array,  # (G,R) i32 — staged KV read keys; -1 = no read
) -> tuple[QuorumState, jax.Array, jax.Array, jax.Array]:
    """One round of the device state machine (devsm, ISSUE 11): stage →
    apply → read.  Returns ``(state, read_val, read_idx, applied)``.

    Stage: a non-``-1`` ``ent_idx`` cell overwrites its buffer slot (the
    engine's host bookkeeping only restages a slot whose previous tenant
    provably applied — the slot-occupancy rule in
    ``BatchedQuorumEngine.stage_kv_ops``).

    Apply — the fold this subsystem exists for: every buffered entry
    whose index the commit watermark has passed writes its value into
    ``kv_value[key]`` and frees its slot, in ONE ``(G,V)`` tensor update.
    Commit-order correctness without a sequential walk: ops are pure SETs,
    so the post-batch value of a key is exactly the value of its
    highest-index ready entry — selected per key by an index-max over the
    ``(G,E,V)`` key one-hot (indexes are unique per group, so exactly one
    winner exists; the selection is bit-identical to applying the batch
    sequentially in log order, which ``tests/test_devsm.py`` pins against
    the scalar oracle).  Entries above the watermark stay buffered for a
    later round — the buffer is always a suffix strictly above
    ``committed``.

    Read: staged keys gather their post-apply value plus the commit
    watermark it reflects.  Captured AFTER the fold, so a read staged in
    the round an entry commits sees it — on this plane apply == commit by
    construction, the property that lets lease/ReadIndex reads serve
    straight from device state with zero host apply.
    """
    staged = ent_idx >= 0                                     # (G,E)
    b_idx = jnp.where(staged, ent_idx, st.kv_ent_index)
    b_key = jnp.where(staged, ent_key, st.kv_ent_key)
    b_val = jnp.where(staged, ent_val, st.kv_ent_val)

    v = st.kv_value.shape[1]
    ready = (b_idx >= 0) & (b_idx <= st.committed[:, None])   # (G,E)
    key_oh = jax.nn.one_hot(b_key, v, dtype=jnp.bool_)        # (G,E,V)
    sel = ready[:, :, None] & key_oh
    masked_idx = jnp.where(sel, b_idx[:, :, None], -1)        # (G,E,V)
    win_idx = jnp.max(masked_idx, axis=1)                     # (G,V)
    is_win = sel & (masked_idx == win_idx[:, None, :]) & (
        win_idx[:, None, :] >= 0
    )
    new_val = jnp.sum(jnp.where(is_win, b_val[:, :, None], 0), axis=1)
    kv_value = jnp.where(win_idx >= 0, new_val, st.kv_value)  # (G,V)

    applied = jnp.sum(ready, axis=1).astype(I32)              # (G,)
    b_idx = jnp.where(ready, -1, b_idx)                       # free applied slots

    st = st._replace(
        kv_value=kv_value,
        kv_ent_index=b_idx,
        kv_ent_key=b_key,
        kv_ent_val=b_val,
    )
    has_read = read_key >= 0                                  # (G,R)
    read_oh = jax.nn.one_hot(read_key, v, dtype=jnp.bool_)    # (G,R,V)
    read_val = jnp.sum(
        jnp.where(read_oh, kv_value[:, None, :], 0), axis=2
    )                                                         # (G,R)
    read_val = jnp.where(has_read, read_val, 0)
    read_idx = jnp.where(has_read, st.committed[:, None], -1)
    return st, read_val, read_idx, applied


def quiesce_marks(
    st: QuorumState, mark: jax.Array, election_tick: jax.Array
) -> tuple[QuorumState, jax.Array]:
    """Apply a round's sleep / wake marks to the quiesce rows (twin:
    ``QuiesceManager.record_activity`` / ``try_enter_quiesce``).

    ``mark`` is ``(G,)``: what the round staged on the reserved last peer
    slot of the ack plane (0 nothing, ``QUIESCE_MARK_SLEEP`` a peer's
    QUIESCE, ``QUIESCE_MARK_WAKE`` any activity; the greater won the
    round).  Activity resets the idle clock, and on a sleeping row ends
    the sleep and starts BOTH raft clocks from the wake: a follower
    counted ``election_tick`` all through its sleep
    (``Raft.quiesced_tick``) and would otherwise campaign against a live
    leader on its first ordinary tick; a leader's check-quorum window
    restarts with its first heartbeat.  Returns the state and the
    election clock (the caller's, which ``track_contact`` may have reset
    already)."""
    qon = st.live & (st.quiesce_threshold > 0)
    wake = qon & (mark >= QUIESCE_MARK_WAKE)
    sleep = qon & (mark == QUIESCE_MARK_SLEEP) & ~st.quiesced
    election_tick = jnp.where(wake & st.quiesced, 0, election_tick)
    st = st._replace(
        idle_tick=jnp.where(wake | sleep, 0, st.idle_tick),
        quiesced=(st.quiesced | sleep) & ~wake,
    )
    return st, election_tick


def tick_step(
    st: QuorumState, has_quiesce: bool = False
) -> tuple[QuorumState, TickFlags]:
    """Advance per-group clocks one tick (twin: ``Raft.tick``).

    Emits *flags* for the rare follow-ups (campaign, heartbeat broadcast,
    leader step-down) which the host executes scalar-side; the dense
    counter arithmetic and CheckQuorum activity scan stay on device.

    ``has_quiesce`` (static) adds a group's sleep (twin:
    ``QuiesceManager.increase_quiesce_tick`` followed by ``Node._tick``'s
    choice of ``Raft.quiesced_tick``): an awake quiesce row's idle clock
    advances and, past the row's threshold, the row goes to sleep and
    raises ``quiesce_enter`` once; a sleeping row (the tick that put it to
    sleep included) only counts ``election_tick``: no election-due, no
    check-quorum window, no heartbeat.  Without it the idle columns pass
    through untouched and the program is the one built before they
    existed.
    """
    live = st.live
    is_leader = (st.node_state == LEADER) & live
    if has_quiesce:
        awake = live & (st.quiesce_threshold > 0) & ~st.quiesced
        idle_tick = jnp.where(awake, st.idle_tick + 1, st.idle_tick)
        quiesce_enter = awake & (idle_tick > st.quiesce_threshold)
        quiesced = st.quiesced | quiesce_enter
        ticking = ~quiesced  # a sleeping row fires nothing
        st = st._replace(idle_tick=idle_tick, quiesced=quiesced)
    else:
        quiesce_enter = jnp.zeros_like(live)
        ticking = True

    election_tick = jnp.where(live, st.election_tick + 1, st.election_tick)

    # non-leader: election timeout (raft.go:568-592)
    elect_due = (
        live
        & ~is_leader
        & st.electable
        & (election_tick >= st.rand_timeout)
        & ticking
    )
    # leader: CheckQuorum window (raft.go:594-623)
    checkq_due = is_leader & (election_tick >= st.election_timeout) & ticking
    election_tick = jnp.where(elect_due | checkq_due, 0, election_tick)

    has_q, cleared_active = check_quorum(
        st.active, st.voting, st.self_slot, st.quorum
    )
    run_checkq = checkq_due & st.check_quorum_on
    # fire on EVERY window expiry (not only when the device tally lacks a
    # quorum): the scalar CHECK_QUORUM handler is the authority and must
    # consume its per-peer activity bits once per window exactly like the
    # reference's leader_tick cadence — otherwise stale scalar bits would
    # make the first real demotion refuse (doubling stale-leader exposure)
    checkq_demote = run_checkq
    del has_q  # advisory only; the scalar re-check decides
    active = jnp.where(run_checkq[:, None], cleared_active, st.active)

    hb_ticks = is_leader & ticking
    heartbeat_tick = jnp.where(hb_ticks, st.heartbeat_tick + 1, st.heartbeat_tick)
    hb_due = hb_ticks & (heartbeat_tick >= st.heartbeat_timeout)
    heartbeat_tick = jnp.where(hb_due, 0, heartbeat_tick)

    st = st._replace(
        election_tick=election_tick,
        heartbeat_tick=heartbeat_tick,
        active=active,
    )
    return st, TickFlags(elect_due, hb_due, checkq_demote, quiesce_enter)


def quorum_step_impl(
    st: QuorumState,
    ack_g: jax.Array,      # (K,) i32 group row of each ack event
    ack_p: jax.Array,      # (K,) i32 peer slot
    ack_val: jax.Array,    # (K,) i32 rel match index acknowledged
    ack_valid: jax.Array,  # (K,) bool
    vote_g: jax.Array,     # (K,) i32
    vote_p: jax.Array,     # (K,) i32
    vote_grant: jax.Array,  # (K,) i8 — 1 grant / 0 reject
    vote_valid: jax.Array,  # (K,) bool
    do_tick: bool = True,
    track_contact: bool = True,
    has_votes: bool = True,
    has_hier: bool = False,
    has_telem: bool = False,
    telem_k: int = TELEM_TOPK,
    has_reads: bool = False,
    has_kv: bool = False,
    has_quiesce: bool = False,
) -> StepOutputs:
    """ONE fused dispatch for a whole engine round (SURVEY.md §7).

    Scalar order of operations matches ``processSteps``: ingest acks and
    votes, tally elections, advance commits, then tick clocks.  Ack
    ingestion uses scatter-max (``remote.try_update`` keeps only forward
    progress, so max is exact and order-independent → deterministic).

    ``has_votes=False`` (static) compiles out the vote-event scatter and
    gather for the common vote-free round; the tally over the standing
    ``st.votes`` still runs (flags stay idempotent across rounds exactly
    as with an empty vote batch).  The vote_* args may then be dummies.
    """
    g_total = st.term.shape[0]
    # route invalid events out of bounds; XLA drops them
    ag = jnp.where(ack_valid, ack_g, g_total)
    mark = None
    if has_quiesce:
        # events on the reserved last peer slot are a quiesce row's sleep
        # / wake marks, no acknowledgements: taken out of the ack lists
        is_mark = ack_valid & (ack_p == st.match.shape[1] - 1)
        mark = (
            jnp.zeros((g_total + 1,), I32)
            .at[jnp.where(is_mark, ack_g, g_total)].max(ack_val)[:g_total]
        )
        ag = jnp.where(is_mark, g_total, ag)

    # --- ack ingestion (twin: handleLeaderReplicateResp raft.go:1671) ---
    match = st.match.at[ag, ack_p].max(ack_val, mode="drop")
    # remote.next >= remote.match + 1 is a raft invariant every writer
    # preserves (make_state, set_leader's reset_remotes, rebase, and this
    # kernel), so the scatter-max of ack_val+1 into ``next`` equals a
    # dense max against the freshly scattered match — one scatter fewer
    # (~1ms/round at 131k groups)
    next_ = jnp.maximum(st.next, match + 1)
    active = st.active.at[ag, ack_p].set(True, mode="drop")
    # leader contact: any event touching a NON-leader row resets its
    # election clock (twin: leader_is_available / raft.go follower
    # heartbeat handling) — the host stages a zero-value ack when a
    # follower hears from its leader, so device-tick followers don't
    # campaign against a healthy leader.  Contact events are ONE-SHOT
    # (consumed by whichever round drains them), so the reset must run on
    # every round of a ticking engine — including its do_tick=False
    # rounds — or an idle follower's clock would climb to elect_due and
    # spam spurious (scalar-rejected) election flags; the ENGINE therefore
    # passes track_contact = device_ticks OR do_tick.  Compiling the
    # scatter out (~8% of the multistep round at 131k groups) is legal
    # only when the engine never ticks on device (host-driven clocks:
    # drive_ticks=False coordinators, the bench host-loop/rung sections)
    # OR no benched row is a non-leader (the reset writes are masked by
    # `contacted & nonleader` — the headline bench's explicit False).
    if track_contact:
        contacted = (
            jnp.zeros((g_total + 1,), bool).at[ag].set(True)[:g_total]
        )
        nonleader = (st.node_state != LEADER) & st.live
        election_tick = jnp.where(
            contacted & nonleader, 0, st.election_tick
        )
    else:
        election_tick = st.election_tick
    # self-acks raise last_index (leader append); followers never exceed it
    self_match = _self_column(match, st.self_slot)
    last_index = jnp.maximum(st.last_index, self_match)

    # --- vote ingestion (first vote per peer per term wins) -------------
    if has_votes:
        vg = jnp.where(vote_valid, vote_g, g_total)
        cur = st.votes[vg.clip(0, g_total - 1), vote_p]
        newv = jnp.where(cur == VOTE_NONE, vote_grant, cur)
        votes = st.votes.at[vg, vote_p].set(newv, mode="drop")
    else:
        votes = st.votes

    out = _finish_step(
        st, match, next_, active, votes, election_tick, last_index, do_tick,
        has_hier=has_hier, mark=mark,
    )
    if has_telem:
        # has_reads/has_kv carry no event planes on this path — they are
        # pure occupancy hints so the fold only sweeps read/kv slot
        # arrays that could actually be non-idle (the engine passes its
        # plane latches).
        tst, agg = telem_fold(
            out.state, telem_k,
            count_reads=has_reads, count_kv=has_kv,
        )
        out = out._replace(state=tst, telem=agg)
    return out


def _finish_step(
    st: QuorumState,
    match: jax.Array,
    next_: jax.Array,
    active: jax.Array,
    votes: jax.Array,
    election_tick: jax.Array,
    last_index: jax.Array,
    do_tick: bool,
    has_hier: bool = False,
    mark: jax.Array | None = None,
) -> StepOutputs:
    """Tally/commit/tick tail shared by the sparse and dense steps — the
    ingestion front-ends differ, the raft semantics must not.  ``mark``
    (``has_quiesce`` programs only) is the round's sleep / wake marks,
    applied before the tick as a step handles its messages before its
    ticks."""
    if mark is not None:
        st, election_tick = quiesce_marks(st, mark, election_tick)
    # --- election tally (twin: handleVoteResp / campaign) ---------------
    granted, rejected = vote_tally(votes, st.voting, st.quorum)
    is_cand = (st.node_state == CANDIDATE) & st.live
    won = is_cand & (granted >= st.quorum)
    lost = is_cand & (rejected >= st.quorum)

    # --- commit advancement (twin: try_commit raft.go:888-909) ----------
    q = commit_quorum(match, st.voting, st.quorum)
    if has_hier:
        # hier sub-quorum rule (twin: Raft._hier_try_commit, ISSUE 18):
        # the near-domain kth-largest can close ahead of the far acks;
        # the classic quorum stays the floor.  sub_quorum == 0 rows
        # (hier off / ineligible domain / non-leader) keep the classic
        # value bit-for-bit — the clamp only satisfies _kth_largest's
        # 1 <= k precondition and its result is discarded by the where.
        q_near = _kth_largest(
            match, st.voting & st.near, jnp.maximum(st.sub_quorum, 1)
        )
        q = jnp.where(st.sub_quorum > 0, jnp.maximum(q, q_near), q)
    is_leader = (st.node_state == LEADER) & st.live
    # raft paper p8: only current-term entries commit by counting; on the
    # leader q >= term_start ⟺ log.match_term(q, term) (see state.py)
    can_commit = is_leader & (q > st.committed) & (q >= st.term_start)
    committed = jnp.where(can_commit, q, st.committed)

    st = st._replace(
        match=match,
        next=next_,
        active=active,
        votes=votes,
        committed=committed,
        last_index=last_index,
        election_tick=election_tick,
    )

    if do_tick:
        st, flags = tick_step(st, has_quiesce=mark is not None)
    else:
        zeros = jnp.zeros_like(won)
        flags = TickFlags(zeros, zeros, zeros, zeros)

    return StepOutputs(st, committed, won, lost, flags)


def _entry(impl, name: str):
    """``impl`` as it is, under a program name of its own: the entries
    below take a ``QuorumState`` and separate event arrays (tests, the
    benches, the ladder harnesses); the engine's programs
    (``ops/packed.py``) carry the kernels' names in a device trace."""

    @functools.wraps(impl)
    def entry(*args, **kwargs):
        return impl(*args, **kwargs)

    entry.__name__ = entry.__qualname__ = name
    return entry


quorum_step = jax.jit(
    _entry(quorum_step_impl, "quorum_step_unpacked"),
    static_argnames=(
        "do_tick", "track_contact", "has_votes", "has_hier", "has_telem",
        "telem_k", "has_reads", "has_kv", "has_quiesce",
    ),
    donate_argnums=(0,),
)


def quorum_step_dense_impl(
    st: QuorumState,
    ack_max: jax.Array,      # (G,P) i32 — max acked rel index, 0 where untouched
    ack_touched: jax.Array,  # (G,P) bool — slot received ≥1 event this round
    vote_new: jax.Array,     # (G,P) i8 — VOTE_NONE where no vote event
    read_stage_idx: jax.Array | None = None,  # (G,S) i32, -1 = no stage
    read_stage_cnt: jax.Array | None = None,  # (G,S) i32
    read_ack: jax.Array | None = None,        # (G,S,P) bool echo events
    kv_ent_idx: jax.Array | None = None,      # (G,E) i32, -1 = no stage
    kv_ent_key: jax.Array | None = None,      # (G,E) i32
    kv_ent_val: jax.Array | None = None,      # (G,E) i32
    kv_read_key: jax.Array | None = None,     # (G,R) i32, -1 = no read
    do_tick: bool = True,
    track_contact: bool = True,
    has_votes: bool = True,
    has_reads: bool = False,
    has_kv: bool = False,
    has_hier: bool = False,
    has_telem: bool = False,
    telem_k: int = TELEM_TOPK,
    has_quiesce: bool = False,
) -> StepOutputs:
    """Dense-ingestion twin of :func:`quorum_step_impl` — zero scatters.

    Scatter-max aggregation is order-independent, so a round's sparse ack
    events collapse exactly into a per-(group, peer) **max matrix** plus a
    touched mask; ingestion becomes pure elementwise ``maximum``/``or`` on
    ``(G, P)`` arrays, which the VPU streams at HBM speed.  Measured on the
    131k-group headline shape: 14.0 → 2.0 ms/round vs the scatter form —
    TPU scatters serialize per update window while this form is shape-
    oblivious.  The engine picks dense vs sparse per dispatch by event
    occupancy (`BatchedQuorumEngine.step`); both produce bit-identical
    states (differential: ``tests/test_ops_quorum.py``).

    Caller contract: ``ack_max`` holds 0 in untouched cells (rel indexes
    are non-negative, so 0 is a max no-op — `ack()` clamps below-base
    retransmits the same way); ``vote_new`` holds first-wins-deduped vote
    events (engine.vote dedups within a batch, the kernel guards against
    standing votes).
    """
    mark = None
    if has_quiesce:
        # the reserved last peer slot holds marks, no acknowledgements
        # (see quorum_step_impl)
        mark = jnp.where(ack_touched[:, -1], ack_max[:, -1], 0)
        ack_touched = ack_touched & (
            jnp.arange(ack_touched.shape[1]) < ack_touched.shape[1] - 1
        )
    # --- ack ingestion ---------------------------------------------------
    match = jnp.maximum(st.match, jnp.where(ack_touched, ack_max, 0))
    # next >= match + 1 invariant (see quorum_step_impl)
    next_ = jnp.maximum(st.next, match + 1)
    active = st.active | ack_touched
    if track_contact:
        contacted = jnp.any(ack_touched, axis=1)
        nonleader = (st.node_state != LEADER) & st.live
        election_tick = jnp.where(contacted & nonleader, 0, st.election_tick)
    else:
        election_tick = st.election_tick
    self_match = _self_column(match, st.self_slot)
    last_index = jnp.maximum(st.last_index, self_match)

    # --- vote ingestion (first vote per peer per term wins) --------------
    if has_votes:
        votes = jnp.where(
            (st.votes == VOTE_NONE) & (vote_new != VOTE_NONE),
            vote_new,
            st.votes,
        )
    else:
        votes = st.votes

    out = _finish_step(
        st, match, next_, active, votes, election_tick, last_index, do_tick,
        has_hier=has_hier, mark=mark,
    )
    if has_reads:
        # read plane LAST: stage / echo ingest / confirm / release
        # (ReadIndex confirmation is independent of this round's commit
        # advancement — the release index is the CAPTURED watermark, not
        # the current one — so ordering vs _finish_step is free; last
        # keeps the write path byte-identical when reads are quiet)
        rst, done_cnt, done_idx = _read_plane(
            out.state, read_stage_idx, read_stage_cnt, read_ack
        )
        out = out._replace(
            state=rst, read_done_count=done_cnt, read_done_index=done_idx
        )
    if has_kv:
        # devsm plane after commit advancement (an entry committing this
        # round applies this round — apply == commit is the plane's whole
        # contract) and after the read plane (a ReadIndex slot confirming
        # this round can pair with a KV read capture at >= its release
        # watermark in the SAME dispatch)
        kst, kv_rv, kv_ri, kv_ap = _kv_plane(
            out.state, kv_ent_idx, kv_ent_key, kv_ent_val, kv_read_key
        )
        out = out._replace(
            state=kst, kv_read_val=kv_rv, kv_read_index=kv_ri,
            kv_applied=kv_ap,
        )
    if has_telem:
        # telemetry fold LAST: the aggregate must describe the state this
        # dispatch leaves behind — including reads released and entries
        # applied above — and the fold writes no field any plane reads,
        # so ordering after them is free and keeps the telem-off program
        # byte-identical.
        tst, agg = telem_fold(
            out.state, telem_k,
            count_reads=has_reads, count_kv=has_kv,
        )
        out = out._replace(state=tst, telem=agg)
    return out


quorum_step_dense = jax.jit(
    _entry(quorum_step_dense_impl, "quorum_step_dense_unpacked"),
    static_argnames=(
        "do_tick", "track_contact", "has_votes", "has_reads", "has_kv",
        "has_hier", "has_telem", "telem_k", "has_quiesce",
    ),
    donate_argnums=(0,),
)


def _apply_recycle(
    st: QuorumState,
    row: jax.Array,    # (C,) i32 — target rows; G (out of range) = padding
    term: jax.Array,   # (C,) i32
    start: jax.Array,  # (C,) i32 rel — term_start of the fresh leader
    last: jax.Array,   # (C,) i32 rel — last_index of the fresh leader
    reset_reads: bool = True,
    reset_kv: bool = True,
    reset_telem: bool = True,
) -> QuorumState:
    """Masked leader-recycle row reset (twin: the host's ``remove_group``
    + ``add_group`` + ``set_leader`` sequence for a SAME-GEOMETRY tenant
    swap, ``engine.py``).  Membership geometry (quorum, self_slot, voting,
    present, electable, timeouts) is untouched — the engine's
    ``stage_recycle`` validates that invariant host-side — so the reset is
    a handful of row scatters instead of a full host re-upload: the
    VERDICT §7 design pivot (churn as masked updates inside the dispatched
    program).  Padding rows carry ``row == G`` and drop out of bounds.
    """
    g, p = st.match.shape
    s = st.read_index.shape[1]
    c = row.shape[0]
    sel = st.self_slot[row.clip(0, g - 1)]  # (C,) — self slot per target row
    cols = jnp.arange(p, dtype=I32)[None, :]
    # reset_remotes: match 0 everywhere except self = last; next = last + 1
    match_rows = jnp.where(cols == sel[:, None], last[:, None], 0)
    next_rows = jnp.broadcast_to(last[:, None] + 1, match_rows.shape)
    zc = jnp.zeros_like(term)
    if reset_reads:
        # pending reads die with the tenant (HostMirror.clear_reads twin).
        # Compiled OUT (reset_reads=False, a static flag) when the engine's
        # read plane has never been used: the read arrays are provably
        # all-zero then, the resets are no-ops, and the three extra row
        # scatters per scanned round cost ~40% of rung-5 throughput at
        # 100k groups under churn (measured 2.83M -> 1.60M w/s).
        zread = jnp.zeros((c, s), I32)
        st = st._replace(
            read_index=st.read_index.at[row].set(zread, mode="drop"),
            read_count=st.read_count.at[row].set(zread, mode="drop"),
            read_acks=st.read_acks.at[row].set(
                jnp.zeros((c, s, p), jnp.bool_), mode="drop"
            ),
        )
    if reset_kv:
        # the fresh tenant starts from an EMPTY device state machine
        # (HostMirror.clear_kv twin).  Compiled OUT (static) while the
        # engine's devsm plane has never been used — the kv arrays are
        # provably at their reset values then, exactly the reset_reads
        # rationale above.
        v = st.kv_value.shape[1]
        e = st.kv_ent_index.shape[1]
        zke = jnp.zeros((c, e), I32)
        st = st._replace(
            kv_value=st.kv_value.at[row].set(
                jnp.zeros((c, v), I32), mode="drop"
            ),
            kv_ent_index=st.kv_ent_index.at[row].set(
                jnp.full((c, e), -1, I32), mode="drop"
            ),
            kv_ent_key=st.kv_ent_key.at[row].set(zke, mode="drop"),
            kv_ent_val=st.kv_ent_val.at[row].set(zke, mode="drop"),
        )
    if reset_telem:
        # the fresh tenant's stall horizon starts at zero (HostMirror.
        # clear_telem twin).  Compiled OUT (static) while the engine's
        # telem plane has never been used — the array is provably zero
        # then, exactly the reset_reads rationale above.
        st = st._replace(
            telem_prev_committed=st.telem_prev_committed.at[row].set(
                zc, mode="drop"
            ),
        )
    return st._replace(
        node_state=st.node_state.at[row].set(LEADER, mode="drop"),
        live=st.live.at[row].set(True, mode="drop"),
        term=st.term.at[row].set(term, mode="drop"),
        term_start=st.term_start.at[row].set(start, mode="drop"),
        last_index=st.last_index.at[row].set(last, mode="drop"),
        committed=st.committed.at[row].set(zc, mode="drop"),
        election_tick=st.election_tick.at[row].set(zc, mode="drop"),
        heartbeat_tick=st.heartbeat_tick.at[row].set(zc, mode="drop"),
        match=st.match.at[row].set(match_rows, mode="drop"),
        next=st.next.at[row].set(next_rows, mode="drop"),
        active=st.active.at[row].set(False, mode="drop"),
        votes=st.votes.at[row].set(
            jnp.full(match_rows.shape, VOTE_NONE, jnp.int8), mode="drop"
        ),
    )


def quorum_multiround_impl(
    st: QuorumState,
    ack_max: jax.Array,     # (K,G,P) i32 — per-round ack maxima; -1 = untouched
    vote_new: jax.Array,    # (K,G,P) i8, or (1,1,1) dummy when not has_votes
    churn_row: jax.Array,   # (K,C) i32 — rows recycled at round start; G = pad
    churn_term: jax.Array,  # (K,C) i32
    churn_start: jax.Array,  # (K,C) i32 rel
    churn_last: jax.Array,  # (K,C) i32 rel
    tick_mask: jax.Array,   # (K,) bool — which rounds tick; dummy when !do_tick
    read_stage_idx: jax.Array | None = None,  # (K,G,S) i32, -1 = no stage
    read_stage_cnt: jax.Array | None = None,  # (K,G,S) i32
    read_ack: jax.Array | None = None,        # (K,G,S,P) bool echoes
    kv_ent_idx: jax.Array | None = None,      # (K,G,E) i32, -1 = no stage
    kv_ent_key: jax.Array | None = None,      # (K,G,E) i32
    kv_ent_val: jax.Array | None = None,      # (K,G,E) i32
    kv_read_key: jax.Array | None = None,     # (K,G,R) i32, -1 = no read
    do_tick: bool = False,
    track_contact: bool = True,
    has_votes: bool = False,
    has_churn: bool = False,
    has_reads: bool = False,
    purge_reads: bool = True,
    has_kv: bool = False,
    purge_kv: bool = True,
    has_hier: bool = False,
    has_telem: bool = False,
    purge_telem: bool = True,
    telem_k: int = TELEM_TOPK,
    has_quiesce: bool = False,
) -> StepOutputs:
    """K engine rounds — INCLUDING membership churn — in ONE dispatch.

    This is the ladder's workhorse (ISSUE 1 tentpole): the host stages K
    rounds of dense event blocks plus per-round leader-recycle records and
    the device scans them, paying one dispatch + one egress transfer for
    the whole block instead of per round.  Round structure mirrors the
    host sequence exactly: (1) apply that round's row recycles (the twin
    of ``_upload_dirty`` scattering a re-registered row before the
    dispatch), (2) ingest the round's dense ack/vote block, (3) tally /
    commit / tick.  The single ``-1``-sentinel ack tensor replaces the
    separate ``(ack_max, ack_touched)`` pair — ``touched == ack_max >= 0``
    is computed on device, halving host staging stores and upload bytes.

    ``tick_mask`` makes the per-round tick decision DYNAMIC under a
    static ``do_tick=True``: the live coordinator catches up a varying
    tick deficit (2..4) by padding every block to a FIXED K with
    event-free masked-off rounds, so one compiled program serves every
    deficit — per-K recompiles measured 0.5-4s each on a loaded 2-vCPU
    host, long enough to stall proposals behind the compile.  A padding
    round (no events, tick masked off) is a provable no-op: ingestion of
    an all-sentinel block changes nothing and the standing-state
    tally/commit flags are idempotent across rounds.

    Ingestion delegates to :func:`quorum_step_dense_impl`, so each scanned
    round is bit-identical to a standalone dense dispatch of the same
    block (differential: ``tests/test_multiround.py``).  Egress carries
    the final state, final commit watermarks (monotone ⇒ sufficient), and
    OR-accumulated flags.  Flag OR-accumulation is per ROW: a row recycled
    mid-block attributes surviving flags to its final tenant — recycling
    callers (bench rungs, tickless coordinators) run flag-free rounds.

    ``has_reads`` rides the device read plane on the same scan: per round,
    staged ReadIndex ctx batches land in their slots, heartbeat echoes OR
    in, and :func:`read_confirm` releases quorum-confirmed slots — read
    contexts confirm in the SAME dispatch that advances commits.  The
    confirmed-read egress accumulates in the scan carry (count-sum /
    index-max per slot; see :class:`StepOutputs`), so one transfer serves
    the whole block.  A slot confirming twice in one block (the engine
    restages only deterministically-confirmed slots) reports the summed
    count at the max index — an UP-only index rewrite, which ReadIndex
    semantics permit (``tests/test_read_confirm.py`` pins all of this
    against the scalar oracle, including a recycle and a leader change
    with pending ctxs mid-block).

    ``has_kv`` folds the device state machine into the same scan (devsm,
    ISSUE 11): per round, staged ``(key_slot, value)`` entry ops land in
    their groups' pending-entry buffers, the apply fold writes every op
    the round's commit advancement covered into the HBM-resident
    ``kv_value`` rows, and staged KV reads capture post-apply values plus
    the watermark they reflect.  Read captures and applied-op counts
    accumulate in the scan carry (overwrite-where-staged / sum; see
    :class:`StepOutputs`), so the whole block's state-machine work rides
    the one dispatch that advances its commits — the apply stage has no
    host component at all (differential: ``tests/test_devsm.py``).
    """

    def body(carry, ev):
        c = 0
        stc = carry[c]; c += 1
        if has_reads:
            rcnt_acc, ridx_acc = carry[c], carry[c + 1]
            c += 2
        if has_kv:
            kval_acc, kidx_acc, kap_acc = carry[c], carry[c + 1], carry[c + 2]
            c += 3
        i = 0
        am = ev[i]; i += 1
        if has_votes:
            vn = ev[i]; i += 1
        else:
            vn = jnp.zeros((1, 1), jnp.int8)
        if has_churn:
            crow, cterm, cstart, clast = (
                ev[i], ev[i + 1], ev[i + 2], ev[i + 3]
            )
            i += 4
            # reset_reads compiles the read-slot purges out of the recycle
            # when the engine's read plane has never been used (all-zero
            # arrays; see _apply_recycle) — the engine passes purge_reads=
            # _read_plane_used; has_reads keeps the purge for blocks that
            # stage reads themselves.  reset_kv is the devsm twin of the
            # same rule (_devsm_used / has_kv).
            stc = _apply_recycle(
                stc, crow, cterm, cstart, clast,
                reset_reads=has_reads or purge_reads,
                reset_kv=has_kv or purge_kv,
                reset_telem=has_telem or purge_telem,
            )
        if has_reads:
            rsi, rsc, rak = ev[i], ev[i + 1], ev[i + 2]
            i += 3
        else:
            rsi = rsc = rak = None
        if has_kv:
            kei, kek, kev, krk = ev[i], ev[i + 1], ev[i + 2], ev[i + 3]
            i += 4
        else:
            kei = kek = kev = krk = None
        out = quorum_step_dense_impl(
            stc,
            jnp.maximum(am, 0),  # -1 sentinel → 0 (a scatter-max no-op)
            am >= 0,
            vn,
            rsi,
            rsc,
            rak,
            kei,
            kek,
            kev,
            krk,
            do_tick=False,  # ticking handled below, per-round masked
            track_contact=track_contact,
            has_votes=has_votes,
            has_reads=has_reads,
            has_kv=has_kv,
            has_hier=has_hier,
            has_quiesce=has_quiesce,
        )
        stc = out.state
        if do_tick:
            tm = ev[i]  # () bool — this round's tick decision
            ticked, tflags = tick_step(stc, has_quiesce=has_quiesce)
            stc = QuorumState(
                *(jnp.where(tm, t, o) for t, o in zip(ticked, stc))
            )
            flags = TickFlags(*(f & tm for f in tflags))
        else:
            zeros = jnp.zeros_like(out.won)
            flags = TickFlags(zeros, zeros, zeros, zeros)
        carry = (stc,)
        if has_reads:
            carry = carry + (
                rcnt_acc + out.read_done_count,
                jnp.maximum(ridx_acc, out.read_done_index),
            )
        if has_kv:
            # a KV read slot captures in exactly one round of the block
            # (the engine never restages a slot before its harvest), so
            # overwrite-where-staged is exact, not a merge heuristic
            kcap = out.kv_read_index >= 0
            carry = carry + (
                jnp.where(kcap, out.kv_read_val, kval_acc),
                jnp.where(kcap, out.kv_read_index, kidx_acc),
                kap_acc + out.kv_applied,
            )
        return carry, (out.won, out.lost, flags)

    xs = (ack_max,)
    if has_votes:
        xs = xs + (vote_new,)
    if has_churn:
        xs = xs + (churn_row, churn_term, churn_start, churn_last)
    if has_reads:
        xs = xs + (read_stage_idx, read_stage_cnt, read_ack)
    if has_kv:
        xs = xs + (kv_ent_idx, kv_ent_key, kv_ent_val, kv_read_key)
    if do_tick:
        xs = xs + (tick_mask,)
    carry0 = (st,)
    if has_reads:
        g, s = st.read_index.shape
        carry0 = carry0 + (
            jnp.zeros((g, s), I32), jnp.full((g, s), -1, I32)
        )
    if has_kv:
        g = st.kv_value.shape[0]
        r = kv_read_key.shape[2]
        carry0 = carry0 + (
            jnp.zeros((g, r), I32), jnp.full((g, r), -1, I32),
            jnp.zeros((g,), I32),
        )
    carry, (won, lost, flags) = jax.lax.scan(body, carry0, xs)
    c = 0
    st = carry[c]; c += 1
    read_done_count = read_done_index = None
    if has_reads:
        read_done_count, read_done_index = carry[c], carry[c + 1]
        c += 2
    kv_read_val = kv_read_index = kv_applied = None
    if has_kv:
        kv_read_val, kv_read_index, kv_applied = (
            carry[c], carry[c + 1], carry[c + 2]
        )
        c += 3
    telem = None
    if has_telem:
        # fold ONCE on the block's final state (see StepOutputs.telem):
        # one set of reductions per dispatch, not per scanned round
        st, telem = telem_fold(
            st, telem_k, count_reads=has_reads, count_kv=has_kv,
        )
    any_ = lambda x: jnp.any(x, axis=0)  # noqa: E731
    return StepOutputs(
        st,
        st.committed,
        any_(won),
        any_(lost),
        TickFlags(*(any_(f) for f in flags)),
        read_done_count,
        read_done_index,
        kv_read_val,
        kv_read_index,
        kv_applied,
        telem,
    )


quorum_multiround = jax.jit(
    _entry(quorum_multiround_impl, "quorum_multiround_unpacked"),
    static_argnames=(
        "do_tick", "track_contact", "has_votes", "has_churn", "has_reads",
        "purge_reads", "has_kv", "purge_kv", "has_hier", "has_telem",
        "purge_telem", "telem_k", "has_quiesce",
    ),
    donate_argnums=(0,),
)
