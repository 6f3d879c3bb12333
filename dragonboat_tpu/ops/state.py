"""Tensor state layout for the batched quorum engine.

All per-group Raft bookkeeping that the reference keeps in per-node structs
(``internal/raft/raft.go:198`` ``raft`` struct, ``internal/raft/remote.go:62``
``remote`` struct) is held here as a struct-of-arrays pytree of
``(nGroups,)`` and ``(nGroups, nPeers)`` device arrays.

TPU-first design decisions (deltas from the reference):

* **int32 indexes over a host uint64 base.**  The reference uses uint64 log
  indexes everywhere.  TPUs emulate int64, so device tensors store indexes
  *relative to a per-group host-side base* (the group's compacted floor).
  Quorum math (k-th largest, comparisons, maxima) is translation-invariant,
  so the kernels are exact; the host rebases a group's row when its relative
  indexes approach 2^31 (see ``BatchedQuorumEngine.rebase``).

* **Term guard without a log probe.**  ``tryCommit`` (reference
  ``raft.go:888-909``) must check ``log.match_term(q, term)`` before
  committing.  A Raft leader appends a noop entry at the start of its term
  (reference ``raft.go:1044`` / thesis p72) and only ever appends entries at
  its own term, so on the leader ``match_term(q, current_term)`` is exactly
  ``q >= term_start_index``.  One ``(G,)`` tensor replaces the log lookup.

* **Masks, not ragged shapes.**  Variable membership (3/5 voters, observers,
  witnesses, mid-change) is expressed by ``voting`` / ``present`` boolean
  masks over a fixed ``nPeers`` axis (SURVEY.md §7 hard-part 4).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Device-side dtypes.  Indexes are int32 *relative to the group base*;
# terms are int32 (terms advance only on elections — 2^31 is unreachable).
I32 = jnp.int32
I8 = jnp.int8
BOOL = jnp.bool_

INDEX_MIN = np.iinfo(np.int32).min

# Raft node states — must match raft.RaftState (reference raft.go:64-71).
FOLLOWER, CANDIDATE, LEADER, OBSERVER, WITNESS = 0, 1, 2, 3, 4

# Vote cell encoding: -1 = no response, 0 = rejected, 1 = granted.
VOTE_NONE, VOTE_REJECT, VOTE_GRANT = -1, 0, 1

# Pending ReadIndex ctx slots per group (the ``S`` axis).  Each slot holds
# ONE staged read batch: its captured commit watermark, the number of
# client reads riding it, and the per-peer heartbeat-echo acks.  Four
# slots cover a full K-round pipeline depth: a batch staged in round r
# confirms in round >= r, and the engine's host-side slot bookkeeping
# only reuses a slot once its batch deterministically confirmed
# (``BatchedQuorumEngine.stage_read``).
READ_SLOTS = 4

# Device state machine (devsm, ISSUE 11): value slots per group (the
# ``V`` axis of ``kv_value``) and pending-entry buffer depth (the ``E``
# axis).  A committed entry is a fixed-width ``(key_slot, value)`` SET op;
# the apply fold (``kernels._kv_plane``) writes it into its group's
# ``kv_value`` row the moment the commit watermark passes its index.  An
# entry staged at APPEND time rides buffer slot ``rel_index % E`` until it
# commits; the engine's host bookkeeping queues ops whose slot is still
# occupied (``BatchedQuorumEngine.stage_kv_ops``).
KV_SLOTS = 16
KV_ENT_SLOTS = 16

# Per-round device KV read slots (the ``R`` axis): a staged read is
# transient — it captures its value (and the committed watermark at
# capture) in exactly its round, so unlike the ReadIndex plane there is
# no device-resident read state, only the per-round stage tensor.
KV_READ_SLOTS = 4


# HBM-ledger plane classification (obs/devprof.py, ISSUE 15): which
# subsystem owns each resident device field.  Everything not listed in
# an optional plane belongs to the core quorum plane; the optional
# planes are exactly the field sets the engine's `_read_plane_used` /
# `_devsm_used` latches gate (``BatchedQuorumEngine._READ_KEYS`` /
# ``_KV_KEYS`` must stay in lockstep — asserted in tests/test_devprof.py).
READ_PLANE_FIELDS = ("read_index", "read_count", "read_acks")
DEVSM_PLANE_FIELDS = ("kv_value", "kv_ent_index", "kv_ent_key", "kv_ent_val")
HIER_PLANE_FIELDS = ("near", "sub_quorum")
TELEM_PLANE_FIELDS = ("telem_prev_committed",)
# A group's sleep (``Config.quiesce`` on the device tick plane): the row's
# threshold (0 = the group does not quiesce), its idle clock and its
# quiesced flag.  Core quorum-plane state for the ledger; the engine's
# ``_quiesce_used`` latch gates the tick kernel's use of them and the row
# syncs' (``BatchedQuorumEngine._QUIESCE_KEYS``).
QUIESCE_FIELDS = ("quiesce_threshold", "idle_tick", "quiesced")
# Ack-plane marks of a quiesce row (values on the reserved LAST peer slot
# of the ack plane, ``kernels._quiesce_marks``): a peer's QUIESCE puts the
# row to sleep, any activity resets its idle clock and wakes it.  The
# greater wins a round, so a wake beats a sleep staged beside it.
QUIESCE_MARK_SLEEP, QUIESCE_MARK_WAKE = 1, 2


def field_plane(name: str) -> str:
    """The HBM-ledger plane a :class:`QuorumState` field belongs to."""
    if name in READ_PLANE_FIELDS:
        return "read"
    if name in DEVSM_PLANE_FIELDS:
        return "devsm"
    if name in HIER_PLANE_FIELDS:
        return "hier"
    if name in TELEM_PLANE_FIELDS:
        return "telem"
    return "quorum"


def state_layout(
    n_groups: int,
    n_peers: int,
    n_read_slots: int = None,
    n_kv_slots: int = None,
    n_kv_ents: int = None,
) -> dict:
    """Shape/dtype/byte layout of the resident device state WITHOUT
    allocating it (``jax.eval_shape`` over :func:`make_state`): the
    capacity model's source of truth.  Every field scales linearly with
    the group axis, so ``sum(nbytes) / n_groups`` is the exact
    bytes-per-group figure ``predict_bytes`` extrapolates from — and
    because this walks the same constructor the engine allocates
    through, a new state field can never silently escape the ledger."""
    kw = {}
    if n_read_slots is not None:
        kw["n_read_slots"] = n_read_slots
    if n_kv_slots is not None:
        kw["n_kv_slots"] = n_kv_slots
    if n_kv_ents is not None:
        kw["n_kv_ents"] = n_kv_ents
    sds = jax.eval_shape(lambda: make_state(n_groups, n_peers, **kw))
    return {
        name: {
            "shape": tuple(int(d) for d in leaf.shape),
            "dtype": str(np.dtype(leaf.dtype)),
            "nbytes": int(np.prod(leaf.shape, dtype=np.int64))
            * np.dtype(leaf.dtype).itemsize,
            "plane": field_plane(name),
        }
        for name, leaf in sds._asdict().items()
    }


class QuorumState(NamedTuple):
    """Struct-of-arrays state for G groups × P peer slots.

    Group-axis ``(G,)`` arrays mirror the per-``raft`` scalars; peer-axis
    ``(G, P)`` arrays mirror the per-``remote`` progress tracker columns.
    """

    # --- per-group scalars ---------------------------------------------
    node_state: jax.Array      # (G,) i8: FOLLOWER..WITNESS
    term: jax.Array            # (G,) i32
    committed: jax.Array       # (G,) i32 rel: log.committed
    last_index: jax.Array      # (G,) i32 rel: log.last_index()
    term_start: jax.Array      # (G,) i32 rel: first index of current leader term
    quorum: jax.Array          # (G,) i32: num_voting//2 + 1
    self_slot: jax.Array       # (G,) i32: peer-slot of this replica
    election_tick: jax.Array   # (G,) i32
    heartbeat_tick: jax.Array  # (G,) i32
    rand_timeout: jax.Array    # (G,) i32: randomized election timeout (host-seeded)
    election_timeout: jax.Array   # (G,) i32
    heartbeat_timeout: jax.Array  # (G,) i32
    electable: jax.Array       # (G,) bool: voter, not self-removed, not observer/witness
    check_quorum_on: jax.Array  # (G,) bool: config.check_quorum
    live: jax.Array            # (G,) bool: row holds a real group

    # --- per-peer columns ----------------------------------------------
    match: jax.Array           # (G,P) i32 rel: remote.match
    next: jax.Array            # (G,P) i32 rel: remote.next
    voting: jax.Array          # (G,P) bool: full member or witness (counts for quorum)
    present: jax.Array         # (G,P) bool: slot occupied (incl. observers)
    active: jax.Array          # (G,P) bool: remote.active (CheckQuorum recency)
    votes: jax.Array           # (G,P) i8: VOTE_NONE / VOTE_REJECT / VOTE_GRANT

    # --- pending ReadIndex ctx slots (device read plane) ---------------
    # Scalar twin: ``raft/readindex.py`` ReadStatus (index + confirmed
    # set), batched per group into S fixed slots.  ``read_count == 0``
    # means the slot is free; confirmation is a masked row-sum of
    # ``read_acks`` vs quorum (kernels.read_confirm).
    read_index: jax.Array      # (G,S) i32 rel: commit watermark captured at stage
    read_count: jax.Array      # (G,S) i32: client reads batched in the slot (0 = free)
    read_acks: jax.Array       # (G,S,P) bool: heartbeat-echo acks per slot

    # --- device state machine (devsm, ISSUE 11) ------------------------
    # Scalar twin: a user KV state machine's value array plus the apply
    # queue between commit and apply.  ``kv_value`` IS the replicated
    # state (HBM-resident, mutated in-program by the apply fold);
    # ``kv_ent_*`` is the pending-entry buffer — a committed entry leaves
    # it the round its index passes the commit watermark, so buffered
    # entries are always a suffix strictly above ``committed``.
    kv_value: jax.Array        # (G,V) i32: the replicated KV state
    kv_ent_index: jax.Array    # (G,E) i32 rel: staged op's log index; -1 = free
    kv_ent_key: jax.Array      # (G,E) i32: key slot of the staged op
    kv_ent_val: jax.Array      # (G,E) i32: value of the staged op

    # --- hierarchical commit plane (ISSUE 18) --------------------------
    # Scalar twin: ``raft/hier.py`` HierPlane's near-voter set and
    # sub-quorum cardinality for a LEADER row (host-authoritative, pushed
    # at promotion like the membership columns).  ``sub_quorum == 0``
    # disables the rule for the row — the commit reduction then matches
    # the classic kth-largest bit-for-bit.
    near: jax.Array            # (G,P) bool: leader-domain voter slots
    sub_quorum: jax.Array      # (G,) i32: domain majority; 0 = hier off

    # --- device telemetry plane (ISSUE 20) -----------------------------
    # Commit watermark at the end of the previous telemetry fold: the
    # cross-dispatch horizon the stalled-group predicate compares against
    # (``committed`` flat since the last fold while ``last_index`` shows
    # pending work).  Written in-program by ``kernels.telem_fold``; reset
    # with the row on recycle so a fresh tenant never inherits the old
    # tenant's watermark.
    telem_prev_committed: jax.Array  # (G,) i32 rel

    # --- a group's sleep (Config.quiesce, ISSUE 44) --------------------
    # Scalar twin: ``quiesce.QuiesceManager`` (``threshold``,
    # ``current_tick - idle_since``, ``_quiesced``).  The tick kernel
    # advances the idle clock of an awake quiesce row and puts the row to
    # sleep when it crosses the threshold; a sleeping row raises no
    # heartbeat-due and no election-due flag.  Last in the tuple, so every
    # older leaf keeps its rows in the packed blocks.
    quiesce_threshold: jax.Array  # (G,) i32: idle ticks before sleep; 0 = off
    idle_tick: jax.Array          # (G,) i32: ticks since the last activity
    quiesced: jax.Array           # (G,) bool: the row sleeps


def make_state(
    n_groups: int,
    n_peers: int,
    n_read_slots: int = READ_SLOTS,
    n_kv_slots: int = KV_SLOTS,
    n_kv_ents: int = KV_ENT_SLOTS,
) -> QuorumState:
    """All-dead state: rows are claimed by the host as groups start."""
    g, p, s = n_groups, n_peers, n_read_slots
    v, e = n_kv_slots, n_kv_ents
    zi = jnp.zeros((g,), I32)
    return QuorumState(
        node_state=jnp.zeros((g,), I8),
        term=zi,
        committed=zi,
        last_index=zi,
        term_start=zi,
        quorum=jnp.ones((g,), I32),
        self_slot=zi,
        election_tick=zi,
        heartbeat_tick=zi,
        rand_timeout=jnp.full((g,), 10, I32),
        election_timeout=jnp.full((g,), 10, I32),
        heartbeat_timeout=jnp.ones((g,), I32),
        electable=jnp.zeros((g,), BOOL),
        check_quorum_on=jnp.zeros((g,), BOOL),
        live=jnp.zeros((g,), BOOL),
        match=jnp.zeros((g, p), I32),
        next=jnp.ones((g, p), I32),
        voting=jnp.zeros((g, p), BOOL),
        present=jnp.zeros((g, p), BOOL),
        active=jnp.zeros((g, p), BOOL),
        votes=jnp.full((g, p), VOTE_NONE, I8),
        read_index=jnp.zeros((g, s), I32),
        read_count=jnp.zeros((g, s), I32),
        read_acks=jnp.zeros((g, s, p), BOOL),
        kv_value=jnp.zeros((g, v), I32),
        kv_ent_index=jnp.full((g, e), -1, I32),
        kv_ent_key=jnp.zeros((g, e), I32),
        kv_ent_val=jnp.zeros((g, e), I32),
        near=jnp.zeros((g, p), BOOL),
        sub_quorum=zi,
        telem_prev_committed=zi,
        quiesce_threshold=zi,
        idle_tick=zi,
        quiesced=jnp.zeros((g,), BOOL),
    )


# ----------------------------------------------------------------------
# packed carry: the state between two dispatches
# ----------------------------------------------------------------------
# A ``QuorumState`` is 34 device arrays, and a step that takes it donated
# and hands back the next one makes 34 and retires 34: each birth and each
# death is a hand-off of the interpreter on the round thread.  Between
# steps the engine therefore holds the same leaves as TWO blocks, one per
# storage dtype, each ``(rows, G)``: a leaf's axes behind the group axis
# (peers, read / kv slots) become rows, G stays last and is the axis a
# group-sharded engine shards (``sharding.block_sharding``).  ``bool``
# leaves are stored as ``int8`` beside the two ``int8`` leaves (one byte a
# cell either way).  The kernels never see the blocks: a jitted program is
# ``unpack -> the kernel as it is -> pack`` (``ops/packed.py``).


class StateBlocks(NamedTuple):
    """The packed carry: every :class:`QuorumState` leaf, in two arrays."""

    i32: jax.Array  # (rows, G) i32: a (G,) leaf one row, (G,X) X, (G,P) P rows
    i8: jax.Array   # (rows, G) i8: node_state, votes, every bool leaf


@functools.lru_cache(maxsize=None)
def _leaf_table() -> tuple:
    """``(name, block, is_bool, axes)`` of every leaf, in field order;
    ``axes`` names what follows the group axis (``""``, ``"p"``, ``"s"``,
    ``"sp"``, ``"v"``, ``"e"``) and becomes rows of the block.  Read off
    :func:`make_state` at five distinct sizes, so a new field packs by
    its dtype and shape without an entry here."""
    sds = jax.eval_shape(lambda: make_state(2, 3, 5, 7, 11))
    axis = {3: "p", 5: "s", 7: "v", 11: "e"}
    return tuple(
        (name, "i32" if np.dtype(leaf.dtype) == np.int32 else "i8",
         np.dtype(leaf.dtype) == np.bool_,
         "".join(axis[d] for d in leaf.shape[1:]))
        for name, leaf in sds._asdict().items()
    )


def block_dims(blocks: StateBlocks, dims: tuple) -> tuple:
    """``(G, P)`` of ``blocks``.  G is the blocks' last axis; P is not an
    axis of its own any more, so it is read off the ``int32`` block's
    row count, which is ``fixed + per_peer * P`` over the leaf table at
    the engine's slot counts ``dims``."""
    size = dict(zip("sve", dims))
    fixed = per_peer = 0
    for _, block, _, axes in _leaf_table():
        if block == "i32":
            n = math.prod(size[a] for a in axes if a != "p")
            if "p" in axes:
                per_peer += n
            else:
                fixed += n
    rows, g = blocks.i32.shape
    return g, (rows - fixed) // per_peer


def pack_state(st: QuorumState, xp=jnp) -> StateBlocks:
    """``st`` as blocks (``xp``: ``jnp`` inside a program, ``numpy`` for
    the host mirror)."""
    parts = {b: [] for b in StateBlocks._fields}
    for leaf, (_, block, _, _) in zip(st, _leaf_table()):
        if block == "i8":
            leaf = leaf.astype(xp.int8)
        parts[block].append(
            xp.moveaxis(leaf, 0, -1).reshape(-1, leaf.shape[0])
        )
    return StateBlocks(*(xp.concatenate(parts[b], axis=0)
                         for b in StateBlocks._fields))


def unpack_state(
    blocks: StateBlocks,
    dims: tuple = (READ_SLOTS, KV_SLOTS, KV_ENT_SLOTS),
    xp=jnp,
) -> QuorumState:
    """The kernels' view of ``blocks``: slices, the group axis moved back
    to the front, and ``!= 0`` where a ``bool`` leaf is stored as
    ``int8``.  ``dims`` is the engine's ``(n_read_slots, n_kv_slots,
    n_kv_ents)``."""
    g, p = block_dims(blocks, dims)
    size = dict(zip("psve", (p,) + tuple(dims)))
    at = dict.fromkeys(StateBlocks._fields, 0)
    leaves = []
    for _, block, is_bool, axes in _leaf_table():
        tail = tuple(size[a] for a in axes)
        lo = at[block]
        at[block] = hi = lo + math.prod(tail)
        leaf = xp.moveaxis(
            getattr(blocks, block)[lo:hi].reshape(tail + (g,)), -1, 0
        )
        leaves.append(leaf != 0 if is_bool else leaf)
    return QuorumState(*leaves)


class HostMirror:
    """Numpy twin of :class:`QuorumState` for cheap host-side mutation.

    The host mutates rows scalar-style for rare transitions (membership
    change, becoming leader, snapshot restore) and uploads only between
    ticks; dense per-tick updates travel as compact event batches instead
    (see ``kernels.quorum_step``).
    """

    def __init__(
        self,
        n_groups: int,
        n_peers: int,
        n_read_slots: int = READ_SLOTS,
        n_kv_slots: int = KV_SLOTS,
        n_kv_ents: int = KV_ENT_SLOTS,
    ):
        self.n_groups = n_groups
        self.n_peers = n_peers
        self.n_read_slots = n_read_slots
        self.n_kv_slots = n_kv_slots
        self.n_kv_ents = n_kv_ents
        dev = make_state(n_groups, n_peers, n_read_slots, n_kv_slots, n_kv_ents)
        self.arrays = {k: np.asarray(v).copy() for k, v in dev._asdict().items()}

    def to_device(self, sharding=None) -> QuorumState:
        put = (
            (lambda a: jax.device_put(a, sharding))
            if sharding is not None
            else jax.device_put
        )
        return QuorumState(**{k: put(v) for k, v in self.arrays.items()})

    def pull(self, st: QuorumState) -> None:
        for k, v in st._asdict().items():
            np.copyto(self.arrays[k], np.asarray(v))

    def recycle_row(
        self,
        row: int,
        term: int,
        term_start: int,
        last_index: int,
        clear_reads: bool = True,
        clear_kv: bool = True,
        clear_telem: bool = True,
    ) -> None:
        """Numpy twin of ``kernels._apply_recycle``: reset a row to a
        fresh same-geometry leader tenant WITHOUT touching membership
        columns.  The engine applies this when it stages a device-side
        recycle (``BatchedQuorumEngine.stage_recycle``) so the mirror's
        host-authoritative columns (term, watermarks) match what the
        dispatched program will compute — the row is deliberately NOT
        marked dirty; the device applies the same reset in-program."""
        a = self.arrays
        a["live"][row] = True
        a["node_state"][row] = LEADER
        a["term"][row] = term
        a["term_start"][row] = term_start
        a["last_index"][row] = last_index
        a["committed"][row] = 0
        a["election_tick"][row] = 0
        a["heartbeat_tick"][row] = 0
        a["match"][row, :] = 0
        a["match"][row, a["self_slot"][row]] = last_index
        a["next"][row, :] = last_index + 1
        a["active"][row, :] = False
        a["votes"][row, :] = VOTE_NONE
        if clear_reads:  # engine skips while its read plane is untouched
            self.clear_reads(row)
        if clear_kv:  # engine skips while its devsm plane is untouched
            self.clear_kv(row)
        if clear_telem:  # engine skips while its telem plane is untouched
            self.clear_telem(row)

    def row_image(self, row: int, skip=frozenset()) -> dict:
        """Per-field dense copy of one row — the stage-out half of a
        cross-shard group migration (``ops/mesh.py``).  ``skip`` names
        fields the caller deliberately leaves behind: the mesh plane
        skips its read/kv-plane columns because the migration quiescence
        gate has already drained them, so the target's fresh-registration
        defaults are the correct values."""
        return {
            k: np.copy(a[row]) for k, a in self.arrays.items()
            if k not in skip
        }

    def restore_row(self, row: int, image: dict) -> None:
        """Paste a captured ``row_image`` onto ``row`` verbatim — the
        stage-in half of a migration (same geometry on both shards; the
        cross-shard twin of ``recycle_row``).  The caller owns dirty
        tracking: unlike ``recycle_row`` there is no in-program twin
        applying the same write, so the row MUST be re-uploaded."""
        a = self.arrays
        for k, v in image.items():
            a[k][row] = v

    def clear_kv(self, row: int) -> None:
        """Reset a row's device state machine: value slots to zero AND the
        pending-entry buffer freed.  A recycle's fresh tenant starts from
        an empty KV exactly like a fresh ``add_group`` registration."""
        a = self.arrays
        a["kv_value"][row, :] = 0
        self.clear_kv_ents(row)

    def clear_kv_ents(self, row: int) -> None:
        """Free a row's pending-entry buffer WITHOUT touching the value
        slots (leadership-transition twin: buffered entries sit strictly
        above the commit watermark, an uncertain log suffix the next
        leader may rewrite — they die with the transition; applied state
        persists exactly like the scalar SM across terms)."""
        a = self.arrays
        a["kv_ent_index"][row, :] = -1
        a["kv_ent_key"][row, :] = 0
        a["kv_ent_val"][row, :] = 0

    def clear_telem(self, row: int) -> None:
        """Reset a row's telemetry watermark: the stalled-group predicate
        compares ``committed`` against the previous fold's value, and a
        recycled row restarts its relative indexes at zero — the old
        tenant's watermark would read as forward progress (or a phantom
        stall) for the new one."""
        self.arrays["telem_prev_committed"][row] = 0

    def clear_reads(self, row: int) -> None:
        """Drop a row's pending ReadIndex slots (twin of the scalar path's
        fresh ``ReadIndex()`` on every state transition, ``raft.py``
        ``become_*``): reads staged under the old leadership must never
        confirm under the new one."""
        a = self.arrays
        a["read_index"][row, :] = 0
        a["read_count"][row, :] = 0
        a["read_acks"][row, :, :] = False
