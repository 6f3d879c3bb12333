"""Host driver for the batched quorum engine.

Replaces the reference's 16-worker per-group iteration
(``execengine.go:860-949``) with: host ingest (queues → compact event
batches) → ONE ``quorum_step`` device dispatch per round → host egress
(commit advances, election/heartbeat/step-down flags).  Rare transitions
(membership change, becoming leader/candidate, snapshot restore, index
rebase) mutate a numpy mirror row and are scattered onto the device arrays
before the next dispatch.

What crosses the host/device boundary of a dispatch is a handful of
arrays, because each one a step makes or retires costs the round thread a
hand-off of the interpreter (PERF.md §5): the state travels as two
blocks, everything staged rides the launch as ONE host buffer, everything
read back is ONE egress block (``packed.py``); ``eng.dev`` unpacks a
``QuorumState`` on demand.

The group axis is shardable over a ``jax.sharding.Mesh`` (see
``sharding.py``): every kernel op is row-wise over groups, so XLA partitions
the whole step with zero collectives — groups are embarrassingly parallel,
exactly like the reference's ``clusterID % workers`` partitioning but over
chips instead of goroutines.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax

from .. import obs as _obs
from ..logger import get_logger
from ..obs.recorder import OFF as _OFF, annotate as _annotate
from . import packed as _pk
from .kernels import TELEM_TOPK
from .sharding import block_sharding
from .state import (
    QUIESCE_FIELDS,
    QUIESCE_MARK_SLEEP,
    QUIESCE_MARK_WAKE,
    CANDIDATE,
    FOLLOWER,
    KV_ENT_SLOTS,
    KV_READ_SLOTS,
    KV_SLOTS,
    LEADER,
    OBSERVER,
    READ_SLOTS,
    VOTE_GRANT,
    VOTE_NONE,
    VOTE_REJECT,
    WITNESS,
    HostMirror,
    QuorumState,
    StateBlocks,
    pack_state,
)

elog = get_logger("ops.engine")


# Event batches are padded to fixed sizes so jit compiles once.
DEFAULT_EVENT_CAP = 4096

# Rebase a row when relative indexes pass this (well clear of int32 max).
REBASE_THRESHOLD = 1 << 30

#: padded fused-block sizes the live coordinator dispatches (and the
#: warmup pass pre-compiles): a K-round backlog pads up to the nearest
#: bucket, so the whole adaptive range is served by len(buckets) compiled
#: programs (the per-round tick mask makes padding rounds provable no-ops)
WARM_K_BUCKETS = (4, 16)


def k_bucket(k: int, buckets=WARM_K_BUCKETS) -> int:
    """Smallest warm bucket holding ``k`` rounds (the largest bucket for
    anything beyond — callers cap K at ``max(buckets)``)."""
    for b in buckets:
        if k <= b:
            return b
    return buckets[-1]


def upload_nbytes(*arrays) -> int:
    """Total bytes of the host tensors one dispatch ships (``None``
    entries — compiled-out optional planes — are skipped).  The ONE
    accounting point for host→device event-tensor volume: the flight
    recorder's ``upload_bytes`` span field, the
    ``dragonboat_device_upload_bytes_total`` counter and the devprof
    capacity model's per-dispatch term all read this, so the sum can
    never drift from the tensors actually passed to the kernel (ISSUE 15
    satellite — three hand-maintained per-site sums preceded it).
    Callers pass EXACTLY what the program receives: the one ingress
    block of the dispatch."""
    return int(sum(a.nbytes for a in arrays if a is not None))


# ----------------------------------------------------------------------
# persistent XLA compilation cache (ISSUE 7 tentpole)
# ----------------------------------------------------------------------
# jax's persistent compilation cache makes restarts skip XLA compilation
# entirely: the warmup pass's first run populates it, every later process
# deserializes the compiled executables in milliseconds.  The directory
# is VERSIONED by a hash of the kernel sources — a kernel change gets a
# fresh subdirectory instead of silently mixing stale executables (jax
# keys on the HLO, which would catch most but not all drift, e.g. a
# semantics change hidden behind an unchanged trace shape).

_CC_MU = threading.Lock()
_CC = {"dir": None, "hits": 0, "misses": 0, "listener": False,
       "read_patched": False}
#: serializes jax's compile-or-deserialize step process-wide once the
#: persistent cache is enabled: concurrent cache-hit deserialization on
#: the shared XLA CPU client corrupts the heap (reproduced 3/3 — three
#: engines warming from a hot cache in one process segfault in the warm
#: thread; a read-only lock around get_executable_and_time still wedged
#: or crashed 2/3, so the unsafe window spans the whole
#: compile_or_get_cached step).  Held only when a program is NOT in the
#: in-memory jit cache, so the dispatch hot path pays nothing.  RLock:
#: a compile may re-enter for subcomputations.
_CC_COMPILE_MU = threading.RLock()


def kernel_source_hash() -> str:
    """SHA-256 over the program-defining sources (kernels.py, packed.py,
    state.py): the version key of the persistent compilation cache
    directory."""
    import hashlib

    h = hashlib.sha256()
    base = os.path.dirname(os.path.abspath(__file__))
    for fname in ("kernels.py", "packed.py", "state.py"):
        with open(os.path.join(base, fname), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _cc_listener(event: str, **kwargs) -> None:
    # jax.monitoring fires for EVERY event; keep this O(1) cheap
    if event == "/jax/compilation_cache/cache_hits":
        _CC["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CC["misses"] += 1


#: where the persistent cache lives when neither JAX_COMPILATION_CACHE_DIR
#: nor a configured directory decides: a FIXED path inside the checkout.
#: The path is part of jax's cache key, so it must never be derived from a
#: temp name, pid or time — such a directory can never hit across processes.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


#: every compile-or-load that reached jax's one compile entry point since
#: the persistent cache was enabled: (perf_counter t0, t1, program name,
#: thread name, "hit" | "miss" | "uncached" of the persistent cache).
#: Bounded; compiles are rare, so always on.
_CC_LOG: deque = deque(maxlen=4096)


def _program_name(computation) -> str:
    """The program's name as jax lowered it (``jit_quorum_step_impl``)."""
    try:
        return str(
            computation.operation.attributes["sym_name"]
        ).strip('"')
    except Exception:
        return "?"


def _locked_compile_or_get_cached(orig):
    def locked(backend, computation, *a, **k):
        with _CC_COMPILE_MU:
            hits, misses = _CC["hits"], _CC["misses"]
            t0 = time.perf_counter()
            with _annotate("compile"):
                out = orig(backend, computation, *a, **k)
            _CC_LOG.append((
                t0, time.perf_counter(), _program_name(computation),
                threading.current_thread().name,
                "miss" if _CC["misses"] != misses
                else "hit" if _CC["hits"] != hits else "uncached",
            ))
            return out

    return locked


def enable_persistent_compilation_cache(cache_dir: str = "") -> str:
    """Turn on jax's persistent compilation cache and install the hit/miss
    counter.  Idempotent; returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set it decides: jax already
    reads it, so NO directory is set from code and the path is used as is
    (jax's own key covers the program).  Otherwise the cache goes to
    ``<cache_dir or DEFAULT_COMPILATION_CACHE_DIR>/xla-<kernel-source-hash>``.
    Safe to call before or after backend init (the cache is consulted per
    compile).  The min-compile-time/min-entry-size floors are zeroed so
    even the fast single-round programs persist — "fast" compiles are
    still hundreds of ms of round-thread stall."""
    resolved = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    from_env = bool(resolved)
    if not from_env:
        resolved = os.path.join(
            cache_dir or DEFAULT_COMPILATION_CACHE_DIR,
            "xla-" + kernel_source_hash()[:16],
        )
    with _CC_MU:
        if _CC["dir"] == resolved:
            return resolved
        if not from_env:
            os.makedirs(resolved, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", resolved)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # jax latches "cache in use?" at the FIRST compile of the process
        # (compilation_cache.is_cache_used's _cache_checked flag): enabling
        # the cache after anything has compiled — a NodeHost that touched
        # jax before the coordinator, a test suite with earlier device
        # work — would silently never engage it.  reset_cache drops that
        # latch (not the compiled executables) so the next compile
        # re-evaluates the config.
        from jax._src import compilation_cache as _jcc

        _jcc.reset_cache()
        # serialize compile-or-deserialize process-wide (see
        # _CC_COMPILE_MU): patching the single entry point covers every
        # engine, warm thread and round thread without touching the
        # execute fast path (already-jit-cached programs never reach
        # compiler.compile_or_get_cached).  pxla resolves it through the
        # module attribute at call time, so rebinding covers every caller.
        if not _CC["read_patched"]:
            from jax._src import compiler as _jcompiler

            _jcompiler.compile_or_get_cached = _locked_compile_or_get_cached(
                _jcompiler.compile_or_get_cached
            )
            _CC["read_patched"] = True
        if not _CC["listener"]:
            from jax import monitoring as _mon

            _mon.register_event_listener(_cc_listener)
            _CC["listener"] = True
        _CC["dir"] = resolved
    return resolved


def compilation_cache_stats() -> dict:
    """Persistent-cache telemetry: the directory in use plus process-
    lifetime hit/miss counts (None dir = cache never enabled here)."""
    return {"dir": _CC["dir"], "hits": _CC["hits"], "misses": _CC["misses"]}


def compilation_log() -> List[Tuple[float, float, str, str, str]]:
    """Every program compiled or loaded from the persistent cache since
    it was enabled, oldest first: ``(t0, t1, program, thread, "hit" |
    "miss" | "uncached")`` with ``t0``/``t1`` on
    ``time.perf_counter()`` — the name behind each count of
    :func:`compilation_cache_stats`, and the other side of a ``warmup``
    span's ``variant``."""
    return list(_CC_LOG)


def _ack_columns(live, blocks) -> tuple:
    """Filtered acks (``_filter_acks``) as three int32 columns."""
    cols = _concat_columns(live, blocks, 3)
    if cols is None:
        z = np.zeros((0,), np.int32)
        return z, z, z
    return cols


def _concat_columns(tuples, blocks, n: int):
    """``n`` columns of staged events, the tuple-staged ones (as int32)
    first, then each block's; None where there is none."""
    parts = list(blocks)
    if tuples:
        parts.insert(0, tuple(np.array(tuples, dtype=np.int32).T))
    if not parts:
        return None
    if len(parts) == 1:
        return parts[0]
    return tuple(np.concatenate([p[i] for p in parts]) for i in range(n))


@dataclass
class GroupInfo:
    cluster_id: int
    row: int
    slots: Dict[int, int]            # node_id -> peer slot
    base: int = 0                    # uint64 absolute index of rel 0
    node_ids: List[int] = field(default_factory=list)
    self_slot: int = 0               # peer slot of this replica


class StepResult:
    """Egress of one dispatch, in absolute-index / cluster-id terms.

    ``commit`` materializes lazily from the vectorized egress arrays: hot
    callers (the bench rungs, watermark probes) read the arrays or the
    engine's ``committed_view`` and never pay the per-row dict build."""

    __slots__ = (
        "won", "lost", "elect", "heartbeat", "demote", "quiesce",
        "_commit_cids", "_commit_abs", "_commit_dict",
        "read_cids", "read_slots", "read_index_abs", "read_counts",
        "_reads_list",
        "kv_cids", "kv_slots", "kv_vals", "kv_index_abs",
        "_kv_reads_list", "kv_applied_ops",
    )

    def __init__(self):
        self._commit_cids = None   # np (n,) int64 cluster ids, or None
        self._commit_abs = None    # np (n,) int64 absolute committed
        self._commit_dict: Optional[Dict[int, int]] = None
        self.won: List[int] = []
        self.lost: List[int] = []
        self.elect: List[int] = []
        self.heartbeat: List[int] = []
        self.demote: List[int] = []
        # groups whose idle clock crossed its threshold: asleep from this
        # dispatch on (``TickFlags.quiesce_enter``)
        self.quiesce: List[int] = []
        # confirmed-read egress, vectorized (None when the dispatch ran
        # read-free): per confirmed pending-read slot, the cluster, the
        # slot, the ABSOLUTE release index, and how many client reads
        # the batch carried.  Like the commit egress, hot callers read
        # the arrays; the list-of-tuples view materializes lazily.
        self.read_cids: Optional[np.ndarray] = None       # (n,) int64
        self.read_slots: Optional[np.ndarray] = None      # (n,) int64
        self.read_index_abs: Optional[np.ndarray] = None  # (n,) int64
        self.read_counts: Optional[np.ndarray] = None     # (n,) int64
        self._reads_list = None
        # devsm KV read egress (None when the dispatch ran kv-free): per
        # captured read slot, the cluster, the slot, the captured value
        # and the ABSOLUTE commit watermark the value reflects; plus the
        # total ops the apply fold consumed this dispatch.
        self.kv_cids: Optional[np.ndarray] = None         # (n,) int64
        self.kv_slots: Optional[np.ndarray] = None        # (n,) int64
        self.kv_vals: Optional[np.ndarray] = None         # (n,) int64
        self.kv_index_abs: Optional[np.ndarray] = None    # (n,) int64
        self._kv_reads_list = None
        self.kv_applied_ops: int = 0

    @property
    def commit(self) -> Dict[int, int]:
        """cluster_id -> new committed (abs); built on first access."""
        if self._commit_dict is None:
            if self._commit_cids is None or not len(self._commit_cids):
                self._commit_dict = {}
            else:
                self._commit_dict = dict(
                    zip(self._commit_cids.tolist(), self._commit_abs.tolist())
                )
        return self._commit_dict

    @property
    def reads(self) -> List[Tuple[int, int, int, int]]:
        """Confirmed reads as ``(cluster_id, slot, abs_index, count)``
        tuples; built on first access (vectorized twin: the
        ``read_*`` arrays)."""
        if self._reads_list is None:
            if self.read_cids is None or not len(self.read_cids):
                self._reads_list = []
            else:
                self._reads_list = list(
                    zip(
                        self.read_cids.tolist(),
                        self.read_slots.tolist(),
                        self.read_index_abs.tolist(),
                        self.read_counts.tolist(),
                    )
                )
        return self._reads_list

    @property
    def kv_reads(self) -> List[Tuple[int, int, int, int]]:
        """Captured devsm KV reads as ``(cluster_id, slot, value,
        abs_index)`` tuples; built on first access (vectorized twin: the
        ``kv_*`` arrays)."""
        if self._kv_reads_list is None:
            if self.kv_cids is None or not len(self.kv_cids):
                self._kv_reads_list = []
            else:
                self._kv_reads_list = list(
                    zip(
                        self.kv_cids.tolist(),
                        self.kv_slots.tolist(),
                        self.kv_vals.tolist(),
                        self.kv_index_abs.tolist(),
                    )
                )
        return self._kv_reads_list


class MultiRoundResult(StepResult):
    """Egress of one K-round fused dispatch (``step_rounds``).

    Adds the raw vectorized views on top of the StepResult interface:
    ``committed_rel`` is the device's final (G,) relative watermark vector
    and ``commit_rows`` the rows that advanced vs the pre-block host twin —
    both numpy, zero per-row Python.  Flags are OR-accumulated across the
    block's rounds (see ``kernels.quorum_multiround_impl`` on recycled-row
    attribution)."""

    __slots__ = ("rounds", "committed_rel", "commit_rows")

    def __init__(self, rounds: int):
        super().__init__()
        self.rounds = rounds
        self.committed_rel: Optional[np.ndarray] = None  # (G,) i32
        self.commit_rows: Optional[np.ndarray] = None    # (n,) changed rows


class _RoundBuf:
    """One closed ingest round awaiting the fused multi-round dispatch:
    epoch-filtered ack arrays, first-wins-deduped votes, and the round's
    leader-recycle records (applied at round start, device-side).
    ``cells`` optionally carries the precomputed flat (row·P + slot)
    index vector when the staging path shares one geometry across rounds
    (``ack_block_rounds``), sparing a per-round int64 conversion.
    ``reads`` / ``racks`` carry the round's staged ReadIndex batches
    ``(rows, slots, rels, counts)`` and heartbeat echoes
    ``(rows, rslots, peers)`` as flat arrays (None = none).
    ``kvents`` / ``kvreads`` carry the round's devsm entry ops
    ``(rows, slots, rels, keys, vals)`` and KV reads
    ``(rows, rslots, keys)`` the same way."""

    __slots__ = (
        "rows", "slots", "rels", "votes", "churn", "cells", "reads", "racks",
        "kvents", "kvreads",
    )

    def __init__(
        self, rows, slots, rels, votes, churn, cells=None,
        reads=None, racks=None, kvents=None, kvreads=None,
    ):
        self.rows = rows
        self.slots = slots
        self.rels = rels
        self.votes = votes   # list[(row, slot, grant)]
        self.churn = churn   # list[(row, term, term_start_rel, last_rel)]
        self.cells = cells   # np (n,) int64 row*P+slot, or None
        self.reads = reads   # (rows, slots, rels, counts) int32 arrays
        self.racks = racks   # (rows, rslots, peers) int32 arrays
        self.kvents = kvents    # (rows, slots, rels, keys, vals) int32 arrays
        self.kvreads = kvreads  # (rows, rslots, keys) int32 arrays


class BatchedQuorumEngine:
    """Device-resident quorum state for up to ``n_groups`` Raft groups.

    Usage::

        eng = BatchedQuorumEngine(n_groups=1024, n_peers=5)
        eng.add_group(cid, node_ids=[1,2,3], self_id=1, election_timeout=10)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
        eng.ack(cid, node_id=2, index=5)      # ReplicateResp ingest
        out = eng.step()                       # one device dispatch
        out.commit[cid]                        # -> advanced commit index
    """

    def __init__(
        self,
        n_groups: int,
        n_peers: int,
        event_cap: int = DEFAULT_EVENT_CAP,
        sharding=None,
        device_ticks: bool = True,
        dense_ingest: str | bool = "auto",
        n_read_slots: int = READ_SLOTS,
        n_kv_slots: int = KV_SLOTS,
        n_kv_ents: int = KV_ENT_SLOTS,
        n_kv_reads: int = KV_READ_SLOTS,
    ):
        self.n_groups = n_groups
        #: index-vector lengths the row syncs pad to: powers of 16 capped
        #: at the group count (1,024 groups: 16, 256, 1,024)
        self._row_buckets = tuple(
            b for b in (16 ** i for i in range(1, 9)) if b < n_groups
        ) + (n_groups,)
        self.n_peers = n_peers
        self.n_read_slots = n_read_slots
        self.n_kv_slots = n_kv_slots
        self.n_kv_ents = n_kv_ents
        self.n_kv_reads = n_kv_reads
        self.event_cap = event_cap
        #: dense-ingestion policy: collapse a round's acks into a (G,P)
        #: max matrix and dispatch the scatter-free dense kernel (see
        #: kernels.quorum_step_dense_impl — ~7× at full occupancy on TPU).
        #: "auto" picks per dispatch by byte volume: dense uploads
        #: 6·G·P bytes vs ~13 per sparse event, so dense wins once the
        #: staged acks outnumber ~G·P/2.  True forces dense, False never.
        # identity checks: `1 in (True, ...)` would pass by int equality
        if not (
            dense_ingest is True
            or dense_ingest is False
            or dense_ingest == "auto"
        ):
            raise ValueError(
                f"dense_ingest must be True, False, or 'auto', got {dense_ingest!r}"
            )
        self.dense_ingest = dense_ingest
        self._dense_threshold = (n_groups * n_peers) // 2
        #: whether this engine EVER runs tick_step on device.  Contact
        #: events (leader_contact zero-acks) are one-shot, so a ticking
        #: engine must apply the election-clock reset on every round —
        #: including do_tick=False rounds that drain staged acks between
        #: host ticks.  Engines that never tick (host-driven clocks) skip
        #: the reset scatter entirely (it is dead work there).
        self.device_ticks = device_ticks
        self.mirror = HostMirror(
            n_groups, n_peers, n_read_slots, n_kv_slots, n_kv_ents
        )
        self.sharding = sharding
        n_dev = (
            len(getattr(sharding, "device_set", ())) if sharding is not None
            else 1
        )
        # Per-shard dispatch lock.  Engines whose state spans more than
        # one device (GSPMD-partitioned programs with collectives) hold
        # this lock from launch through the blocking egress: XLA's CPU
        # client runs each collective as an all-participant rendezvous on
        # a shared per-device thread pool, and two sharded programs of
        # the SAME engine launched from different threads could otherwise
        # interleave their per-device work and deadlock the rendezvous
        # (programs of one engine are normally ordered by their
        # donated-state data dependency; the lock makes that ordering
        # explicit across host threads).  This used to be a PROCESS-WIDE
        # class lock (`_MULTIDEV_MU`) because independent multi-device
        # engines in one process shared the rendezvous pool too; the mesh
        # dispatch plane (ops/mesh.py) now gives every shard its own
        # single-device engine — no collectives, no rendezvous — so the
        # global mutex died and each engine keeps only its own lock.
        # Reentrant on purpose: step -> step_rounds -> _harvest_inflight
        # all guard themselves.
        self._n_devices = n_dev
        self._dispatch_mu = threading.RLock() if n_dev > 1 else nullcontext()
        #: the slot counts the layout rule needs besides G and P
        self._dims = (n_read_slots, n_kv_slots, n_kv_ents, n_kv_reads)
        # --- the packed carry -------------------------------------------
        # Between steps the 34 leaves live as two blocks
        # (state.StateBlocks): a step retires the blocks and one egress
        # block, not ~90 arrays, each of which cost the round thread a
        # hand-off of the interpreter.  ``dev`` unpacks on demand;
        # steady-state steps never do.
        self._blk: StateBlocks = self._put_state(self.mirror)
        # the blocks the last launch replaced (donated: they hold no
        # device memory) and its egress block once fetched, kept so that
        # their deaths do not stand between a launch and the fan-out of
        # what it committed: dropped by ``drop_retired`` (a coordinator,
        # once its round's commits are offloaded) or by the next launch,
        # so at most one step's are ever held
        self._retired: tuple = ()
        # the host buffers dispatches stage into (_pk.Ingress), restaged
        # in place: a buffer is reused only once the program that read it
        # has handed back its egress.  Bounded: one per shape of the warm
        # plan (``_ingress_keep``: what a live coordinator dispatches),
        # and for every other shape the last-used one of its kind
        self._ingress: Dict[object, Tuple[tuple, _pk.Ingress]] = {}
        self._ingress_keep: set = set()
        self._cache_stale = False
        self.groups: Dict[int, GroupInfo] = {}
        self.rows: Dict[int, GroupInfo] = {}
        # vectorized row→(cluster_id, base) translation for egress: at
        # full occupancy tens of thousands of rows change per round, and
        # a per-row Python dict walk dominates the host loop
        self._row_cid = np.full((n_groups,), -1, np.int64)
        self._row_base = np.zeros((n_groups,), np.int64)
        #: host twin of dev.committed — device state changes only through
        #: _dispatch (whose egress refreshes this) and _upload_dirty
        #: (which syncs the dirty rows), so step() never needs a device
        #: readback just to learn the PREVIOUS watermarks (that readback
        #: was a full extra round trip per step on a network-attached TPU)
        self._committed_cache = np.zeros((n_groups,), np.int32)
        self._free = list(range(n_groups - 1, -1, -1))
        self._dirty: set[int] = set()
        # rows bulk-pulled from the device since the last dispatch
        # (sync_rows); invalidated whenever device state advances
        self._synced: set[int] = set()
        # per-row staging epoch: a state transition bumps it, and events
        # staged under an older epoch are filtered at dispatch.  This is
        # the O(1) replacement for scanning the whole event buffer on
        # every transition (measured 0.66ms per transition at 4k groups —
        # an election burst of 1,024 transitions cost a 680ms round).
        self._row_epoch = np.zeros((n_groups,), np.int32)
        # the epochs' generation: one counter, bumped with every row's
        # epoch.  A staged BLOCK (acks, read stages, read echoes) carries
        # it instead of a per-row copy of ``_row_epoch`` (an index-array
        # read when it is staged and another when it is gathered, each a
        # hand-off of the interpreter on the round thread): a block of
        # the current generation is whole, an older one is filtered
        # against the newest entries of ``_epoch_bumped`` (the rows
        # bumped while a block was staged, kept until the next gather),
        # as many as it is generations old
        self._epoch_gen = 0
        self._epoch_bumped: List[int] = []
        # since the last span: staged blocks that took the per-row
        # comparison (acks; read stages and echoes), tuple-staged read
        # events filtered as tuples, (row, slot) pairs the decode visited
        self._n_stale_blocks = 0
        self._n_stale_read_blocks = 0
        self._n_reads_scalar = 0
        self._n_echoes_scalar = 0
        # pending event buffers (grow unbounded host-side; chunked at
        # dispatch); entries carry the staging epoch as a 4th column
        self._acks: List[Tuple[int, int, int, int]] = []  # row, slot, rel, ep
        self._votes: List[Tuple[int, int, int, int]] = []  # row, slot, g, ep
        self._voted_cells: dict = {}  # (row, slot) -> staging epoch
        # vectorized bulk-ingest blocks (ack_block): (rows, slots, rels,
        # epoch generation)
        self._ack_blocks: List[Tuple[np.ndarray, ...]] = []
        # --- multi-round fused staging (ISSUE 1 tentpole) ---------------
        # closed ingest rounds awaiting ONE fused dispatch (begin_round /
        # step_rounds); each round's epoch filter resolves at close time,
        # so a later transition only purges rounds still open
        self._round_blocks: List[_RoundBuf] = []
        # leader-recycle records of the CURRENT open round (stage_recycle)
        self._churn: List[Tuple[int, int, int, int]] = []
        self._churn_rows: set = set()  # one recycle per row per round
        # rows with an UNDISPATCHED recycle anywhere in the backlog (open
        # round or closed blocks): their mirror rows are authoritative
        # (recycle_row already applied) and host reads must not consult
        # the pre-recycle device row; a rare-path mutation on such a row
        # collapses the recycle to pre-block ordering (_sync_row)
        self._churn_pending: set = set()
        # in-flight pipelined dispatch: (egress block, telem aggregate,
        # (has_reads, has_kv), prev_committed, row_cid snapshot, row_base
        # snapshot, n_rounds) — the ingest of
        # block i+1 overlaps the device execution of block i, and every
        # host read of device state harvests first (_harvest_inflight)
        self._inflight = None
        # --- device read plane staging (ISSUE 3 tentpole) ---------------
        # ReadIndex batches and heartbeat echoes of the CURRENT open
        # round; an epoch column (tuples) or the epoch generation (blocks)
        # filters events staged before a transition, exactly like the
        # ack buffers: (row, slot, rel, count, epoch) / (rows, slots,
        # rels, counts, generation); (row, slot, peer, epoch) / (rows,
        # rslots, peers, generation)
        self._read_stages: List[Tuple[int, int, int, int, int]] = []
        self._read_stage_blocks: List[tuple] = []
        self._read_echoes: List[Tuple[int, int, int, int]] = []
        self._read_echo_blocks: List[tuple] = []
        # (row, slot) pairs the next read-plane decode must visit besides
        # those its own dispatch stages or echoes: the slots pending on a
        # row whose mirror was uploaded since the last such decode (an
        # upload rewrites ``voting`` / ``quorum`` / ``node_state`` /
        # ``live``, which ``kernels.read_confirm`` reads too).  None:
        # the device state was assigned from outside, scan the plane.
        self._read_recheck: Optional[set] = set()
        # host slot bookkeeping.  A slot is BUSY from stage until its
        # batch deterministically confirms: the device only ever sees
        # echoes this host staged, so once the staged echoes of a batch
        # reach quorum (counting self), the batch WILL confirm in its
        # round — the host predicts that without a readback and frees
        # the slot for rounds AFTER the current open one (a same-round
        # restage would overwrite the batch before its echoes land).
        # A batch whose echoes never reach quorum holds its slot until a
        # row transition purges it (the scalar path bounds the same case
        # with request timeouts, requests.py).
        self._read_busy = np.zeros((n_groups, n_read_slots), bool)
        self._read_echo_host = np.zeros(
            (n_groups, n_read_slots, n_peers), bool
        )
        self._read_next_slot = np.zeros((n_groups,), np.int32)
        # round seq of the moment a slot was predicted-confirmed: the
        # slot is reusable only in a LATER round
        self._read_freed_round = np.full((n_groups, n_read_slots), -1, np.int64)
        self._round_seq = 0
        # LATCH: set on the first read-plane ingress (stage/echo/cancel),
        # never reset.  Until it flips, the device read arrays are
        # provably all-zero — they mutate only inside has_reads dispatches,
        # which only staging triggers — and the mirror's are too (row
        # transitions merely re-zero them), so the rare-path row syncs
        # skip them (_sync_keys).  That is not dead-work avoidance: the
        # extra eager gather/scatter programs the read arrays add (incl. a
        # 3-D (rows,S,P) bool scatter) deadlocked XLA's CPU client when
        # several coordinator round threads first-compiled them while
        # other multi-device dispatches were in flight on the 8-virtual-
        # device mesh (test_full_stack_sharded_engine hung in
        # _upload_dirty).  A read-free engine keeps the exact eager
        # program set it had before the read plane existed.
        self._read_plane_used = False
        # --- device state machine staging (devsm, ISSUE 11) -------------
        # LATCH, same contract as _read_plane_used: until the first devsm
        # ingress (stage_kv_ops / stage_kv_read / kv_restore) the kv
        # arrays are provably at their reset values, every dispatch runs
        # has_kv=False, the rare-path row syncs skip the kv fields
        # (_sync_keys) and the recycle purge compiles out (purge_kv) — an
        # SM-free engine keeps today's host cost and eager-op set
        # bit-identical.
        self._devsm_used = False
        # --- hierarchical commit plane (hier, ISSUE 18) ------------------
        # LATCH, same contract as _read_plane_used/_devsm_used: until the
        # first enabling set_hier the near/sub_quorum arrays are provably
        # all-zero, every dispatch runs has_hier=False — the compiled
        # program set stays byte-identical to the pre-hier build — and
        # the rare-path row syncs skip the hier fields (_sync_keys).
        # Flipping the latch makes the next dispatch of each variant
        # compile its has_hier=True twin once (the late-devsm precedent);
        # hier deployments install domain geometry at registration /
        # first promotion, ahead of steady-state load.
        self._hier_used = False
        # --- device telemetry plane (telem, ISSUE 20) --------------------
        # LATCH, same contract as _hier_used: until enable_telem flips it,
        # telem_prev_committed is provably all-zero, every dispatch runs
        # has_telem=False — the compiled program set stays byte-identical
        # to the pre-telem build — and the rare-path row syncs skip the
        # telem field (_sync_keys).  Flip BEFORE warmup_fused (NodeHost
        # wires health_aggregate into the coordinator constructor for
        # exactly this) so the warmed programs carry the fold; a late
        # flip compiles each variant's has_telem=True twin on next use
        # (the late-devsm precedent).
        self._telem_used = False
        # --- a group's sleep (Config.quiesce, ISSUE 44) ------------------
        # LATCH, same contract as _hier_used: until ``enable_quiesce``
        # flips it the three idle columns are provably at their reset
        # values, every dispatch runs has_quiesce=False (the program set
        # of an engine with no quiesce group is the one built without the
        # columns' arithmetic) and the row syncs skip them (_sync_keys).
        # The coordinator flips it at the first quiesce group's
        # registration; a warm-up under way or done starts over, so the
        # live path never meets an unwarmed has_quiesce twin.
        self._quiesce_used = False
        self._warm_gen = 0
        self._warm_args = None
        # static top-K width of the fold's drill-down egress; changing it
        # after programs compiled recompiles them, so it is ctor/enable
        # time configuration, not a per-dispatch knob
        self.n_telem_topk = TELEM_TOPK
        # last harvested aggregate: raw device arrays + the dispatch-time
        # row->cid capture, materialized into the snapshot dict LAZILY
        # (telem_snapshot) — per-dispatch harvest cost is one tuple
        # store, the numpy conversion runs at sampler cadence instead of
        # dispatch cadence
        self._last_telem = None
        self._telem_raw = None
        self._telem_seq = 0
        # host record of the rel index staged in each device entry-buffer
        # slot (-1 = free): slot ``rel % E`` is reusable once the
        # HARVESTED commit watermark has passed its tenant (the device
        # frees it the round the entry applies; the host learns at
        # harvest).  Ops whose slot is still occupied queue per row in
        # _kv_queue and drain — in log order — as harvests free slots.
        self._kv_ent_rel = np.full((n_groups, n_kv_ents), -1, np.int64)
        self._kv_queue: Dict[int, "deque"] = {}
        # staged-but-undispatched kv ops / reads of the CURRENT open
        # round, epoch-tagged like every other staging buffer:
        # (row, slot, rel, key, val, epoch) / (row, rslot, key, epoch)
        self._kv_stage: List[Tuple[int, int, int, int, int, int]] = []
        self._kv_read_stage: List[Tuple[int, int, int, int]] = []
        # a staged KV read captures in exactly its round, so its slot is
        # busy from stage until that dispatch's harvest reports the
        # capture (or a row transition purges it)
        self._kv_read_busy = np.zeros((n_groups, n_kv_reads), bool)
        # capture-egress callback (the devsm plane's read service): fired
        # with the StepResult of EVERY harvest that carried captures —
        # including rare-path internal harvests whose results the caller
        # never sees (a row sync forcing _harvest_inflight would
        # otherwise strand parked readers until their timeout)
        self.kv_egress_hook = None
        # --- device-plane observability (ISSUE 5 tentpole) --------------
        # OFF by default: self._obs stays None and every hot-path site
        # gates on a plain `is not None` check, so an obs-off engine keeps
        # a bit-identical host path and eager-op set (the _read_plane_used
        # precedent; parity asserted by bench._run_obs_axis).  The module
        # latch (obs.enable) flips newly built engines on; live wiring
        # goes through NodeHostConfig.enable_metrics -> the coordinator.
        self._obs = None
        self._obs_span = None      # span of the in-flight fused dispatch
        self._obs_kv_span = None   # apply_kernel span of the same dispatch
        self._obs_mu_wait = 0.0    # _dispatch_mu wait of the next dispatch
        self._obs_upload = 0       # upload bytes of the current dispatch
        self._n_retired = 0        # arrays retired since the last span
        # --- device capacity & profiling plane (ISSUE 15) ---------------
        # LATCH, same contract as _obs: None by default, every hot-path
        # site gates on `is not None`, so a profile-off engine keeps a
        # bit-identical host path.  Attached via enable_devprof (live
        # wiring: NodeHostConfig.device_profile → the coordinator).  The
        # attached DevProf samples 1-in-N dispatches with a
        # block_until_ready delta (the device-time estimator), accounts
        # fused padding waste, and walks self._dev for the HBM ledger.
        self._devprof = None
        # seq of the newest recorded dispatch span (-1 = none / obs off):
        # the request tracer links this into sampled traces' device_round
        # stage (ISSUE 9); written only inside the obs-gated branches
        self.last_span_seq = -1
        if _obs.enabled():
            self.enable_obs()
        # --- AOT warm-compile of the fused variants (ISSUE 7 tentpole) --
        # The latch gates the LIVE coordinator's fused dispatches: until
        # warmup has compiled the padded (K,G,P) program set, rounds fall
        # back to the already-compiled single-round path, so a proposal
        # never blocks behind a first-use XLA compile (0.5-4s measured on
        # the loaded 2-vCPU box).  Bulk drivers (bench ladder, native
        # control planes) may keep calling step_rounds without warmup —
        # they pay first-use compiles by construction and don't care.
        self._fused_ready = threading.Event()
        # devsm program readiness (set by a warmup that included the
        # has_kv variants, or by a later warmup_devsm): the coordinator
        # only FUSES kv-carrying blocks once these compiled — before
        # that they take the single-round dense path
        self._kv_fused_ready = threading.Event()
        self._warmup_thread: Optional[threading.Thread] = None
        self._kv_warmup_thread: Optional[threading.Thread] = None
        self._warmup_mu = threading.Lock()
        self._warmup_cancel = threading.Event()
        self.warmup_stats = {
            "seconds": 0.0, "programs": 0,
            "cache_hits": 0, "cache_misses": 0, "error": None,
        }

    def enable_obs(self, recorder=None, registry=None, shard=None,
                   host=None):
        """Attach device-plane instruments (``obs.instruments.EngineObs``):
        per-dispatch flight-recorder spans plus the ``dragonboat_device_*``
        metric families in ``registry`` (default: the process registry
        ``events.DEFAULT_REGISTRY`` that ``write_health_metrics`` exposes).
        Returns the attached instruments.  A repeat call with no arguments
        is a no-op; passing ``recorder``/``registry`` REBINDS the
        instruments — an engine self-attached by the module latch must not
        swallow a later explicit wiring (NodeHost routing the families
        into ITS registry would otherwise silently publish to the default
        one and expose nothing).  ``shard`` tags this engine's dispatch
        spans with its mesh shard index (``ops/mesh.py`` wiring — all
        shards share ONE recorder, the tag tells their streams apart);
        ``host`` with the owning NodeHost (co-hosted NodeHosts share one
        recorder too)."""
        if self._obs is not None and recorder is None and registry is None:
            if host is not None:
                self._obs.host = host
            return self._obs
        from ..obs.instruments import EngineObs

        # `is None`, not truthiness: an EMPTY recorder is falsy
        # (__len__ == 0) and must still be honored
        if recorder is None:
            recorder = (
                self._obs.recorder if self._obs is not None
                else _obs.default_recorder()
            )
        if host is None and self._obs is not None:
            host = self._obs.host
        self._obs = EngineObs(
            recorder, registry=registry, shard=shard, host=host
        )
        return self._obs

    def disable_obs(self) -> None:
        self._obs = None

    def set_span_parent(self, seq: Optional[int]) -> None:
        """The coordinator round now driving this engine (its
        ``coord_round`` span's ``seq``): stamped as ``parent`` into the
        dispatch spans recorded until the next call.  Callers gate on
        their own obs latch."""
        obs = self._obs
        if obs is not None:
            obs.parent = seq

    def enable_devprof(self, devprof) -> None:
        """Attach a :class:`dragonboat_tpu.obs.devprof.DevProf` plane:
        sampled device-time estimation, fused padding-waste accounting
        and the HBM ledger all key off this latch (``is not None`` on
        every hot-path site — the ``_obs`` contract exactly)."""
        self._devprof = devprof

    def disable_devprof(self) -> None:
        self._devprof = None

    def enable_telem(self, topk: int | None = None) -> None:
        """Flip the device telemetry latch (ISSUE 20): every subsequent
        dispatch runs its ``has_telem=True`` variant, folding the shard's
        health aggregate (``kernels.telem_fold``) into the egress it
        already pays for.  One-way, like the other plane latches — the
        telem field starts participating in rare-path row syncs and
        recycle purges the moment it can be nonzero.  Call BEFORE
        ``warmup_fused`` to get the fold into the warmed program set; a
        later call recompiles each variant once on next use.  ``topk``
        sets the fold's static drill-down width (default
        ``kernels.TELEM_TOPK``); it must not change after programs
        compiled against it."""
        if topk is not None:
            self.n_telem_topk = int(topk)
        self._telem_used = True

    @property
    def telem_enabled(self) -> bool:
        return self._telem_used

    def enable_quiesce(self) -> None:
        """Flip the quiesce latch (one-way, idempotent): from the next
        dispatch on the programs carry ``has_quiesce`` (the tick kernel's
        idle clocks, the sleep / wake marks on the reserved last peer
        slot of the ack plane).  A warm-up that has started, or finished,
        compiled the other program set: it is abandoned and started over
        with the same arguments, and ``fused_ready`` is False until the
        new set is compiled."""
        with self._warmup_mu:
            if self._quiesce_used:
                return
            self._quiesce_used = True
            args = self._warm_args
            rewarm = args is not None and (
                self._warmup_thread is not None or self._fused_ready.is_set()
            )
            if rewarm:
                self._warm_gen += 1  # the thread under way gives up
                self._warmup_thread = None
                self._fused_ready.clear()
        if rewarm:
            self.warmup_fused(*args)

    @property
    def quiesce_enabled(self) -> bool:
        return self._quiesce_used

    def telem_snapshot(self) -> dict | None:
        """The last harvested telemetry aggregate, or None before the
        first telem-carrying harvest (or while the plane is off).

        PASSIVE by design: the aggregate refreshes whenever a dispatch's
        egress is harvested — the plane adds no dispatches of its own,
        so an idle engine serves a stale snapshot.  Consumers read
        ``seq``/``mono`` for staleness; the health sampler's cadence
        rides the coordinator round loop, which dispatches every tick.

        LAZY materialization: the harvest stores the raw device arrays
        (one tuple assignment on the dispatch path); the numpy pull +
        dict build runs here, at CONSUMER cadence — the sampler reads
        ~once per 50ms while a loaded shard harvests hundreds of folds
        a second, and eager per-harvest conversion showed up as
        dispatch overhead in the telem bench axis."""
        raw = self._telem_raw
        if raw is not None:
            tel, row_cid, rounds, mono, seq = raw
            self._telem_raw = None
            self._ingest_telem(tel, row_cid, rounds, mono, seq)
        t = self._last_telem
        return dict(t) if t is not None else None

    def _stage_telem(self, tel, row_cid, rounds: int) -> None:
        """Record one harvested TelemAggregate for lazy materialization.
        ``row_cid`` must be the DISPATCH-TIME capture (copied), so a
        re-registration between dispatch and snapshot can't mislabel a
        drill-down row."""
        self._telem_seq += 1
        self._telem_raw = (
            tel, row_cid, rounds, time.monotonic(), self._telem_seq
        )

    def _ingest_telem(self, tel, row_cid, rounds, mono, seq) -> None:
        """Translate a TelemAggregate into the host snapshot dict."""
        state_counts = np.asarray(tel.state_counts, dtype=np.int64)
        rows = np.asarray(tel.topk_row)
        lags = np.asarray(tel.topk_lag)
        topk = [
            (int(row_cid[r]), int(lag))
            for r, lag in zip(rows, lags)
            if r >= 0 and row_cid[r] >= 0
        ]
        self._last_telem = {
            "seq": seq,
            "mono": mono,
            "rounds": int(rounds),
            "groups": int(state_counts.sum()),
            "lag_hist": [int(v) for v in np.asarray(tel.lag_hist)],
            "state_counts": [int(v) for v in state_counts],
            "stalled": int(tel.stalled),
            "read_slots": int(tel.read_slots),
            "kv_ents": int(tel.kv_ents),
            "topk": topk,
        }

    # ------------------------------------------------------------------
    # AOT warm-compile (ISSUE 7 tentpole)
    # ------------------------------------------------------------------

    @property
    def fused_ready(self) -> bool:
        """True once the warmup pass has compiled the fused live-path
        program set (the coordinator's gate for K>1 dispatches)."""
        return self._fused_ready.is_set()

    @property
    def kv_fused_ready(self) -> bool:
        """True once the devsm (has_kv) program variants compiled."""
        return self._kv_fused_ready.is_set()

    def warmup_fused(
        self,
        k_buckets=WARM_K_BUCKETS,
        include_reads: bool = True,
        include_single: bool = True,
        background: bool = True,
        include_kv: bool = False,
    ):
        """Pre-compile the live path's device programs against a THROWAWAY
        state of identical shapes/shardings, so first use on the live
        state hits the jit cache instead of stalling proposals 0.5-4s
        behind XLA.

        The set is small and closed: the fused ``quorum_multiround``
        variant per K bucket (reads on/off; votes stay OFF — the live
        coordinator routes vote-carrying rounds to the single-round path,
        elections want the fastest round, not a batched one), plus — with
        ``include_single`` — the sparse tick/no-tick single-round
        programs and the dense read-carrying ones the per-round fallback
        uses.  Warm dispatches run real (empty, all-rows-dead) programs,
        so the jit cache is populated by construction, and with the
        persistent compilation cache enabled
        (:func:`enable_persistent_compilation_cache`) a restarted process
        deserializes instead of compiling.

        ``background=True`` (default) runs on a niced daemon thread and
        returns it; the readiness latch (:attr:`fused_ready`) flips only
        after every fused variant compiled.  Repeat calls are no-ops.

        ``include_kv`` adds the devsm (``has_kv``) fused and dense
        variants — the coordinator passes it when a
        ``DeviceKVStateMachine`` group is expected; SM-free hosts keep
        the historical warm set and cost.  A devsm group registering
        AFTER warmup warms its variants separately
        (:meth:`warmup_devsm`).
        """
        args = (tuple(k_buckets), include_reads, include_single, include_kv)
        with self._warmup_mu:
            if self._warmup_thread is not None or self._fused_ready.is_set():
                return self._warmup_thread
            # (what ``enable_quiesce`` starts over with)
            self._warm_args = (
                tuple(k_buckets), include_reads, include_single, background,
                include_kv,
            )
            gen = self._warm_gen
            if background:
                t = threading.Thread(
                    target=self._warmup_main, args=args + (gen,),
                    name="engine-warmup", daemon=True,
                )
                self._warmup_thread = t
                t.start()
                return t
        self._warmup_main(*args, gen)
        return self.warmup_stats

    def warmup_devsm(self, k_buckets=WARM_K_BUCKETS, background: bool = True):
        """Warm ONLY the devsm (``has_kv``) program variants — the
        late-registration path: a ``DeviceKVStateMachine`` group joining
        a coordinator whose main warmup ran kv-free must not stall its
        first fused dispatch behind XLA.  Until :attr:`kv_fused_ready`
        flips, kv-carrying rounds take the single-round dense path."""
        args = (tuple(k_buckets),)
        with self._warmup_mu:
            if (
                self._kv_warmup_thread is not None
                or self._kv_fused_ready.is_set()
            ):
                return self._kv_warmup_thread
            if background:
                t = threading.Thread(
                    target=self._warmup_devsm_main, args=args,
                    name="engine-warmup-devsm", daemon=True,
                )
                self._kv_warmup_thread = t
                t.start()
                return t
        self._warmup_devsm_main(*args)
        return self.warmup_stats

    def _warmup_devsm_main(self, k_buckets) -> None:
        try:
            # same deprioritization as the main warm thread: these XLA
            # compiles run for tens of seconds and an un-niced compile
            # thread starves raft/transport on a core-starved box —
            # observed as leadership churn for the whole warm window
            if threading.current_thread() is self._kv_warmup_thread:
                try:
                    os.setpriority(
                        os.PRIO_PROCESS, threading.get_native_id(), 10
                    )
                except (OSError, AttributeError):
                    pass
            scratch = self._scratch_blocks()
            for kind, a, hr, kv in self._kv_plan(k_buckets):
                if self._warmup_cancel.is_set():
                    return
                scratch = self._warm_one(scratch, kind, a, hr, kv)
                self.warmup_stats["programs"] += 1
            self._kv_fused_ready.set()
        except Exception as e:  # latch stays unset; dense path serves kv
            elog.warning("devsm warmup failed (kv stays single-round): %r", e)
            self.warmup_stats["error"] = repr(e)

    @staticmethod
    def _kv_plan(k_buckets):
        """The devsm program variants: fused per K bucket with and
        without the read plane riding along (a devsm round may carry
        ReadIndex echoes too), plus the dense single-round fallbacks."""
        plan = [
            ("fused", k, hr, True)
            for k in sorted({int(k) for k in k_buckets})
            for hr in (False, True)
        ]
        plan += [("dense", dt, hr, True) for dt in (True, False)
                 for hr in (False, True)]
        return plan

    def warm_plan(
        self,
        k_buckets=WARM_K_BUCKETS,
        include_reads: bool = True,
        include_single: bool = True,
        include_kv: bool = False,
    ):
        """The closed live-path program set as ``(kind, arg, has_reads,
        has_kv)`` tuples — the ONE enumeration both the warmup pass
        (``_warmup_main``) and the devprof program registry
        (``obs/devprof.py`` via :meth:`lower_variant`) walk, so the
        registry can never analyze a program the live path doesn't run
        nor miss one it does."""
        read_set = (False, True) if include_reads else (False,)
        plan = [
            ("fused", k, hr, False)
            for k in sorted({int(k) for k in k_buckets})
            for hr in read_set
        ]
        if include_single:
            plan += [("sparse", dt, False, False) for dt in (True, False)]
            # elections dispatch the vote-carrying sparse variant; warm
            # it so the first campaign after enable doesn't compile
            plan += [
                ("sparse_votes", dt, False, False) for dt in (True, False)
            ]
            # a round whose acks pass ``_dense_threshold`` (a block of
            # groups all acknowledging in one round; a campaign wave
            # with its votes) takes the dense kernel with no read plane:
            # first used on the round thread at a thousand rows, its
            # compile is a stall of seconds exactly when a cluster
            # elects or is busiest
            plan += [
                (kind, dt, False, False)
                for kind in ("dense", "dense_votes") for dt in (True, False)
            ]
            if include_reads:
                # ... and with the read plane riding along; the vote
                # twin too: a host that campaigns for one group while it
                # serves another's reads dispatches both in one round
                plan += [
                    (kind, dt, True, False)
                    for kind in ("dense", "dense_votes")
                    for dt in (True, False)
                ]
        if include_kv:
            plan += self._kv_plan(k_buckets)
        return plan

    @staticmethod
    def variant_label(kind: str, arg, has_reads: bool, has_kv: bool) -> str:
        """Stable display name of a warm-plan variant (warmup spans and
        the devprof "Device programs" table share it)."""
        return (
            f"{kind}:k{arg}" if kind == "fused"
            else f"{kind}:{'tick' if arg else 'notick'}"
        ) + (":reads" if has_reads else "") + (":kv" if has_kv else "")

    def cancel_warmup(self) -> None:
        """Stop warming after the current variant (coordinator shutdown);
        a cancelled warmup leaves the latch unset — the fallback
        single-round path simply stays in effect."""
        self._warmup_cancel.set()

    def _warmup_main(
        self, k_buckets, include_reads, include_single, include_kv=False,
        gen: int = 0,
    ) -> None:
        t0 = time.perf_counter()
        try:
            # same deprioritization as the coordinator round thread: a
            # multi-second XLA compile must not starve raft/transport
            # threads on a core-starved box (that contention was the
            # original reason the live path avoided fused variants).
            # ONLY on the dedicated warm thread — a foreground
            # (background=False) caller must not have its thread left
            # permanently niced.
            if threading.current_thread() is self._warmup_thread:
                try:
                    os.setpriority(
                        os.PRIO_PROCESS, threading.get_native_id(), 10
                    )
                except (OSError, AttributeError):
                    pass
            hits0, miss0 = _CC["hits"], _CC["misses"]
            scratch = self._scratch_blocks()
            scratch = self._warm_row_syncs(scratch, include_reads)
            plan = self.warm_plan(
                k_buckets, include_reads, include_single, include_kv
            )
            for kind, a, hr, kv in plan:
                if self._warmup_cancel.is_set():
                    self.warmup_stats["error"] = "cancelled"
                    return
                if gen != self._warm_gen:
                    return  # enable_quiesce started the warm-up over
                tv = time.perf_counter()
                scratch = self._warm_one(scratch, kind, a, hr, kv)
                dt_s = time.perf_counter() - tv
                self.warmup_stats["programs"] += 1
                obs = self._obs  # re-read: may attach mid-warmup
                if obs is not None:
                    obs.warmup(
                        variant=self.variant_label(kind, a, hr, kv),
                        seconds=dt_s,
                    )
            with self._warmup_mu:
                if gen != self._warm_gen:
                    return
                self.warmup_stats["seconds"] = time.perf_counter() - t0
                self.warmup_stats["cache_hits"] = _CC["hits"] - hits0
                self.warmup_stats["cache_misses"] = _CC["misses"] - miss0
                self._fused_ready.set()
            if include_kv:
                self._kv_fused_ready.set()
            elog.info(
                "engine warmup: %d programs in %.2fs (cache: %d hits, "
                "%d misses)",
                self.warmup_stats["programs"], self.warmup_stats["seconds"],
                self.warmup_stats["cache_hits"],
                self.warmup_stats["cache_misses"],
            )
        except Exception as e:  # latch stays unset; live path unaffected
            self.warmup_stats["error"] = repr(e)
            self.warmup_stats["seconds"] = time.perf_counter() - t0
            elog.warning("engine warmup failed (fused path stays off): %r", e)

    def _variant_args(
        self, kind: str, arg, has_reads: bool, has_kv: bool = False,
        abstract: bool = False,
    ):
        """Program, ingress block (state excluded) and static kwargs for
        one warm-plan variant.  ``abstract=False`` builds the concrete
        nothing-staged ingress the warm dispatch runs (``_warm_one``);
        ``abstract=True`` a :class:`jax.ShapeDtypeStruct` stand-in for
        the devprof program registry's AOT ``lower().compile()``
        (``lower_variant``) — ONE builder, so the registry analyzes
        byte-for-byte the programs the warmup compiled.  The statics
        must mirror the live call sites EXACTLY — a near-miss warms a
        program the live path never uses; the ingress shape cannot miss,
        both sides take it from ``_pk.ingress_sections``.  A concrete
        variant's shape joins ``_ingress_keep``: the live path keeps the
        staging buffer of a warmed shape (``_ingress_for``)."""
        if kind == "fused":
            fn, layout = _pk.quorum_multiround, dict(
                k=arg, c=0, do_tick=True, has_votes=False, has_churn=False,
                has_reads=has_reads, has_kv=has_kv,
            )
            statics = dict(
                layout,
                track_contact=True,
                purge_reads=False,
                purge_kv=False,
                purge_telem=False,
            )
        elif kind in ("dense", "dense_votes"):
            do_tick = arg
            fn, layout = _pk.quorum_step_dense, dict(
                has_votes=kind == "dense_votes", has_reads=has_reads,
                has_kv=has_kv,
            )
            kind = "dense"
            statics = dict(
                layout,
                do_tick=do_tick,
                track_contact=self.device_ticks or do_tick,
            )
        else:  # sparse single-round (the quiet-path workhorse)
            do_tick = arg
            fn, layout = _pk.quorum_step, dict(
                cap=self.event_cap, has_votes=kind == "sparse_votes",
            )
            kind = "sparse"
            statics = dict(
                layout,
                do_tick=do_tick,
                track_contact=self.device_ticks or do_tick,
                **self._fold_hints(),
            )
        sections = _pk.ingress_sections(
            kind, self.n_groups, self.n_peers, self._dims, **layout
        )
        if not abstract:  # warmed: a live shape, its buffer is kept
            self._ingress_keep.add(self._ingress_key(kind, layout))
        ing = (
            jax.ShapeDtypeStruct((_pk.ingress_size(sections),), np.int32)
            if abstract else _pk.Ingress(sections).buf
        )
        return fn, ing, dict(statics, **self._engine_statics())

    def _engine_statics(self) -> dict:
        """The statics every program of this engine takes, whatever the
        dispatch carries (``_launch`` adds them; the warm plan too)."""
        return dict(
            dims=self._dims,
            has_hier=self._hier_used,
            has_telem=self._telem_used,
            telem_k=self.n_telem_topk,
            has_quiesce=self._quiesce_used,
        )

    def _fold_hints(self) -> dict:
        """The sparse program's occupancy hints for the telem fold (it
        never carries read/kv event planes).  Statics key the jit cache
        whether the program reads them or not — and an omitted one and
        an explicit False key it apart — so they are held False without
        the fold: the first read must not flip them and recompile the
        warmed sparse programs on the round thread."""
        return dict(
            has_reads=self._telem_used and self._read_plane_used,
            has_kv=self._telem_used and self._devsm_used,
        )

    def _warm_one(
        self, scratch: StateBlocks, kind: str, arg, has_reads: bool,
        has_kv: bool = False,
    ):
        """Compile-and-run one variant against the scratch blocks
        (donated; their successors are returned)."""
        fn, ing, statics = self._variant_args(kind, arg, has_reads, has_kv)
        with self._dispatch_mu:  # multi-device programs take the lock
            out = fn(scratch, ing, **statics)
            jax.block_until_ready(out.egress)
        return out.blocks

    def lower_variant(
        self, kind: str, arg, has_reads: bool, has_kv: bool = False
    ):
        """AOT-lower one warm-plan variant against abstract shapes — no
        allocation, no dispatch.  ``.compile()`` on the result yields the
        XLA executable's ``cost_analysis()`` / ``memory_analysis()``:
        the devprof program registry's per-program flops/bytes/peak-temp
        figures (ISSUE 15).  With the persistent compilation cache
        enabled the compile step deserializes the warmed executable
        instead of recompiling."""
        fn, ing, statics = self._variant_args(
            kind, arg, has_reads, has_kv, abstract=True
        )
        # a mesh-sharded engine's live/warmed programs are GSPMD
        # partitions of the state — lowering unsharded here would
        # analyze an executable the cluster never runs (and miss the
        # persistent cache).  The ingress stays unsharded, matching the
        # live call sites (host numpy → replication decided by GSPMD,
        # exactly as _warm_one dispatches it).
        sh = block_sharding(self.sharding)
        st = jax.tree_util.tree_map(
            lambda b: jax.ShapeDtypeStruct(b.shape, b.dtype, sharding=sh),
            self._blk,
        )
        return fn.lower(st, ing, **statics)

    @staticmethod
    def _obs_gate(do_tick, acks, votes, recycles, reads, echoes) -> str:
        """Why the dispatch fired, for the span record."""
        parts = []
        if do_tick:
            parts.append("tick")
        if recycles:
            parts.append("churn")
        if acks or votes:
            parts.append("acks")
        if reads or echoes:
            parts.append("reads")
        return "+".join(parts) or "drain"

    def _put_state(self, mirror: HostMirror) -> StateBlocks:
        """``mirror``'s arrays on the device, as blocks."""
        sh = block_sharding(self.sharding)
        return StateBlocks(*(
            jax.device_put(b, sh)
            for b in pack_state(QuorumState(**mirror.arrays), np)
        ))

    def _scratch_blocks(self) -> StateBlocks:
        """An all-dead state of this engine's shapes and shardings, for
        the warm-up passes to run (and donate) programs against."""
        return self._put_state(HostMirror(
            self.n_groups, self.n_peers, self.n_read_slots,
            self.n_kv_slots, self.n_kv_ents,
        ))

    def state_nbytes(self) -> Dict[str, int]:
        """Resident bytes of every state leaf, by field name, from the
        shapes alone (the HBM ledger's walk: a leaf costs in its block
        what it cost as an array of its own)."""
        return {
            name: int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for name, leaf in jax.eval_shape(
                lambda b: _pk.unpack_state(b, self._dims[:3]), self._blk
            )._asdict().items()
        }

    @property
    def dev(self) -> QuorumState:
        """The state as the kernels see it, unpacked on demand (tests,
        benches, the HBM ledger; the steady-state step never asks).
        Point-in-time: the next dispatch donates the blocks it was read
        from, not these leaves."""
        with self._dispatch_mu:
            return _pk.unpack(self._blk, dims=self._dims[:3])

    @dev.setter
    def dev(self, st: QuorumState) -> None:
        """External state assignment (hybrid direct-dispatch callers) —
        the host committed twin can no
        longer be trusted, so the next step() re-reads it from the device
        once instead of mis-reporting commit deltas."""
        self._harvest_inflight()
        with self._dispatch_mu:
            blocks = _pk.pack(st)
            if self.sharding is not None:
                blocks = jax.device_put(blocks, block_sharding(self.sharding))
        self._blk = blocks
        self._cache_stale = True
        self._read_recheck = None
        self._synced.clear()

    # ------------------------------------------------------------------
    # group lifecycle (rare path, host scalar)
    # ------------------------------------------------------------------

    def add_group(
        self,
        cluster_id: int,
        node_ids: List[int],
        self_id: int,
        election_timeout: int = 10,
        heartbeat_timeout: int = 1,
        rand_timeout: Optional[int] = None,
        check_quorum: bool = False,
        witnesses: Tuple[int, ...] = (),
        observers: Tuple[int, ...] = (),
        quiesce_threshold: int = 0,
    ) -> GroupInfo:
        """``quiesce_threshold`` > 0 (``enable_quiesce`` first): the row
        goes to sleep after that many ticks without activity; its sleep /
        wake marks ride the LAST peer slot of the ack plane, which such a
        group's members therefore leave free."""
        if cluster_id in self.groups:
            raise ValueError(f"group {cluster_id} already registered")
        if not self._free:
            raise RuntimeError("quorum engine full")
        all_ids = sorted(set(node_ids) | set(witnesses) | set(observers))
        if quiesce_threshold > 0 and not self._quiesce_used:
            raise ValueError("quiesce row on an engine without enable_quiesce")
        if len(all_ids) > self.n_peers - (1 if quiesce_threshold > 0 else 0):
            raise ValueError("too many peers for tensor width")
        row = self._free.pop()
        slots = {nid: i for i, nid in enumerate(all_ids)}
        gi = GroupInfo(
            cluster_id, row, slots, node_ids=all_ids,
            self_slot=slots[self_id],
        )
        self.groups[cluster_id] = gi
        self.rows[row] = gi
        self._row_cid[row] = cluster_id
        self._row_base[row] = 0

        a = self.mirror.arrays
        a["live"][row] = True
        a["node_state"][row] = FOLLOWER
        a["term"][row] = 0
        a["committed"][row] = 0
        a["last_index"][row] = 0
        a["term_start"][row] = 0
        n_voting = len(set(node_ids) | set(witnesses))
        a["quorum"][row] = n_voting // 2 + 1
        a["self_slot"][row] = gi.self_slot
        a["election_tick"][row] = 0
        a["heartbeat_tick"][row] = 0
        a["election_timeout"][row] = election_timeout
        a["heartbeat_timeout"][row] = heartbeat_timeout
        a["rand_timeout"][row] = (
            rand_timeout if rand_timeout is not None else election_timeout * 2
        )
        is_voter = self_id in node_ids or self_id in witnesses
        a["electable"][row] = is_voter and self_id not in witnesses
        a["check_quorum_on"][row] = check_quorum
        a["match"][row, :] = 0
        a["next"][row, :] = 1
        a["voting"][row, :] = False
        a["present"][row, :] = False
        a["active"][row, :] = False
        a["votes"][row, :] = VOTE_NONE
        for nid, slot in slots.items():
            a["present"][row, slot] = True
            a["voting"][row, slot] = nid not in observers
        if self._read_plane_used:  # else provably already clear
            self.mirror.clear_reads(row)
            self._reset_read_rows([row])
        if self._devsm_used:  # fresh registration starts from an empty KV
            self.mirror.clear_kv(row)
            self._reset_kv_rows([row])
        if self._hier_used:  # else provably already clear
            a["near"][row, :] = False
            a["sub_quorum"][row] = 0
        if self._quiesce_used:  # else provably already clear
            a["quiesce_threshold"][row] = max(int(quiesce_threshold), 0)
            self._wake_mirror_row(row)
        self._dirty.add(row)
        return gi

    def _wake_mirror_row(self, row: int) -> None:
        """A row (re)built or moved by a transition is awake with a fresh
        idle clock: whatever caused it was activity."""
        a = self.mirror.arrays
        a["idle_tick"][row] = 0
        a["quiesced"][row] = False

    def quiesce_mark(self, cluster_id: int, wake: bool) -> None:
        """Stage a quiesce row's mark for the next dispatch: ``wake`` (any
        activity: the idle clock restarts, a sleeping row wakes with both
        raft clocks at zero) or sleep (a peer's QUIESCE).  It rides the
        ack plane on the reserved last peer slot, so it is ordered, epoch
        filtered and dispatched with the round's acknowledgements; where
        both are staged for one round the wake wins."""
        gi = self.groups[cluster_id]
        self._acks.append((
            gi.row, self.n_peers - 1,
            QUIESCE_MARK_WAKE if wake else QUIESCE_MARK_SLEEP,
            int(self._row_epoch[gi.row]),
        ))

    def _purge_row_events(self, row: int) -> None:
        """Invalidate queued acks/votes for a row.  Called on every state
        transition (and removal): events staged before the transition
        belong to the old term and must never reach the new term's tally
        (the scalar twin drops mismatched-term responses in
        ``handle_vote_resp`` / ``handle_replicate_resp``).  O(1): the row's
        staging epoch is bumped and stale-epoch events are filtered at
        dispatch (``_gather_acks``).

        Pending READS die with the transition too (scalar twin: every
        ``become_*`` builds a fresh ``ReadIndex``) — slot bookkeeping and
        the mirror's read fields reset here; staged read/echo events fall
        to the same epoch filter as acks/votes.

        Devsm: BUFFERED entry ops die too (they sit strictly above the
        commit watermark — an uncertain log suffix the next leadership
        may rewrite), while the applied ``kv_value`` rows persist exactly
        like a scalar SM across terms.  Queued ops, staged slots and
        pending read captures drop with the host bookkeeping reset."""
        self._row_epoch[row] += 1
        self._epoch_gen += 1
        if (
            self._ack_blocks or self._read_stage_blocks
            or self._read_echo_blocks
        ):  # only a staged block can be older than this
            self._epoch_bumped.append(row)
        self._reset_read_rows([row])
        if self._read_plane_used:  # else provably already clear
            self.mirror.clear_reads(row)
        self._reset_kv_rows([row])
        if self._devsm_used:  # else provably already clear
            self.mirror.clear_kv_ents(row)

    def _drop_churn_records(self, row: int, drop_events: bool = False) -> None:
        """Strip every undispatched recycle record for ``row`` — from the
        open round AND from closed blocks awaiting dispatch.  A stale
        record surviving into the program would revive a freed row (or
        clobber its next tenant) with the dead recycle's reset.

        ``drop_events=True`` additionally strips the row's ack/vote
        events from CLOSED blocks.  Required when the recycle collapses
        to pre-block ordering (a rare-path mutation, ``_sync_row``): the
        row's fresh state uploads before the block, so old-tenant events
        sealed into earlier rounds — whose epoch filters resolved at
        close time, immune to the recycle's epoch bump — would otherwise
        scatter into the NEW tenant.  This restores the single-round
        path's semantics, where a transition purges every staged event
        for its row."""
        if row in self._churn_rows:
            self._churn = [c for c in self._churn if c[0] != row]
            self._churn_rows.discard(row)
        if row in self._churn_pending:
            for b in self._round_blocks:
                if b.churn:
                    b.churn = [c for c in b.churn if c[0] != row]
            self._churn_pending.discard(row)
        if drop_events:
            for b in self._round_blocks:
                if b.rows.size:
                    keep = b.rows != row
                    if not keep.all():
                        b.rows = b.rows[keep]
                        b.slots = b.slots[keep]
                        b.rels = b.rels[keep]
                        if b.cells is not None:
                            b.cells = b.cells[keep]
                if b.votes:
                    b.votes = [v for v in b.votes if v[0] != row]
                self._purge_block_reads(b, row)
                self._purge_block_kv(b, row)

    @staticmethod
    def _purge_block_reads(b, row: int) -> None:
        """Drop ``row``'s staged read-stage/read-ack batches from one
        sealed round block (reads are droppable by contract; see
        ``recycle_leader``)."""
        if b.reads is not None and b.reads[0].size:
            keep = b.reads[0] != row
            if not keep.all():
                b.reads = tuple(a[keep] for a in b.reads)
        if b.racks is not None and b.racks[0].size:
            keep = b.racks[0] != row
            if not keep.all():
                b.racks = tuple(a[keep] for a in b.racks)

    def remove_group(self, cluster_id: int) -> None:
        gi = self.groups.pop(cluster_id)
        # any undispatched recycle of this row is now moot — it must not
        # revive the freed row when the block dispatches — and events
        # already sealed into closed blocks must die with the tenant (a
        # future add_group may hand this row to a new group before the
        # block dispatches)
        self._drop_churn_records(gi.row, drop_events=True)
        del self.rows[gi.row]
        self.mirror.arrays["live"][gi.row] = False
        self._dirty.add(gi.row)
        # purge queued events so a future tenant of this row never receives
        # the dead group's acks/votes
        self._purge_row_events(gi.row)
        self._row_cid[gi.row] = -1
        self._free.append(gi.row)

    # ------------------------------------------------------------------
    # rare-path row mutations (host scalar, mask-update tensors)
    # ------------------------------------------------------------------

    def _rel(self, gi: GroupInfo, index: int) -> int:
        rel = index - gi.base
        if rel < 0:
            raise ValueError(f"index {index} below base {gi.base}")
        if rel >= REBASE_THRESHOLD:
            raise ValueError("index needs rebase before ingest")
        return rel

    def set_leader(
        self, cluster_id: int, term: int, term_start: int, last_index: int
    ) -> None:
        """Promote to leader (twin: ``become_leader`` raft.go:1027-1045)."""
        gi = self.groups[cluster_id]
        a = self.mirror.arrays
        row = gi.row
        self._sync_row(row)
        a["node_state"][row] = LEADER
        a["term"][row] = term
        a["term_start"][row] = self._rel(gi, term_start)
        a["last_index"][row] = self._rel(gi, last_index)
        a["election_tick"][row] = 0
        a["heartbeat_tick"][row] = 0
        a["votes"][row, :] = VOTE_NONE
        # reset_remotes: fresh Remote structs — next = last+1 for all,
        # self match = last, activity cleared (raft.go:991-1010)
        a["match"][row, :] = 0
        a["next"][row, :] = self._rel(gi, last_index) + 1
        a["match"][row, a["self_slot"][row]] = self._rel(gi, last_index)
        a["active"][row, :] = False
        if self._quiesce_used:
            self._wake_mirror_row(row)
        self._purge_row_events(row)
        self._dirty.add(row)

    def set_hier(
        self, cluster_id: int, near_ids, sub_quorum: int
    ) -> None:
        """Install a row's hier sub-quorum geometry (ISSUE 18): the
        leader-domain voter mask plus the domain-majority cardinality the
        fused commit reduction runs (kernels._finish_step has_hier
        branch).  ``sub_quorum=0`` disables the rule for the row — the
        coordinator pushes the real geometry at leader promotion and
        zeroes it on demotion.  A disable on a never-enabled engine is a
        no-op (the arrays are provably already clear), so hier-off hosts
        keep the latch down and their compiled program set unchanged."""
        if sub_quorum <= 0 and not self._hier_used:
            return
        gi = self.groups[cluster_id]
        a = self.mirror.arrays
        row = gi.row
        self._sync_row(row)
        a["near"][row, :] = False
        for nid in near_ids:
            slot = gi.slots.get(nid)
            if slot is not None:
                a["near"][row, slot] = True
        a["sub_quorum"][row] = max(int(sub_quorum), 0)
        if sub_quorum > 0:
            self._hier_used = True
        self._dirty.add(row)

    def set_candidate(self, cluster_id: int, term: int) -> None:
        """Start campaigning (twin: ``become_candidate``); the self-vote is
        ingested like any other vote event."""
        gi = self.groups[cluster_id]
        a = self.mirror.arrays
        row = gi.row
        self._sync_row(row)
        a["node_state"][row] = CANDIDATE
        a["term"][row] = term
        a["votes"][row, :] = VOTE_NONE
        a["election_tick"][row] = 0
        if self._quiesce_used:
            self._wake_mirror_row(row)
        self._purge_row_events(row)
        self._dirty.add(row)

    def set_follower(self, cluster_id: int, term: int) -> None:
        gi = self.groups[cluster_id]
        a = self.mirror.arrays
        row = gi.row
        self._sync_row(row)
        a["node_state"][row] = FOLLOWER
        a["term"][row] = term
        a["votes"][row, :] = VOTE_NONE
        a["election_tick"][row] = 0
        if self._quiesce_used:
            self._wake_mirror_row(row)
        self._purge_row_events(row)
        self._dirty.add(row)

    def set_randomized_timeout(self, cluster_id: int, timeout: int) -> None:
        """Host-seeded randomized election timeout (determinism: the PRNG
        stays host-side and seeded, see raft.py design notes)."""
        gi = self.groups[cluster_id]
        self._sync_row(gi.row)
        self.mirror.arrays["rand_timeout"][gi.row] = timeout
        self._dirty.add(gi.row)

    def restore_progress(
        self, cluster_id: int, committed: int, last_index: int
    ) -> None:
        """Snapshot-restore / log-truncation repair of the watermarks."""
        gi = self.groups[cluster_id]
        a = self.mirror.arrays
        row = gi.row
        self._sync_row(row)
        a["committed"][row] = self._rel(gi, committed)
        a["last_index"][row] = self._rel(gi, last_index)
        self._dirty.add(row)

    def rebase(self, cluster_id: int) -> None:
        """Shift a row's base up to its committed watermark so relative
        int32 indexes stay far from overflow (state.py design note)."""
        gi = self.groups[cluster_id]
        a = self.mirror.arrays
        row = gi.row
        self._sync_row(row)
        shift = int(a["committed"][row])
        if shift <= 0:
            return
        gi.base += shift
        self._row_base[row] = gi.base
        for f in ("committed", "last_index", "term_start"):
            a[f][row] = max(0, int(a[f][row]) - shift)
        a["match"][row, :] = np.maximum(a["match"][row, :] - shift, 0)
        a["next"][row, :] = np.maximum(a["next"][row, :] - shift, 1)
        # pending-read watermarks shift with the base; clamping to the new
        # floor only ever REWRITES a release index up (rel 0 = the old
        # committed), which ReadIndex semantics permit
        a["read_index"][row, :] = np.maximum(a["read_index"][row, :] - shift, 0)
        if self._devsm_used:
            # buffered devsm entries shift with the base (they sit above
            # the old committed == the shift, so the result stays >= 1);
            # host slot records whose tenants the shift proves applied
            # free outright
            ents = a["kv_ent_index"][row, :]
            a["kv_ent_index"][row, :] = np.where(ents >= 0, ents - shift, -1)
            kv = self._kv_ent_rel[row]
            self._kv_ent_rel[row] = np.where(
                (kv >= 0) & (kv - shift > 0), kv - shift, -1
            )
            q = self._kv_queue.get(row)
            if q:
                self._kv_queue[row] = deque(
                    (rel - shift, key, val) for rel, key, val in q
                )
        self._dirty.add(row)

    # ------------------------------------------------------------------
    # dense-path event ingest
    # ------------------------------------------------------------------

    def ack(self, cluster_id: int, node_id: int, index: int) -> None:
        """ReplicateResp success / local append (self ack).

        Acks below the rebased floor are legal raft traffic (delayed
        retransmits); they clamp to rel 0, a scatter-max no-op that still
        marks the peer active — same outcome as ``remote.try_update`` on a
        stale index.
        """
        gi = self.groups[cluster_id]
        rel = max(0, index - gi.base)
        if rel >= REBASE_THRESHOLD:
            raise ValueError(f"index {index} needs rebase (base {gi.base})")
        self._acks.append(
            (gi.row, gi.slots[node_id], rel, int(self._row_epoch[gi.row]))
        )

    def ack_block(self, rows, slots, rels) -> None:
        """Vectorized bulk ack ingest (numpy arrays in row/slot space).

        The per-event ``ack()`` path costs a Python call per event; a
        native or vectorized control plane staging thousands of acks per
        round uses this instead — arrays append as one block and are
        concatenated at dispatch.  Caller contract: rows are live group
        rows, slots valid for their rows, ``rels`` already rebased
        (0 <= rel < REBASE_THRESHOLD); the bounds are validated
        vectorized, membership is the caller's responsibility.
        """
        # validate on the ORIGINAL dtype (an int64 >= 2^32 must hit the
        # rebase guard, not wrap into range), then narrow
        rows = np.asarray(rows)
        slots = np.asarray(slots)
        rels = np.asarray(rels)
        if not (rows.shape == slots.shape == rels.shape):
            raise ValueError("ack_block arrays must share a shape")
        if rels.size and rels.max() >= REBASE_THRESHOLD:
            raise ValueError("ack_block rel out of range (rebase needed)")
        if rows.size and (rows.min() < 0 or rows.max() >= self.n_groups):
            raise ValueError("ack_block row out of range")
        if slots.size and (slots.min() < 0 or slots.max() >= self.n_peers):
            raise ValueError("ack_block slot out of range")
        # below-base acks are legal raft traffic (delayed retransmits) and
        # clamp to rel 0, matching ack()'s scalar semantics
        rels = np.maximum(rels, 0)
        self._ack_blocks.append(
            (rows.astype(np.int32), slots.astype(np.int32),
             rels.astype(np.int32), self._epoch_gen)
        )

    def vote(self, cluster_id: int, node_id: int, granted: bool) -> None:
        """First vote per (group, peer) wins (twin: ``handle_vote_resp``).

        The kernel's first-wins guard reads pre-batch state, so within-batch
        duplicates must be deduped here — keep only the first event per cell.
        """
        gi = self.groups[cluster_id]
        cell = (gi.row, gi.slots[node_id])
        ep = int(self._row_epoch[gi.row])
        if self._voted_cells.get(cell) == ep:
            return
        self._voted_cells[cell] = ep
        self._votes.append(
            (cell[0], cell[1], VOTE_GRANT if granted else VOTE_REJECT, ep)
        )

    def heartbeat_resp(self, cluster_id: int, node_id: int) -> None:
        """Heartbeat response marks the peer active; an ack at index 0 is a
        no-op for match (scatter-max) but sets the activity bit."""
        gi = self.groups[cluster_id]
        self._acks.append(
            (gi.row, gi.slots[node_id], 0, int(self._row_epoch[gi.row]))
        )

    def leader_contact(self, cluster_id: int) -> None:
        """A follower heard from its leader: reset the row's election clock
        (twin: ``leader_is_available`` — the kernel resets election_tick on
        any event touching a non-leader row)."""
        gi = self.groups[cluster_id]
        self._acks.append(
            (gi.row, gi.self_slot, 0, int(self._row_epoch[gi.row]))
        )

    def heartbeat_resp_block(self, rows, slots) -> None:
        """``heartbeat_resp`` for many (row, peer slot) pairs, and
        ``leader_contact`` for many follower rows, each with its own slot
        (``GroupInfo.self_slot``): one ack block at rel 0 (``ack_block``'s
        caller contract)."""
        rows = np.asarray(rows, dtype=np.int32)
        self.ack_block(rows, slots, np.zeros(rows.shape, np.int32))

    # ------------------------------------------------------------------
    # device read plane: ReadIndex staging (ISSUE 3 tentpole)
    # ------------------------------------------------------------------

    def _free_read_slot(self, rows: np.ndarray) -> np.ndarray:
        """Vectorized per-row free-slot pick (cursor + S-step scan);
        returns -1 where a row has no reusable slot.  A slot freed by
        predicted confirmation only becomes reusable in a LATER round
        (``_read_freed_round``): the device applies a round's stage
        BEFORE its echoes, so a same-round restage would overwrite the
        confirming batch ahead of its own release."""
        s = self.n_read_slots
        slot = np.full(rows.shape, -1, np.int32)
        cur = self._read_next_slot[rows]
        for k in range(s):
            cand = (cur + k) % s
            ok = (
                (slot < 0)
                & ~self._read_busy[rows, cand]
                & (self._read_freed_round[rows, cand] < self._round_seq)
            )
            slot = np.where(ok, cand, slot)
        return slot

    def _free_read_slot_one(self, row: int) -> int:
        """``_free_read_slot`` for ONE row, on scalars.  Single-op
        staging runs on a coordinator round thread that shares the
        interpreter with every raft worker of the process; an index-array
        read (``arr[rows, cand]``) releases the interpreter lock whatever
        its size, and winning it back costs the round milliseconds per
        staged read.  ``arr[row, slot]`` on ints does not.  Same rule as
        the block form (test_read_confirm holds the two equal)."""
        s = self.n_read_slots
        busy = self._read_busy
        freed = self._read_freed_round
        seq = self._round_seq
        cur = int(self._read_next_slot[row])
        for k in range(s):
            cand = (cur + k) % s
            if not busy[row, cand] and freed[row, cand] < seq:
                return cand
        return -1

    def _predict_read_confirm(self, rows: np.ndarray, rslots: np.ndarray) -> None:
        """Host-side confirmation prediction: the device only ever sees
        echoes THIS host staged, so once a batch's staged echoes reach
        quorum (self counted via the one-hot column, observers masked
        out — the exact ``kernels.read_confirm`` arithmetic on the
        mirror's host-authoritative membership), the batch provably
        confirms in its round and the slot can be freed for restaging
        without a device readback."""
        a = self.mirror.arrays
        echo = self._read_echo_host[rows, rslots]            # (n,P)
        selfc = (
            np.arange(self.n_peers, dtype=np.int32)[None, :]
            == a["self_slot"][rows][:, None]
        )
        cnt = ((echo | selfc) & a["voting"][rows]).sum(axis=1)
        conf = self._read_busy[rows, rslots] & (cnt >= a["quorum"][rows])
        if conf.any():
            self._read_busy[rows[conf], rslots[conf]] = False
            self._read_freed_round[rows[conf], rslots[conf]] = self._round_seq

    def _predict_read_confirm_one(self, row: int, slot: int) -> None:
        """``_predict_read_confirm`` for ONE (row, slot), on scalars (see
        ``_free_read_slot_one``): count the voting peers that echoed or
        are self; at quorum a still-busy slot frees for later rounds."""
        if not self._read_busy[row, slot]:
            return
        a = self.mirror.arrays
        voting = a["voting"]
        echo = self._read_echo_host
        self_slot = int(a["self_slot"][row])
        cnt = 0
        for p in range(self.n_peers):
            if voting[row, p] and (p == self_slot or echo[row, slot, p]):
                cnt += 1
        if cnt >= a["quorum"][row]:
            self._read_busy[row, slot] = False
            self._read_freed_round[row, slot] = self._round_seq

    def _reset_read_rows(self, rows) -> None:
        """Drop the rows' pending-read bookkeeping (transition purge).
        Skipped outright until the read plane has been used: the arrays
        still hold their reset values then, and this runs on EVERY row
        transition — 265k numpy row-writes per rung-5 window, ~20% of
        its whole host budget (profiled), for a plane the ladder's write
        rungs never touch."""
        if not self._read_plane_used:
            return
        self._read_busy[rows] = False
        self._read_freed_round[rows] = -1
        self._read_echo_host[rows] = False

    def stage_read(
        self, cluster_id: int, count: int = 1, index: Optional[int] = None
    ) -> int:
        """Stage a batch of ``count`` ReadIndex requests for the group;
        returns the pending-read SLOT the batch rides (the caller keys
        its ctx bookkeeping on it — the confirmed-read egress names the
        slot back).  Scalar twin: ``ReadIndex.add_request``.

        ``index`` (absolute) pins the captured watermark explicitly (the
        live coordinator passes scalar raft's ``log.committed``); default
        is the engine's host view of the row's committed watermark.  The
        host view may trail an unharvested in-flight block, which is
        still linearizable: commits become client-observable only
        through harvest egress, so the host view is exactly the upper
        bound of what any client can have seen.

        Raises ``RuntimeError`` when all S slots hold unconfirmed
        batches — backpressure; the caller batches further reads into
        the next free slot (the scalar path bounds the same situation
        with request timeouts, ``requests.py``).
        """
        if count < 1:
            raise ValueError("stage_read count must be >= 1")
        gi = self.groups[cluster_id]
        row = gi.row
        slot = self._free_read_slot_one(row)
        if slot < 0:
            raise RuntimeError(
                f"no free pending-read slot for group {cluster_id}"
            )
        if index is not None:
            rel = self._rel(gi, index)
        else:
            self._refresh_committed_cache()
            if row in self._dirty or row in self._churn_pending:
                rel = int(self.mirror.arrays["committed"][row])
            else:
                rel = int(self._committed_cache[row])
        self._read_plane_used = True
        self._read_busy[row, slot] = True
        self._read_next_slot[row] = (slot + 1) % self.n_read_slots
        self._read_echo_host[row, slot, :] = False
        self._read_stages.append(
            (row, slot, rel, count, int(self._row_epoch[row]))
        )
        return slot

    def stage_read_block(self, rows, rels, counts) -> np.ndarray:
        """Vectorized bulk read staging: one batch per row (rows must be
        unique), ``rels`` already rebased.  Returns the assigned slot per
        row.  Caller contract mirrors ``ack_block``: live rows, bounds
        validated vectorized here, membership the caller's business."""
        rows = np.asarray(rows)
        rels = np.asarray(rels)
        counts = np.asarray(counts)
        if not (rows.shape == rels.shape == counts.shape) or rows.ndim != 1:
            raise ValueError("stage_read_block arrays must share a 1-D shape")
        if rows.size == 0:
            return np.zeros((0,), np.int32)
        if rows.min() < 0 or rows.max() >= self.n_groups:
            raise ValueError("stage_read_block row out of range")
        if rels.min() < 0 or rels.max() >= REBASE_THRESHOLD:
            raise ValueError("stage_read_block rel out of range")
        if counts.min() < 1:
            raise ValueError("stage_read_block counts must be >= 1")
        if np.unique(rows).size != rows.size:
            raise ValueError("stage_read_block rows must be unique")
        rows64 = rows.astype(np.int64)
        slot = self._free_read_slot(rows64)
        if (slot < 0).any():
            raise RuntimeError(
                f"no free pending-read slot for {int((slot < 0).sum())} rows"
            )
        self._read_plane_used = True
        self._read_busy[rows64, slot] = True
        self._read_next_slot[rows64] = (slot + 1) % self.n_read_slots
        self._read_echo_host[rows64, slot, :] = False
        self._read_stage_blocks.append(
            (rows.astype(np.int32), slot.astype(np.int32),
             rels.astype(np.int32), counts.astype(np.int32),
             self._epoch_gen)
        )
        return slot

    def read_ack(self, cluster_id: int, node_id: int, slot: int) -> None:
        """Heartbeat-echo confirmation for the group's pending-read slot
        (scalar twin: the ``m.hint != 0`` branch of
        ``handle_leader_heartbeat_resp`` feeding ``ReadIndex.confirm``)."""
        gi = self.groups[cluster_id]
        row = gi.row
        if not (0 <= slot < self.n_read_slots):
            raise ValueError(f"read slot {slot} out of range")
        peer = gi.slots[node_id]
        self._read_plane_used = True
        self._read_echoes.append(
            (row, slot, peer, int(self._row_epoch[row]))
        )
        self._read_echo_host[row, slot, peer] = True
        self._predict_read_confirm_one(row, slot)

    def read_ack_block(self, rows, rslots, peers) -> None:
        """Vectorized bulk echo ingest (row / pending-read-slot / peer-slot
        space); duplicates are harmless (echo sets are idempotent)."""
        rows = np.asarray(rows)
        rslots = np.asarray(rslots)
        peers = np.asarray(peers)
        if not (rows.shape == rslots.shape == peers.shape) or rows.ndim != 1:
            raise ValueError("read_ack_block arrays must share a 1-D shape")
        if rows.size == 0:
            return
        if rows.min() < 0 or rows.max() >= self.n_groups:
            raise ValueError("read_ack_block row out of range")
        if rslots.min() < 0 or rslots.max() >= self.n_read_slots:
            raise ValueError("read_ack_block read slot out of range")
        if peers.min() < 0 or peers.max() >= self.n_peers:
            raise ValueError("read_ack_block peer slot out of range")
        self._read_plane_used = True
        self._read_echo_blocks.append(
            (rows.astype(np.int32), rslots.astype(np.int32),
             peers.astype(np.int32), self._epoch_gen)
        )
        rows64 = rows.astype(np.int64)
        rslots64 = rslots.astype(np.int64)
        self._read_echo_host[rows64, rslots64, peers.astype(np.int64)] = True
        self._predict_read_confirm(rows64, rslots64)

    def cancel_read(self, cluster_id: int, slot: int) -> None:
        """Withdraw a pending-read slot whose reads were released by
        another path (the scalar prefix release frees every ctx queued
        before a confirmed one — their device slots would otherwise leak
        until a transition purge).  The slot frees host-side now and
        device-side at its round: a zero-count stage overwrites the batch
        (``read_count == 0`` means free; ``read_confirm`` gates on it)."""
        gi = self.groups[cluster_id]
        row = gi.row
        if not (0 <= slot < self.n_read_slots):
            raise ValueError(f"read slot {slot} out of range")
        self._read_plane_used = True
        self._read_stages.append((row, slot, 0, 0, int(self._row_epoch[row])))
        self._read_busy[row, slot] = False
        self._read_freed_round[row, slot] = self._round_seq
        self._read_echo_host[row, slot, :] = False

    def read_slots_free(self, cluster_id: int) -> int:
        """Reusable pending-read slots for the group RIGHT NOW (counting
        the next-round availability rule) — backpressure introspection."""
        row = self.groups[cluster_id].row
        free = ~self._read_busy[row] & (
            self._read_freed_round[row] < self._round_seq
        )
        return int(free.sum())

    def _filter_reads(self):
        """The open round's read-plane buffers with stale-epoch events
        (staged before a row transition) filtered out: ``(stages,
        stage_blocks, echoes, echo_blocks)``, the tuples ``(row, slot,
        rel, count)`` / ``(row, slot, peer)`` as they were staged, the
        blocks as tuples of int32 arrays; clears the buffers and advances
        the slot-reuse round seq (one call per round close, after
        ``_filter_acks``: no staged block is left to be older than a
        bump).  Each event keeps the arity it was staged in, as in
        ``_filter_acks``: tuples are filtered as tuples against
        ``_row_epoch[r]`` read as a scalar and no array is made of them;
        a block is whole unless a row's epoch was bumped after it was
        staged, and only then compared, against those rows."""
        self._round_seq += 1
        epoch = self._row_epoch
        stages = echoes = ()
        if self._read_stages:
            stages = [
                e[:4] for e in self._read_stages if e[4] == epoch[e[0]]
            ]
            self._read_stages = []
            self._n_reads_scalar += len(stages)
        if self._read_echoes:
            echoes = [
                e[:3] for e in self._read_echoes if e[3] == epoch[e[0]]
            ]
            self._read_echoes = []
            self._n_echoes_scalar += len(echoes)
        stage_blocks = echo_blocks = ()
        if self._read_stage_blocks:
            stage_blocks, stale = self._live_blocks(self._read_stage_blocks)
            self._n_stale_read_blocks += stale
            self._read_stage_blocks = []
        if self._read_echo_blocks:
            echo_blocks, stale = self._live_blocks(self._read_echo_blocks)
            self._n_stale_read_blocks += stale
            self._read_echo_blocks = []
        self._epoch_bumped.clear()
        return stages, stage_blocks, echoes, echo_blocks

    def _live_blocks(self, blocks) -> tuple:
        """Staged blocks ``(rows, ..., generation)`` less the rows bumped
        after each was staged, and how many of them had to be compared
        row by row: a block of the current generation is whole."""
        out, stale = [], 0
        for *cols, gen in blocks:
            if gen == self._epoch_gen:
                out.append(tuple(cols))
                continue
            stale += 1
            keep = np.isin(
                cols[0], self._epoch_bumped[gen - self._epoch_gen:],
                invert=True,
            )
            if keep.any():
                out.append(tuple(c[keep] for c in cols))
        return out, stale

    def _gather_reads(self):
        """``_filter_reads`` as flat arrays, for a sealed round of the
        fused path: ``(reads, racks)``, each a tuple of int32 arrays
        (tuple-staged events first) or None."""
        stages, stage_blocks, echoes, echo_blocks = self._filter_reads()
        return (
            _concat_columns(stages, stage_blocks, 4),
            _concat_columns(echoes, echo_blocks, 3),
        )

    def _reads_pending(self) -> bool:
        return bool(
            self._read_stages or self._read_stage_blocks
            or self._read_echoes or self._read_echo_blocks
        )

    # ------------------------------------------------------------------
    # device state machine: entry ops + KV reads (devsm, ISSUE 11)
    # ------------------------------------------------------------------

    def stage_kv_op(
        self, cluster_id: int, index: int, key: int, value: int
    ) -> None:
        """Stage one committed-entry ``SET key := value`` op for log
        ``index`` (absolute).  Scalar twin: the apply executor handing the
        entry to the user SM's ``update`` — here the write happens inside
        the fused program the moment the commit watermark passes the
        index, as a ``(G, slots)`` tensor update in HBM."""
        self.stage_kv_ops(cluster_id, [index], [key], [value])

    def stage_kv_ops(self, cluster_id: int, indexes, keys, values) -> bool:
        """Vectorized entry-op staging for one group.  ``indexes`` must be
        strictly increasing (log-append order); ops whose buffer slot
        (``rel % E``) still holds an unapplied tenant queue host-side and
        drain — order preserved — as harvested commit watermarks free
        slots.  A queued op therefore never errors; it just rides a later
        round (the scalar twin's apply queue depth, bounded by E on
        device and unbounded host-side).

        Returns True when EVERYTHING staged immediately (nothing queued
        for the row).  A False is the backpressure signal consumers that
        release reads at the commit watermark must honor: a QUEUED op may
        commit before it applies, so ``kv_value`` momentarily trails the
        watermark — the live plane unbinds and re-arms past the batch
        (``DevKVPlane.handle_ops``) instead of serving that window."""
        gi = self.groups[cluster_id]
        row = gi.row
        indexes = np.asarray(indexes, dtype=np.int64)
        keys = np.asarray(keys, dtype=np.int64)
        values = np.asarray(values, dtype=np.int64)
        if not (indexes.shape == keys.shape == values.shape) or (
            indexes.ndim != 1
        ):
            raise ValueError("stage_kv_ops arrays must share a 1-D shape")
        if indexes.size == 0:
            return True  # nothing to stage, nothing queued
        rels = indexes - gi.base
        if rels.min() < 1:
            raise ValueError("stage_kv_ops index at or below the group base")
        if rels.max() >= REBASE_THRESHOLD:
            raise ValueError("stage_kv_ops index needs rebase")
        if indexes.size > 1 and (np.diff(indexes) <= 0).any():
            raise ValueError("stage_kv_ops indexes must be strictly increasing")
        if keys.min() < 0 or keys.max() >= self.n_kv_slots:
            raise ValueError("stage_kv_ops key slot out of range")
        imin, imax = np.iinfo(np.int32).min, np.iinfo(np.int32).max
        if values.min() < imin or values.max() > imax:
            raise ValueError("stage_kv_ops value outside int32")
        self._devsm_used = True
        q = self._kv_queue.setdefault(row, deque())
        for rel, key, val in zip(
            rels.tolist(), keys.tolist(), values.tolist()
        ):
            q.append((rel, key, val))
        self._drain_kv_queue(row)
        return row not in self._kv_queue

    def _drain_kv_queue(self, row: int) -> None:
        """Move queued ops into the open round while their slots are
        free, in log order; stops at the first occupied slot (staging out
        of order would let a later op apply before an earlier same-key
        one)."""
        q = self._kv_queue.get(row)
        if not q:
            self._kv_queue.pop(row, None)
            return
        e = self.n_kv_ents
        ep = int(self._row_epoch[row])
        ent_rel = self._kv_ent_rel[row]
        while q:
            rel, key, val = q[0]
            slot = rel % e
            if ent_rel[slot] != -1:
                break
            ent_rel[slot] = rel
            self._kv_stage.append((row, slot, rel, key, val, ep))
            q.popleft()
        if not q:
            self._kv_queue.pop(row, None)

    def _kv_free_applied(self) -> None:
        """Free entry-buffer slots whose tenants the HARVESTED commit
        watermark has passed (the device freed them the round they
        applied), then drain any host-queued overflow into the open
        round.  Runs at every egress; devsm-free engines skip it via the
        latch."""
        mask = (self._kv_ent_rel >= 0) & (
            self._kv_ent_rel <= self._committed_cache[:, None]
        )
        if mask.any():
            self._kv_ent_rel[mask] = -1
        for row in list(self._kv_queue):
            self._drain_kv_queue(row)

    def stage_kv_read(self, cluster_id: int, key: int) -> int:
        """Stage a device KV read for the group; returns the read SLOT
        the capture will egress under (``StepResult.kv_reads``).  The
        value is captured in the read's own round, AFTER that round's
        apply fold, together with the commit watermark it reflects — the
        caller checks the watermark against its ReadIndex release index
        (on this plane apply == commit, so watermark >= release index
        means the value is linearizable for that release).

        Raises ``RuntimeError`` when all R slots hold un-harvested
        captures — backpressure, the ``stage_read`` precedent."""
        gi = self.groups[cluster_id]
        row = gi.row
        if not (0 <= key < self.n_kv_slots):
            raise ValueError(f"kv key slot {key} out of range")
        free = np.nonzero(~self._kv_read_busy[row])[0]
        if not free.size:
            raise RuntimeError(
                f"no free devsm read slot for group {cluster_id}"
            )
        slot = int(free[0])
        self._devsm_used = True
        self._kv_read_busy[row, slot] = True
        self._kv_read_stage.append(
            (row, slot, key, int(self._row_epoch[row]))
        )
        return slot

    def kv_reads_free(self, cluster_id: int) -> int:
        """Free devsm read slots for the group right now."""
        row = self.groups[cluster_id].row
        return int((~self._kv_read_busy[row]).sum())

    def kv_values(self, cluster_id: int) -> np.ndarray:
        """The group's device KV row (introspection / snapshot save):
        pending mirror edits win over the device, like every rare-path
        read."""
        gi = self.groups[cluster_id]
        return np.array(self._read("kv_value", gi.row), dtype=np.int64)

    def kv_restore(self, cluster_id: int, values) -> None:
        """Install a group's KV image (snapshot recover / the devsm
        plane's leadership rebind): mirror row write + dirty upload, with
        the pending-entry buffer cleared — the image IS the applied
        state, nothing buffered belongs with it."""
        gi = self.groups[cluster_id]
        row = gi.row
        values = np.asarray(values, dtype=np.int64)
        if values.shape != (self.n_kv_slots,):
            raise ValueError(
                f"kv_restore expects shape ({self.n_kv_slots},), "
                f"got {values.shape}"
            )
        self._devsm_used = True
        self._sync_row(row)
        a = self.mirror.arrays
        a["kv_value"][row, :] = values.astype(np.int32)
        self.mirror.clear_kv_ents(row)
        self._reset_kv_rows([row])
        self._dirty.add(row)

    def _reset_kv_rows(self, rows) -> None:
        """Drop the rows' devsm host bookkeeping (transition purge twin
        of ``_reset_read_rows``): queued ops die, staged slots free, read
        captures are abandoned.  Device-side entry buffers are cleared by
        the caller's mirror write (``clear_kv_ents``) or the in-program
        recycle reset."""
        if not self._devsm_used:
            return
        self._kv_ent_rel[rows] = -1
        self._kv_read_busy[rows] = False
        for r in np.atleast_1d(np.asarray(rows, dtype=np.int64)):
            self._kv_queue.pop(int(r), None)

    def _gather_kv(self):
        """Open-round devsm buffers as flat arrays with stale-epoch
        events filtered; clears the buffers.  Returns ``(kvents,
        kvreads)`` — tuples of int32 arrays or None.  Re-attempts the
        overflow drain first so ops unblocked by the latest harvest ride
        this round."""
        if self._kv_queue:
            for row in list(self._kv_queue):
                self._drain_kv_queue(row)
        kvents = kvreads = None
        if self._kv_stage:
            cols = np.array(self._kv_stage, dtype=np.int64)
            rows = cols[:, 0].astype(np.int32)
            keep = cols[:, 5].astype(np.int32) == self._row_epoch[rows]
            if keep.any():
                kvents = tuple(
                    cols[keep, i].astype(np.int32) for i in range(5)
                )
            self._kv_stage = []
        if self._kv_read_stage:
            cols = np.array(self._kv_read_stage, dtype=np.int64)
            rows = cols[:, 0].astype(np.int32)
            keep = cols[:, 3].astype(np.int32) == self._row_epoch[rows]
            if keep.any():
                kvreads = tuple(
                    cols[keep, i].astype(np.int32) for i in range(3)
                )
            self._kv_read_stage = []
        return kvents, kvreads

    def _kv_pending(self) -> bool:
        return bool(
            self._kv_stage or self._kv_read_stage or self._kv_queue
        )

    def _kv_ents_buffered(self) -> bool:
        """True while any entry-buffer slot holds an op the harvested
        watermark has not passed — the condition under which every
        dispatch must carry the apply fold (see ``_step_locked``)."""
        return self._devsm_used and bool((self._kv_ent_rel >= 0).any())

    @staticmethod
    def _purge_block_kv(b, row: int) -> None:
        """Drop ``row``'s staged devsm ops/reads from one sealed round
        block (recycle path: an old-tenant op applying before the
        in-program reset is wasted work, and a read capture there would
        egress misattributed to the new tenant — the ``_purge_block_reads``
        rationale exactly)."""
        if b.kvents is not None and b.kvents[0].size:
            keep = b.kvents[0] != row
            if not keep.all():
                b.kvents = tuple(a[keep] for a in b.kvents)
        if b.kvreads is not None and b.kvreads[0].size:
            keep = b.kvreads[0] != row
            if not keep.all():
                b.kvreads = tuple(a[keep] for a in b.kvreads)

    # ------------------------------------------------------------------
    # multi-round fused staging (ISSUE 1 tentpole)
    # ------------------------------------------------------------------

    def begin_round(self) -> None:
        """Close the current ingest round: everything staged so far forms
        one scanned round of the next fused dispatch; events staged after
        this call land in the NEXT round.  The round's stale-epoch filter
        resolves NOW — a transition staged later (including a
        ``stage_recycle`` in a later round) must not retroactively purge
        events that a per-round host dispatch would already have consumed.
        """
        if self._votes:
            votes = [
                (r, s, v)
                for r, s, v, ep in self._votes
                if ep == self._row_epoch[r]
            ]
            self._votes = []
            self._voted_cells.clear()
        else:
            votes = []
        rows, slots, rels = self._gather_acks()
        reads, racks = self._gather_reads()
        kvents, kvreads = self._gather_kv()
        self._round_blocks.append(
            _RoundBuf(
                rows, slots, rels, votes, self._churn,
                reads=reads, racks=racks, kvents=kvents, kvreads=kvreads,
            )
        )
        self._churn = []
        self._churn_rows = set()

    def pending_rounds(self) -> int:
        """Closed rounds awaiting the fused dispatch."""
        return len(self._round_blocks)

    def ack_block_rounds(self, rows, slots, rels_rounds) -> None:
        """K CLOSED rounds of bulk acks over ONE (row, slot) geometry —
        the steady-state shape of every ladder section (same cells every
        round, advancing rel indexes).  Validates the geometry once and
        snapshots the epoch filter once for the whole block instead of
        per round: at 64k groups × 3 acks × K=16 the per-round
        ``ack_block`` + ``begin_round`` path spent ~60ms/dispatch on
        validation min/max scans and defensive copies this API skips
        (the round buffers alias the caller's arrays — the caller must
        not mutate them until the block is dispatched).

        ``rels_rounds`` is (K, n): row ``r`` forms scanned round ``r``.
        Events/churn already staged are closed into one preceding round
        first (exactly ``begin_round`` semantics).
        """
        rows = np.asarray(rows)
        slots = np.asarray(slots)
        rels_rounds = np.asarray(rels_rounds)
        if rels_rounds.ndim != 2 or rows.shape != slots.shape or (
            rels_rounds.shape[1:] != rows.shape
        ):
            raise ValueError("ack_block_rounds: shape mismatch")
        if rels_rounds.size and rels_rounds.max() >= REBASE_THRESHOLD:
            raise ValueError("ack_block_rounds rel out of range")
        if rows.size and (rows.min() < 0 or rows.max() >= self.n_groups):
            raise ValueError("ack_block_rounds row out of range")
        if slots.size and (slots.min() < 0 or slots.max() >= self.n_peers):
            raise ValueError("ack_block_rounds slot out of range")
        if (
            self._acks or self._ack_blocks or self._votes or self._churn
            or self._reads_pending() or self._kv_pending()
        ):
            self.begin_round()
        rows32 = rows.astype(np.int32, copy=False)
        slots32 = slots.astype(np.int32, copy=False)
        cells = rows32.astype(np.int64) * self.n_peers + slots32
        # no epoch filter needed: every event is staged NOW under the
        # rows' current epochs — begin_round closing each round here
        # would resolve to the identity filter
        if rels_rounds.size and rels_rounds.min() < 0:
            # below-base retransmits clamp to rel 0 (ack() semantics)
            rels_rounds = np.maximum(rels_rounds, 0)
        for r in range(rels_rounds.shape[0]):
            self._round_blocks.append(
                _RoundBuf(
                    rows32, slots32,
                    rels_rounds[r].astype(np.int32, copy=False),
                    [], [], cells=cells,
                )
            )

    def stage_recycle(
        self,
        old_cluster_id: int,
        new_cluster_id: int,
        term: int,
        term_start: int,
        last_index: int,
        rand_timeout: Optional[int] = None,
    ) -> GroupInfo:
        """Replace a group with a fresh SAME-GEOMETRY leader tenant as a
        masked row update INSIDE the next dispatched program — the
        device-side twin of ``remove_group`` + ``add_group`` +
        ``set_leader`` (kernels._apply_recycle), with none of the
        host-side re-upload those pay (the dominant cost of churn-under-
        load at 100k groups: one dirty-row scatter per recycle).

        The reset applies at the START of the recycle's ingest round —
        before that round's events — exactly where the host path's
        ``_upload_dirty`` lands relative to its dispatch, so acks staged
        for the new tenant in the same round ingest correctly and events
        already staged for the old tenant this round are purged (epoch
        bump), while earlier CLOSED rounds still reach the old tenant.

        Geometry (peer slots, voting/present masks, quorum, self slot,
        timeouts) carries over unchanged; anything else — different
        membership, witnesses, a different randomized timeout — must take
        the host path.  ``rand_timeout`` may be passed to ASSERT the
        carried-over value.  Raises ValueError when the swap isn't a pure
        recycle.
        """
        gi = self.groups.get(old_cluster_id)
        if gi is None:
            raise ValueError(f"group {old_cluster_id} not registered")
        if new_cluster_id in self.groups:
            raise ValueError(f"group {new_cluster_id} already registered")
        row = gi.row
        if row in self._churn_rows:
            raise ValueError(
                f"row {row} already recycled this round (begin_round first)"
            )
        a = self.mirror.arrays
        if rand_timeout is not None and rand_timeout != int(a["rand_timeout"][row]):
            raise ValueError("rand_timeout differs: recycle must keep geometry")
        if term_start < 0 or last_index < 0 or term_start > last_index:
            raise ValueError("term_start/last_index out of range")
        if last_index >= REBASE_THRESHOLD:
            raise ValueError("index needs rebase before recycle")
        # host bookkeeping: the new tenant takes the SAME row at base 0
        del self.groups[old_cluster_id]
        ngi = GroupInfo(
            new_cluster_id, row, gi.slots, base=0, node_ids=gi.node_ids,
            self_slot=gi.self_slot,
        )
        self.groups[new_cluster_id] = ngi
        self.rows[row] = ngi
        self._row_cid[row] = new_cluster_id
        self._row_base[row] = 0
        # old-tenant events staged this round must not reach the new
        # tenant (closed rounds resolved their filter at close time)
        self._purge_row_events(row)
        # old-tenant READS die entirely — including batches sealed into
        # closed pre-recycle rounds.  Acks in those rounds still apply to
        # the old tenant (they run before the in-program reset), but a
        # read CONFIRMED there would egress after the recycle, when the
        # (G,S) accumulators can only attribute it to the row's final
        # tenant — a misdelivered read.  Reads are droppable by contract
        # (the scalar path drops on leader change/timeout and clients
        # retry), so dropping beats misattributing.  Devsm ops/reads of
        # the old tenant die the same way (_purge_block_kv rationale).
        for b in self._round_blocks:
            self._purge_block_reads(b, row)
            self._purge_block_kv(b, row)
        self._reset_kv_rows([row])
        # mirror coherence WITHOUT dirtying the row: the device applies
        # the identical reset in-program (state.HostMirror.recycle_row);
        # until the block dispatches, host reads of this row resolve to
        # the mirror (_read / committed caches), never the stale device
        self.mirror.recycle_row(
            row, term, term_start, last_index,
            clear_reads=self._read_plane_used,
            clear_kv=self._devsm_used,
            clear_telem=self._telem_used,
        )
        self._committed_cache[row] = 0
        self._synced.discard(row)
        self._churn.append((row, term, term_start, last_index))
        self._churn_rows.add(row)
        self._churn_pending.add(row)
        return ngi

    def step_rounds(
        self,
        do_tick: bool = False,
        pipelined: bool = False,
        pad_rounds_to: int = 0,
        tick_rounds: Optional[int] = None,
    ) -> Optional[MultiRoundResult]:
        """ONE fused dispatch over every staged round (``begin_round``
        boundaries; a non-empty open round is closed implicitly).

        ``pipelined=True`` double-buffers host staging against device
        execution: the call returns the PREVIOUS dispatch's egress (None
        on the first) and leaves this dispatch in flight, so the caller
        ingests/encodes block i+1 while block i executes.  Any host read
        of device state (``committed_view``, ``_read``, a rare-path
        transition, the next dispatch) harvests the in-flight block
        first, so the pipelining is invisible to correctness.  Host
        rare-path mutations (``set_leader`` …) staged between rounds
        apply BEFORE the whole block — mid-block transitions must use
        ``stage_recycle`` or split the block.

        ``pad_rounds_to`` pads the block with event-free, tick-masked-off
        rounds (provable no-ops) up to a fixed K, so a caller with a
        VARYING round count — the coordinator's missed-tick catch-up —
        reuses one compiled program instead of paying a multi-second
        XLA compile per distinct K (kernels.quorum_multiround tick_mask
        note).

        ``tick_rounds`` (with ``do_tick=True``) sets how many of the
        block's rounds tick — default: every REAL (unpadded) round, the
        historical behavior.  It may exceed the real round count up into
        the padding: the live coordinator replays a tick deficit of N
        with ONE staged event round plus N-1 event-free ticking padding
        rounds, fused into a single dispatch (the adaptive-K live path).
        """
        obs = self._obs
        if obs is None:
            with self._dispatch_mu:
                return self._step_rounds_locked(
                    do_tick, pipelined, pad_rounds_to, tick_rounds
                )
        # _dispatch_mu wait (EXACTLY zero on single-device engines, where
        # the "lock" is a nullcontext — don't record timer noise there):
        # attributed to the NEXT dispatch's span; a wait past the stall
        # threshold auto-dumps via the span's stall check.  ACCUMULATED,
        # not assigned — step()'s reroute into step_rounds() re-enters
        # here with the reentrant lock already held, and its ~0 wait must
        # not erase the contended outer acquire.
        timed = self._n_devices > 1
        t0 = time.perf_counter() if timed else 0.0
        with self._dispatch_mu:
            if timed:
                self._obs_mu_wait += (time.perf_counter() - t0) * 1e3
            with obs.step_scope():
                return self._step_rounds_locked(
                    do_tick, pipelined, pad_rounds_to, tick_rounds
                )

    def _step_rounds_locked(
        self, do_tick: bool, pipelined: bool, pad_rounds_to: int,
        tick_rounds: Optional[int] = None,
    ) -> Optional[MultiRoundResult]:
        if (
            self._acks or self._ack_blocks or self._votes or self._churn
            or self._reads_pending() or self._kv_pending()
        ):
            self.begin_round()
        if not self._round_blocks:
            # nothing staged: drain whatever is still in flight
            return self._harvest_inflight()
        blocks, self._round_blocks = self._round_blocks, []
        n_real = len(blocks)
        z = np.zeros((0,), np.int32)
        while len(blocks) < pad_rounds_to:
            blocks.append(_RoundBuf(z, z, z, [], []))
        if tick_rounds is None:
            tick_rounds = n_real
        tick_rounds = min(tick_rounds, len(blocks))
        tick_mask = np.zeros((len(blocks),), bool)
        tick_mask[:tick_rounds] = True
        prev = self._harvest_inflight()
        obs = self._obs
        if obs is not None:
            obs.begin_step()
        self._upload_dirty()
        self._refresh_committed_cache()
        out, planes = self._dispatch_multiround(
            blocks, do_tick, tick_mask,
            k_rounds=max(n_real, tick_rounds if do_tick else 0),
        )
        self._synced.clear()
        # every staged recycle is now inside the dispatched program
        self._churn_pending.clear()
        self._inflight = (
            out.egress,
            out.telem,
            planes,
            # snapshot, not alias: stage_recycle zeroes cache rows in
            # place while this dispatch is in flight, which must not
            # corrupt ITS commit-delta baseline
            self._committed_cache.copy(),
            self._row_cid.copy(),
            self._row_base.copy(),
            len(blocks),
        )
        if pipelined:
            return prev
        return self.harvest()

    def harvest(self) -> Optional[MultiRoundResult]:
        """Egress of the in-flight pipelined dispatch (None when idle)."""
        return self._harvest_inflight()

    def _harvest_inflight(self) -> Optional[MultiRoundResult]:
        if self._inflight is None:
            return None
        with self._dispatch_mu:
            return self._harvest_inflight_locked()

    def _harvest_inflight_locked(self) -> Optional[MultiRoundResult]:
        if self._inflight is None:
            return None
        (
            egress, telem, planes, prev_committed, row_cid, row_base,
            n_rounds,
        ) = self._inflight
        self._inflight = None
        obs = self._obs
        span, self._obs_span = self._obs_span, None
        kv_span, self._obs_kv_span = self._obs_kv_span, None
        t_eg = time.perf_counter() if obs is not None else 0.0
        eg = self._fetch_egress(egress)
        with (obs.phase("decode") if obs is not None else _OFF):
            if telem is not None:
                # dispatch-time row_cid snapshot: a re-registration while
                # the block was in flight must not mislabel a drill-down
                # row.  The device arrays stay resident until
                # telem_snapshot pulls them — the fold must not add a
                # per-dispatch readback
                self._stage_telem(telem, row_cid, rounds=n_rounds)
            res = MultiRoundResult(n_rounds)
            committed, bits, rdc, rdi, kvv, kvi, kva = _pk.split_egress(
                eg, self._dims, *planes
            )
            if rdc is not None:
                self._translate_reads(res, rdc, rdi, row_cid, row_base)
                self._read_recheck = set()  # the scan saw every slot
            res.committed_rel = committed
            self._committed_cache = np.array(committed, dtype=np.int32)
            if self._churn_pending:
                # recycles staged while this block was in flight: their
                # rows' host watermark is the mirror's (new tenant) until
                # THEIR block lands — the harvested vector still shows
                # the old one
                rows = np.fromiter(self._churn_pending, dtype=np.int64)
                self._committed_cache[rows] = (
                    self.mirror.arrays["committed"][rows]
                )
            if kvi is not None:
                self._translate_kv(res, kvv, kvi, kva, row_cid, row_base)
                if self.kv_egress_hook is not None:
                    self.kv_egress_hook(res)
            if self._devsm_used:
                self._kv_free_applied()
            res.commit_rows = self._translate_egress(
                res, committed, prev_committed, row_cid, row_base, bits,
                self._flag_bits(),
            )
        if obs is not None and span is not None:
            obs.egress(
                span,
                arrays_retired=1,
                egress_ms=(time.perf_counter() - t_eg) * 1e3,
                egress_rows=int(res.commit_rows.size),
                reads_released=(
                    int(res.read_counts.sum())
                    if res.read_counts is not None else 0
                ),
            )
        if obs is not None and kv_span is not None:
            obs.devsm_egress(
                kv_span,
                applied=res.kv_applied_ops,
                reads_served=(
                    int(len(res.kv_cids)) if res.kv_cids is not None else 0
                ),
            )
        return res

    def _fetch_egress(self, egress) -> np.ndarray:
        """The blocking ``device_get`` of a dispatch's ONE egress block.
        The device array joins ``_retired`` (it is counted as retired on
        the span's egress half): its death, like the replaced state
        blocks', is not on the way from the fetch to the fan-out."""
        obs = self._obs
        with (obs.phase("egress_wait") if obs is not None else _OFF):
            eg = jax.device_get(egress)
        self._retired += (egress,)
        return eg

    def _flag_bits(self) -> tuple:
        """The egress bits this engine's programs can raise: the sleep
        bit, the last, only with the quiesce latch up (each bit decoded is
        a numpy pass on the round thread, whatever it finds)."""
        return _pk.FLAG_BITS if self._quiesce_used else _pk.FLAG_BITS[:-1]

    @staticmethod
    def _translate_egress(
        res, committed, prev_committed, row_cid, row_base, bits,
        flags=_pk.FLAG_BITS,
    ) -> np.ndarray:
        """Vectorized row→cluster egress translation, shared by step()'s
        single-round path and the fused harvest: watermark deltas become
        (cid, abs) arrays (dead rows — cid -1 — dropped; the commit dict
        materializes lazily), the flag bit field (``flags``, low bit
        first: ``_flag_bits``) becomes cid lists.  Returns the
        changed-row index vector."""
        changed = np.nonzero(committed != prev_committed)[0]
        if changed.size:
            cids = row_cid[changed]
            live = cids >= 0
            res._commit_cids = cids[live]
            res._commit_abs = (row_base[changed] + committed[changed])[live]
        if bits.any():
            for i, name in enumerate(flags):
                idx = np.nonzero(bits & (1 << i))[0]
                if idx.size:
                    cids = row_cid[idx]
                    getattr(res, name).extend(cids[cids >= 0].tolist())
        return changed

    def _translate_kv(self, res, kvv, kvi, kva, row_cid, row_base) -> None:
        """Vectorized devsm egress translation: the device's (G,R)
        capture accumulators become flat (cid, slot, value, abs index)
        vectors (dead rows dropped; the tuple list materializes lazily
        via ``StepResult.kv_reads``), captured read slots free for
        restaging, and the block's applied-op total lands on the
        result."""
        kvi = np.asarray(kvi)
        res.kv_applied_ops = int(np.asarray(kva).sum())
        rows, slots = np.nonzero(kvi >= 0)
        if not rows.size:
            return
        self._kv_read_busy[rows, slots] = False
        cids = row_cid[rows]
        live = cids >= 0
        rows, slots = rows[live], slots[live]
        res.kv_cids = cids[live]
        res.kv_slots = slots.astype(np.int64)
        res.kv_vals = np.asarray(kvv)[rows, slots].astype(np.int64)
        res.kv_index_abs = row_base[rows] + kvi[rows, slots]

    @staticmethod
    def _translate_reads(res, done_cnt, done_idx, row_cid, row_base) -> None:
        """Vectorized confirmed-read egress translation: the device's
        (G,S) count/index accumulators become flat (cid, slot, abs index,
        count) vectors (dead rows dropped; the tuple list materializes
        lazily via ``StepResult.reads``)."""
        done_cnt = np.asarray(done_cnt)
        rows, slots = np.nonzero(done_cnt)
        if not rows.size:
            return
        cids = row_cid[rows]
        live = cids >= 0
        rows, slots = rows[live], slots[live]
        res.read_cids = cids[live]
        res.read_slots = slots.astype(np.int64)
        res.read_index_abs = (
            row_base[rows] + np.asarray(done_idx)[rows, slots]
        )
        res.read_counts = done_cnt[rows, slots].astype(np.int64)

    def _decode_reads(self, res, done_cnt, done_idx, reads) -> int:
        """Confirmed-read egress of ONE single-round dispatch, off the
        fetched block: visits only the (row, slot) pairs that can have
        confirmed in it, on scalars, and returns how many it visited (0:
        it scanned the plane).  A slot's confirmation changes only in a
        dispatch that staged or echoed it (a quorum of one confirms at
        its stage), or after its row's mirror was uploaded
        (``_read_recheck``); everything already confirmed was released by
        the dispatch that confirmed it.  The device stays the authority:
        what is released is what the block says at those pairs, in
        ``_translate_reads``' order and types.  A dispatch that carried
        block-staged events keeps the vectorized scan."""
        stages, stage_blocks, echoes, echo_blocks = reads
        recheck, self._read_recheck = self._read_recheck, set()
        if stage_blocks or echo_blocks or recheck is None:
            self._translate_reads(
                res, done_cnt, done_idx, self._row_cid, self._row_base
            )
            return 0
        for r, sl, _rel, _c in stages:
            recheck.add((r, sl))
        for r, sl, _pe in echoes:
            recheck.add((r, sl))
        row_cid, row_base = self._row_cid, self._row_base
        out = []
        for r, sl in sorted(recheck):
            c = done_cnt[r, sl]
            if c and row_cid[r] >= 0:
                out.append((
                    int(row_cid[r]), sl,
                    int(row_base[r]) + int(done_idx[r, sl]), int(c),
                ))
        if out:
            (res.read_cids, res.read_slots, res.read_index_abs,
             res.read_counts) = np.array(out, dtype=np.int64).T
            res._reads_list = out
        return len(recheck)

    def _dispatch_multiround(
        self, blocks: List[_RoundBuf], do_tick: bool, tick_mask: np.ndarray,
        k_rounds: Optional[int] = None,
    ):
        """Stage K closed rounds into ONE ingress block — (K,G,P) ack
        maxima, (K,C) churn records, the planes in use — and run the
        fused program: one scan, one upload, one egress for the whole
        block.  Returns ``(out, (has_reads, has_kv))``."""
        obs = self._obs
        with (obs.phase("stage") if obs is not None else _OFF):
            k = len(blocks)
            g, p = self.n_groups, self.n_peers
            has_votes = any(b.votes for b in blocks)
            has_churn = any(b.churn for b in blocks)
            cap = 0
            if has_churn:
                # pad the per-round churn width to a power of two so the
                # jit cache stays bounded at ~log2(G) entries per K (the
                # same shape-bucketing rationale as _pad_rows)
                cmax = max(len(b.churn) for b in blocks)
                cap = max(1 << max(0, cmax - 1).bit_length(), 1)
            has_reads = any(
                b.reads is not None or b.racks is not None for b in blocks
            )
            # the fold runs while ops sit buffered
            has_kv = any(
                b.kvents is not None or b.kvreads is not None
                for b in blocks
            ) or self._kv_ents_buffered()
            ing = self._ingress_for(
                "fused", k=k, c=cap, has_votes=has_votes,
                has_churn=has_churn, do_tick=do_tick, has_reads=has_reads,
                has_kv=has_kv,
            )
            v = ing.views
            # -1 = untouched sentinel: one plane instead of (max,
            # touched) — halves both the host staging stores and the
            # upload bytes
            flat = v["ack"].reshape(-1)
            stride = g * p
            for r, b in enumerate(blocks):
                if b.rows.size:
                    if b.cells is not None:  # shared-geometry fast path
                        cell = r * stride + b.cells
                    else:
                        cell = (
                            r * g + b.rows.astype(np.int64)
                        ) * p + b.slots
                    np.maximum.at(flat, cell, b.rels)
                if b.votes:
                    cols = np.array(b.votes, dtype=np.int64).T
                    v["votes"][r, cols[0], cols[1]] = cols[2]
                if b.churn:
                    cols = np.array(b.churn, dtype=np.int64).T
                    n = cols.shape[1]
                    v["churn_row"][r, :n] = cols[0]
                    v["churn_term"][r, :n] = cols[1]
                    v["churn_start"][r, :n] = cols[2]
                    v["churn_last"][r, :n] = cols[3]
                if b.reads is not None and b.reads[0].size:
                    rr, sl, val, c = b.reads
                    v["read_idx"][r, rr, sl] = val
                    v["read_cnt"][r, rr, sl] = c
                if b.racks is not None and b.racks[0].size:
                    rr, sl, pe = b.racks
                    np.bitwise_or.at(
                        v["read_echo"][r], (rr, sl), np.left_shift(1, pe)
                    )
                if b.kvents is not None and b.kvents[0].size:
                    rr, sl, rel, key, val = b.kvents
                    v["kv_idx"][r, rr, sl] = rel
                    v["kv_key"][r, rr, sl] = key
                    v["kv_val"][r, rr, sl] = val
                if b.kvreads is not None and b.kvreads[0].size:
                    rr, sl, key = b.kvreads
                    v["kv_rkey"][r, rr, sl] = key
            if do_tick:
                v["tick"][:] = tick_mask
        out = self._launch(
            _pk.quorum_multiround,
            ing,
            k=k,
            c=cap,
            do_tick=do_tick,
            track_contact=self.device_ticks or do_tick,
            has_votes=has_votes,
            has_churn=has_churn,
            has_reads=has_reads,
            # a never-used read plane is all-zero: compile its recycle
            # purges out (measured ~40% of rung-5 churn throughput).
            # Normalized to False when the block carries no churn —
            # the flag is only consumed inside _apply_recycle, but as
            # a static it keys the jit cache, and letting it flip with
            # _read_plane_used would recompile the live coordinator's
            # fused program the moment the first read stages (exactly
            # the first-use stall the warmup pass exists to kill)
            purge_reads=self._read_plane_used and has_churn,
            has_kv=has_kv,
            # the devsm twin of purge_reads, same normalization
            # rationale
            purge_kv=self._devsm_used and has_churn,
            # the telem twin of purge_reads, same normalization
            # rationale
            purge_telem=self._telem_used and has_churn,
        )
        if obs is not None:
            n_acks = int(sum(b.rows.size for b in blocks))
            n_votes = sum(len(b.votes) for b in blocks)
            n_rec = sum(len(b.churn) for b in blocks)
            n_reads = int(sum(
                b.reads[0].size for b in blocks if b.reads is not None
            ))
            n_echo = int(sum(
                b.racks[0].size for b in blocks if b.racks is not None
            ))
            # EXACTLY what the program received: the one ingress block
            # (the one accounting point shared with the devprof capacity
            # model: upload_nbytes docstring)
            up = upload_nbytes(ing.buf)
            if has_kv:
                n_kvops = int(sum(
                    b.kvents[0].size for b in blocks if b.kvents is not None
                ))
                n_kvreads = int(sum(
                    b.kvreads[0].size for b in blocks
                    if b.kvreads is not None
                ))
                self._obs_kv_span = obs.apply_kernel(
                    ops=n_kvops,
                    reads=n_kvreads,
                    rounds=k,
                    slot_occupancy=int((self._kv_ent_rel >= 0).sum()),
                )
            mu_wait, self._obs_mu_wait = self._obs_mu_wait, 0.0
            self._obs_span = obs.dispatch(
                "fused",
                rounds=k,
                k_rounds=k_rounds if k_rounds is not None else k,
                acks=n_acks,
                votes=n_votes,
                recycles=n_rec,
                reads=n_reads,
                echoes=n_echo,
                upload_bytes=int(up),
                dispatch_ms=(time.perf_counter() - obs.t0) * 1e3,
                gate=self._obs_gate(
                    do_tick, n_acks, n_votes, n_rec, n_reads, n_echo
                ),
                mu_wait_ms=mu_wait,
                pending_rounds=len(self._round_blocks),
                read_slots_in_use=(
                    int(self._read_busy.sum())
                    if self._read_plane_used else None
                ),
                **self._take_array_counts(),
            )
            self.last_span_seq = self._obs_span["seq"]
        dp = self._devprof
        if dp is not None:
            # device capacity & profiling plane (ISSUE 15): padding-waste
            # accounting (padded program K vs live rounds — the padding
            # rounds are provable no-ops, i.e. measurable wasted device
            # work) plus the sampled block_until_ready device-time
            # estimate; the sampled delta is stamped onto this
            # dispatch's flight-recorder span as `device_ms`
            dp.note_dispatch(
                "fused", out.egress, rounds=k,
                live_rounds=(
                    min(k, k_rounds) if k_rounds is not None else k
                ),
                # only a span THIS dispatch recorded: after disable_obs
                # the stale _obs_span still references an old ring
                # record, and stamping device_ms there would corrupt it
                span=self._obs_span if obs is not None else None,
            )
        return out, (has_reads, has_kv)

    def _refresh_committed_cache(self) -> None:
        """Re-read the host committed twin from the device when it was
        invalidated (external ``dev`` assignment).  Rows with a staged
        in-program recycle keep their MIRROR watermark (the device still
        holds the old tenant until the block dispatches)."""
        if not self._cache_stale:
            return
        obs = self._obs
        with (obs.phase("row_sync") if obs is not None else _OFF):
            self._committed_cache = np.array(
                np.asarray(self.dev.committed), dtype=np.int32
            )
            if self._churn_pending:
                rows = np.fromiter(self._churn_pending, dtype=np.int64)
                self._committed_cache[rows] = (
                    self.mirror.arrays["committed"][rows]
                )
            self._cache_stale = False

    def committed_view(self) -> np.ndarray:
        """Absolute committed watermark per ROW as one (G,) int64 vector —
        the fully vectorized egress view (dead rows included; mask with
        ``row_cids() >= 0``).  Fresh after any step/harvest; reads the
        host twin, never the device."""
        self._harvest_inflight()
        self._refresh_committed_cache()
        view = self._row_base + self._committed_cache.astype(np.int64)
        if self._dirty:
            rows = np.fromiter(self._dirty, dtype=np.int64)
            view[rows] = (
                self._row_base[rows]
                + self.mirror.arrays["committed"][rows].astype(np.int64)
            )
        return view

    def row_cids(self) -> np.ndarray:
        """(G,) int64 cluster id per row (-1 = dead); pairs with
        ``committed_view`` for vectorized watermark asserts."""
        return self._row_cid.copy()

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------

    def _sync_row(self, row: int) -> None:
        """Pull one device row into the mirror before mutating it (the
        dense path may have advanced it since the last upload).

        A row with an undispatched in-program recycle is special: its
        MIRROR already holds the post-recycle state (recycle_row) and the
        device row is stale pre-recycle data — pulling it would resurrect
        the old tenant under the new cid.  The caller is about to mutate
        the row host-side, which supersedes the staged device reset, so
        the recycle collapses to pre-block ordering: drop the in-program
        record and dirty the (post-recycle) mirror for upload instead."""
        self._harvest_inflight()
        if row in self._churn_pending:
            self._drop_churn_records(row, drop_events=True)
            self._dirty.add(row)
            return
        if row in self._dirty or row in self._synced:
            return
        self._pull_rows(self._pad_rows(np.array([row], np.int32)))
        self._synced.add(row)

    def _gather_sync_rows(self, blocks: StateBlocks, keys, idx) -> dict:
        """Rows ``idx`` of the ``keys`` fields of ``blocks`` as host
        arrays: one gather program, one transfer."""
        with self._dispatch_mu:  # the gather is a multi-device program
            return jax.device_get(_pk.gather_rows(
                blocks, idx, dims=self._dims[:3], keys=tuple(keys)
            ))

    def _scatter_sync_rows(self, blocks: StateBlocks, idx, vals: dict):
        """``blocks`` (donated) with rows ``idx`` of the ``vals`` fields
        overwritten (one scatter program)."""
        return _pk.scatter_rows(blocks, idx, vals, dims=self._dims[:3])

    def _pull_rows(self, idx: np.ndarray) -> None:
        """Device rows ``idx`` -> mirror, every sync field."""
        obs = self._obs
        with (obs.phase("row_sync") if obs is not None else _OFF):
            got = self._gather_sync_rows(self._blk, self._sync_keys(), idx)
            for k, v in got.items():
                self.mirror.arrays[k][idx] = v

    _READ_KEYS = ("read_index", "read_count", "read_acks")
    _KV_KEYS = ("kv_value", "kv_ent_index", "kv_ent_key", "kv_ent_val")
    _HIER_KEYS = ("near", "sub_quorum")
    _TELEM_KEYS = ("telem_prev_committed",)
    _QUIESCE_KEYS = QUIESCE_FIELDS

    def _sync_keys(self, read_plane: Optional[bool] = None):
        """Mirror fields the rare-path row syncs move between host and
        device.  The read-plane arrays join only once the plane has been
        used (see the ``_read_plane_used`` latch in ``__init__``); before
        that both sides are all-zero by construction and the extra eager
        gather/scatter programs must not be dispatched at all.  The devsm,
        hier and telem arrays follow the same rule on their own latches."""
        skip = ()
        if read_plane is None:
            read_plane = self._read_plane_used
        if not read_plane:
            skip += self._READ_KEYS
        if not self._devsm_used:
            skip += self._KV_KEYS
        if not self._hier_used:
            skip += self._HIER_KEYS
        if not self._telem_used:
            skip += self._TELEM_KEYS
        if not self._quiesce_used:
            skip += self._QUIESCE_KEYS
        if not skip:
            return list(self.mirror.arrays)
        return [k for k in self.mirror.arrays if k not in skip]

    def _pad_rows(self, idx: np.ndarray) -> np.ndarray:
        """Pad a row-index vector to the next bucket length by repeating
        its first element.  Gather/scatter with a fresh index SHAPE
        compiles a new program (measured: an election burst's varying
        transition counts cost ~620ms/round in backend_compile_and_load);
        the few coarse buckets are all compiled by the warm-up pass
        (``_warm_row_syncs``), so the round thread never meets a new one.
        Duplicate indexes are harmless: gathers repeat a value, scatters
        rewrite the same value."""
        cap = next(b for b in self._row_buckets if b >= idx.size)
        if cap == idx.size:
            return idx
        return np.concatenate(
            [idx, np.full(cap - idx.size, idx[0], idx.dtype)]
        )

    def _warm_row_syncs(self, scratch: StateBlocks, include_reads: bool):
        """Compile the row gather/scatter programs for every bucket, for
        the current sync field set and (``include_reads``) the one the
        first staged read switches to — against the scratch state, which
        the scatter rewrites with its own values."""
        key_sets = [self._sync_keys()]
        if include_reads and not self._read_plane_used:
            key_sets.append(self._sync_keys(read_plane=True))
        for keys in key_sets:
            for b in self._row_buckets:
                if self._warmup_cancel.is_set():
                    return scratch
                idx = np.zeros(b, np.int32)
                vals = self._gather_sync_rows(scratch, keys, idx)
                with self._dispatch_mu:
                    scratch = self._scatter_sync_rows(scratch, idx, vals)
        return scratch

    def sync_rows(self, rows) -> None:
        """Bulk-pull many device rows into the mirror: one gather per
        field for the whole set instead of ~20 single-row device reads
        per transition (the per-row form measured ~0.5ms each on the CPU
        backend — an election burst syncing 1,024 rows one at a time was
        the bulk of a 680ms round)."""
        self._harvest_inflight()
        if self._churn_pending:
            # recycled-but-undispatched rows keep their mirror state and
            # collapse the recycle to pre-block ordering (see _sync_row)
            for r in rows:
                if r in self._churn_pending:
                    self._drop_churn_records(r, drop_events=True)
                    self._dirty.add(r)
        todo = [
            r for r in rows if r not in self._dirty and r not in self._synced
        ]
        if not todo:
            return
        # unique: callers may name a row twice, and the largest bucket is
        # exactly the group count
        self._pull_rows(self._pad_rows(np.unique(np.asarray(todo, np.int32))))
        self._synced.update(todo)

    def _upload_dirty(self) -> None:
        if not self._dirty:
            return
        self._harvest_inflight()
        obs = self._obs
        with (obs.phase("row_sync") if obs is not None else _OFF):
            rows = self._pad_rows(np.fromiter(self._dirty, dtype=np.int32))
            vals = {k: self.mirror.arrays[k][rows] for k in self._sync_keys()}
            with self._dispatch_mu:
                self._blk = self._scatter_sync_rows(self._blk, rows, vals)
            pending = vals.get("read_count")
            if (
                pending is not None and self._read_recheck is not None
                and pending.any()
            ):
                # an upload rewrites what ``read_confirm`` reads of the
                # row: its pending slots may confirm in the next
                # read-plane dispatch with no event of their own
                at, slots = np.nonzero(pending)
                self._read_recheck.update(
                    zip(rows[at].tolist(), slots.tolist())
                )
            # keep the host committed twin coherent with the rows just
            # written
            self._committed_cache[rows] = (
                self.mirror.arrays["committed"][rows]
            )
            self._dirty.clear()

    def step(self, do_tick: bool = True) -> StepResult:
        """Run one fused device dispatch over all pending events.

        Oversized event backlogs run extra (tickless) dispatches first so
        the jit program never recompiles for a new batch size.

        When rounds were staged (``begin_round`` / ``stage_recycle``),
        the whole backlog — closed rounds plus the open buffers as the
        final round — runs as ONE fused multi-round dispatch instead
        (``step_rounds``; the result satisfies the StepResult interface).
        """
        obs = self._obs
        if obs is None:
            with self._dispatch_mu:
                return self._step_locked(do_tick)
        timed = self._n_devices > 1
        t0 = time.perf_counter() if timed else 0.0
        with self._dispatch_mu:
            if timed:
                self._obs_mu_wait += (time.perf_counter() - t0) * 1e3
            with obs.step_scope():
                return self._step_locked(do_tick)

    def _step_locked(self, do_tick: bool) -> StepResult:
        if self._round_blocks or self._churn:
            return self.step_rounds(do_tick=do_tick)
        self._harvest_inflight()
        obs = self._obs
        if obs is not None:
            obs.begin_step()
        with (obs.phase("stage") if obs is not None else _OFF):
            # stale-epoch votes (staged before a row transition) drop
            # here; surviving entries shed the epoch column for the
            # dispatch path
            if self._votes:
                self._votes = [
                    (r, s, v)
                    for r, s, v, ep in self._votes
                    if ep == self._row_epoch[r]
                ]
        self._upload_dirty()
        # host twin, not a device readback (a full extra round trip per
        # step on a network-attached chip); _upload_dirty and the egress
        # below keep it coherent.  An external `eng.dev = ...` assignment
        # marks it stale and forces a one-time device re-read here.
        self._refresh_committed_cache()
        prev_committed = self._committed_cache

        n_dispatches = 1
        with (obs.phase("stage") if obs is not None else _OFF):
            acks = self._filter_acks()
            n_acks = len(acks[0]) + sum(b[0].size for b in acks[1])
            reads = self._filter_reads()
            kvents, kvreads = self._gather_kv()
            n_votes = len(self._votes) if obs is not None else 0
            has_reads = any(reads)
            # the apply fold must ALSO run while any entry sits buffered
            # on device: its commit may land in this (otherwise kv-free)
            # dispatch, and a fold-free program would leave it unapplied
            # — stale for kv_values and unsafe for the host slot-free
            # rule.  Empties back to event-driven the moment the buffers
            # drain.
            has_kv = (
                kvents is not None or kvreads is not None
                or self._kv_ents_buffered()
            )
        # dense mode collapses ANY number of acks/votes into (G,P)
        # matrices — no cap, no chunk loop (votes are already first-wins
        # deduped per cell, so a dense matrix holds a whole round).
        # The read plane — and the devsm plane — exist only on the dense
        # kernel, so pending reads/kv ops force dense regardless of
        # occupancy or policy.
        if has_reads or has_kv or self.dense_ingest is True or (
            self.dense_ingest == "auto"
            and (
                n_acks >= self._dense_threshold
                or n_acks > self.event_cap
                or len(self._votes) > self.event_cap
            )
        ):
            out = self._dispatch_dense(
                acks, self._votes, do_tick, reads if has_reads else None,
                kvents, kvreads, has_kv=has_kv,
            )
            planes = (has_reads, has_kv)
        else:
            planes = (False, False)  # the sparse program carries neither
            ack_g, ack_p, ack_v = _ack_columns(*acks)
            pos = 0
            n_chunks = 0
            while (ack_g.size - pos) > self.event_cap or len(self._votes) > self.event_cap:
                take = min(self.event_cap, ack_g.size - pos)
                chunk = self._dispatch(
                    (ack_g[pos : pos + take], ack_p[pos : pos + take],
                     ack_v[pos : pos + take]),
                    self._votes[: self.event_cap],
                    False,
                )
                # the next chunk restages the ingress buffer this one
                # was launched on: not before the program has read it
                jax.block_until_ready(chunk.egress)
                if obs is not None:
                    self._n_retired += 1  # this chunk's egress, unread
                del chunk
                pos += take
                n_chunks += 1
                del self._votes[: self.event_cap]
            out = self._dispatch(
                (ack_g[pos:], ack_p[pos:], ack_v[pos:]), self._votes, do_tick
            )
            n_dispatches += n_chunks
        self._votes.clear()
        self._voted_cells.clear()
        # the dispatch advanced every row on device; bulk-synced mirror
        # rows are stale now
        self._synced.clear()

        if obs is not None:
            stages, stage_blocks, echoes, echo_blocks = reads
            n_reads = len(stages) + sum(b[0].size for b in stage_blocks)
            n_echo = len(echoes) + sum(b[0].size for b in echo_blocks)
            if has_kv:
                self._obs_kv_span = obs.apply_kernel(
                    ops=int(kvents[0].size) if kvents is not None else 0,
                    reads=int(kvreads[0].size) if kvreads is not None else 0,
                    rounds=1,
                    slot_occupancy=int((self._kv_ent_rel >= 0).sum()),
                )
            mu_wait, self._obs_mu_wait = self._obs_mu_wait, 0.0
            upload, self._obs_upload = self._obs_upload, 0
            span = obs.dispatch(
                "dispatch",
                rounds=1,
                k_rounds=1,
                acks=n_acks,
                votes=n_votes,
                recycles=0,
                reads=n_reads,
                echoes=n_echo,
                upload_bytes=upload,
                n_dispatches=n_dispatches,
                dispatch_ms=(time.perf_counter() - obs.t0) * 1e3,
                gate=self._obs_gate(
                    do_tick, n_acks, n_votes, 0, n_reads, n_echo
                ),
                mu_wait_ms=mu_wait,
                pending_rounds=0,
                read_slots_in_use=(
                    int(self._read_busy.sum())
                    if self._read_plane_used else None
                ),
                **self._take_array_counts(),
            )
            self.last_span_seq = span["seq"]
            t_eg = time.perf_counter()

        res = StepResult()
        # ONE device→host transfer for the whole egress set (a
        # network-attached chip pays the full round trip per readback)
        telem = out.telem
        eg = self._fetch_egress(out.egress)
        del out
        with (obs.phase("decode") if obs is not None else _OFF):
            if telem is not None:
                # deferred readback: stage the device aggregate, pull it
                # at snapshot (sampler) cadence, not dispatch cadence
                self._stage_telem(telem, self._row_cid.copy(), rounds=1)
            committed, bits, rdc, rdi, kvv, kvi, kva = _pk.split_egress(
                eg, self._dims, *planes
            )
            n_pairs = (
                self._decode_reads(res, rdc, rdi, reads)
                if rdc is not None else 0
            )
            # device_get arrays are read-only; the cache must stay
            # writable for _upload_dirty's row sync
            self._committed_cache = np.array(committed, dtype=np.int32)
            if kvi is not None:
                self._translate_kv(
                    res, kvv, kvi, kva, self._row_cid, self._row_base
                )
                if self.kv_egress_hook is not None:
                    self.kv_egress_hook(res)
            if self._devsm_used:
                self._kv_free_applied()
            changed = self._translate_egress(
                res, committed, prev_committed, self._row_cid,
                self._row_base, bits, self._flag_bits(),
            )
        if obs is not None:
            obs.egress(
                span,
                arrays_retired=1,
                egress_ms=(time.perf_counter() - t_eg) * 1e3,
                egress_rows=int(changed.size),
                reads_released=(
                    int(res.read_counts.sum())
                    if res.read_counts is not None else 0
                ),
                decode_pairs=n_pairs,
            )
            kv_span, self._obs_kv_span = self._obs_kv_span, None
            if kv_span is not None:
                obs.devsm_egress(
                    kv_span,
                    applied=res.kv_applied_ops,
                    reads_served=(
                        int(len(res.kv_cids))
                        if res.kv_cids is not None else 0
                    ),
                )
        return res

    def _filter_acks(self):
        """Tuple-staged and block-staged acks with stale-epoch events
        (staged before a row transition) filtered out: ``(live, blocks)``,
        the ``(row, slot, rel)`` tuples and the blocks as triples of
        int32 arrays; clears both buffers.  No array is read through an
        index array on the way (each such read hands the interpreter
        away, whatever its size): tuples are filtered as tuples; a block
        is whole unless a row's epoch was bumped after it was staged, and
        only then compared, against those rows (``_epoch_bumped``, which
        ``_filter_reads`` clears once the round's read blocks have been
        held against it too)."""
        live, blocks = (), ()
        if self._acks:
            epoch = self._row_epoch
            live = [
                (r, s, v) for r, s, v, ep in self._acks if ep == epoch[r]
            ]
            self._acks = []
        if self._ack_blocks:
            blocks, stale = self._live_blocks(self._ack_blocks)
            self._n_stale_blocks += stale
            self._ack_blocks = []
        return live, blocks

    def _gather_acks(self):
        """``_filter_acks`` as three flat arrays (the sparse program's
        event lists, a sealed round of the fused path)."""
        return _ack_columns(*self._filter_acks())

    def _ingress_for(self, kind: str, **layout) -> _pk.Ingress:
        """The host buffer a dispatch of this shape stages into, every
        section back at its nothing-staged fill.  Made on first use and
        restaged in place from then on: the caller has fetched the egress
        of the last program that read it (the sparse chunk loop blocks
        on it instead)."""
        key = self._ingress_key(kind, layout)
        # a shape outside the warm plan (a churn width, a K of a bench's
        # own) shares one slot with the others of its kind: the buffers
        # held never outgrow the plan's plus one a kind
        slot = key if key in self._ingress_keep else kind
        held = self._ingress.get(slot)
        if held is not None and held[0] == key:
            held[1].reset()
            return held[1]
        ing = _pk.Ingress(_pk.ingress_sections(
            kind, self.n_groups, self.n_peers, self._dims, **layout
        ))
        self._ingress[slot] = (key, ing)
        return ing

    @staticmethod
    def _ingress_key(kind: str, layout: dict) -> tuple:
        return (kind,) + tuple(sorted(layout.items()))

    def _launch(self, fn, ing: _pk.Ingress, **statics) -> _pk.PackedOut:
        """Run ``fn`` on the donated state blocks and the staged ingress
        block, handed over as the host buffer it is (the put rides the
        launch: no array of the step's own is made, none has to die), and
        keep the blocks' successors.  The blocks the launch replaced stay
        in ``_retired`` until ``drop_retired``: their deaths are not on
        the way from the launch to the egress and the fan-out."""
        obs = self._obs
        if self._retired:  # a bare caller's last step's, still held
            self._drop_retired()
        with (obs.phase("launch") if obs is not None else _OFF):
            out = fn(
                self._blk, ing.buf, **self._engine_statics(), **statics
            )
        self._retired, self._blk = tuple(self._blk), out.blocks
        if obs is not None:
            self._n_retired += len(StateBlocks._fields)
        return out

    def _drop_retired(self) -> None:
        obs = self._obs
        with (obs.phase("retire") if obs is not None else _OFF):
            self._retired = ()

    def drop_retired(self) -> None:
        """Drop the state blocks the last step's launch replaced and its
        fetched egress block.  A coordinator calls this once its round's
        commits are offloaded (each death hands the interpreter away, and
        there it delays no acknowledgement); the time lands late on that
        step's span, as ``retire_ms``.  A caller that never does loses
        nothing: the next launch drops them."""
        if self._retired:
            self._drop_retired()
            if self._obs is not None:
                self._obs.retire()

    def _take_array_counts(self) -> dict:
        """``arrays_made`` (none: the ingress block rides the launch),
        ``arrays_retired`` (state blocks replaced, unread egress),
        ``ack_blocks_stale`` / ``read_blocks_stale`` (staged blocks that
        took the per-row epoch comparison) and ``reads_scalar`` /
        ``echoes_scalar`` (tuple-staged read events filtered as tuples)
        since the last span, zeroed."""
        out = {
            "arrays_made": 0,
            "arrays_retired": self._n_retired,
            "ack_blocks_stale": self._n_stale_blocks,
            "read_blocks_stale": self._n_stale_read_blocks,
            "reads_scalar": self._n_reads_scalar,
            "echoes_scalar": self._n_echoes_scalar,
        }
        self._n_retired = self._n_stale_blocks = 0
        self._n_stale_read_blocks = 0
        self._n_reads_scalar = self._n_echoes_scalar = 0
        return out

    def _stage_sparse(self, acks, votes, has_votes: bool) -> _pk.Ingress:
        """The event lists of one sparse dispatch in its ingress block:
        ``acks`` as ``_gather_acks`` hands them, ``votes`` the staged
        (row, slot, grant) tuples."""
        ing = self._ingress_for(
            "sparse", cap=self.event_cap, has_votes=has_votes
        )
        v = ing.views
        ag, ap, av = acks
        n = ag.size
        v["ack_g"][:n] = ag
        v["ack_p"][:n] = ap
        v["ack_val"][:n] = av
        v["n"][0] = n
        if votes:
            cols = np.array(votes, dtype=np.int64).T
            n = cols.shape[1]
            v["vote_g"][:n] = cols[0]
            v["vote_p"][:n] = cols[1]
            v["vote_grant"][:n] = cols[2]
            v["n"][1] = n
        return ing

    def _dispatch(self, acks, votes, do_tick: bool):
        obs = self._obs
        # vote-free round: the has_votes=False variant compiles the vote
        # scatter out entirely and its ingress has no vote lists
        has_votes = bool(votes)
        with (obs.phase("stage") if obs is not None else _OFF):
            ing = self._stage_sparse(acks, votes, has_votes)
            if obs is not None:
                # accumulated: an oversized backlog runs several chunked
                # dispatches per step and the span must account them all
                self._obs_upload += upload_nbytes(ing.buf)
        out = self._launch(
            _pk.quorum_step,
            ing,
            cap=self.event_cap,
            do_tick=do_tick,
            # ticking rounds must track contact even on a
            # device_ticks=False engine (defensive: a stray
            # do_tick=True call would otherwise consume one-shot
            # contact acks without the reset)
            track_contact=self.device_ticks or do_tick,
            has_votes=has_votes,
            **self._fold_hints(),
        )
        dp = self._devprof
        if dp is not None:
            dp.note_dispatch("sparse", out.egress, rounds=1, live_rounds=1)
        return out

    def _dispatch_dense(
        self, acks, votes, do_tick: bool, reads=None,
        kvents=None, kvreads=None, has_kv=None,
    ):
        """Aggregate a round's events into the (G,P) planes of ONE
        ingress block and run the scatter-free dense kernel
        (kernels.quorum_step_dense_impl).  ``acks`` is ``_filter_acks``'
        pair, ``reads`` the round's ``_filter_reads`` (None: none
        survived) and ``kvents``/``kvreads`` the devsm buffers
        (``_gather_kv`` shape); both planes live only on this kernel —
        step() forces dense whenever they are present.

        An event is stored in the arity it was staged in: a tuple by a
        scalar store (``read_idx[r, s] = v``: nothing on the way hands
        the interpreter away), a block through its index arrays.  The
        sections that held only scalar stores name their cells to the
        ingress buffer, so its next reset puts back those and refills
        nothing whole."""
        obs = self._obs
        with (obs.phase("stage") if obs is not None else _OFF):
            p = self.n_peers
            has_votes = bool(votes)
            has_reads = reads is not None
            if has_kv is None:
                has_kv = kvents is not None or kvreads is not None
            ing = self._ingress_for(
                "dense", has_votes=has_votes, has_reads=has_reads,
                has_kv=has_kv,
            )
            v = ing.views
            cells = ing.cells
            live, blocks = acks
            ack = v["ack"]
            # max-aggregation == scatter-max: order-independent, exact;
            # -1 = untouched (touched cells hold rel >= 0)
            for r, sl, rel in live:
                if rel > ack[r, sl]:
                    ack[r, sl] = rel
            if blocks:
                # Flat 1-D indexing keeps ufunc.at on numpy's contiguous
                # fast path (the 2-D tuple form is several× slower at the
                # very occupancies that select the dense path).  The flat
                # index is made block by block: a coordinator's blocks
                # are a host's heartbeats each, short of the length past
                # which a numpy pass hands the interpreter away, which
                # their concatenation is not
                cell, rels = _concat_columns((), [
                    (r.astype(np.int64) * p + sl, rel)
                    for r, sl, rel in blocks
                ], 2)
                np.maximum.at(ack.reshape(-1), cell, rels)
            else:
                cells["ack"] = [(r, sl) for r, sl, _rel in live]
            if has_votes:
                cols = np.array(votes, dtype=np.int64).T
                v["votes"][cols[0], cols[1]] = cols[2]
            if has_reads:
                stages, stage_blocks, echoes, echo_blocks = reads
                ridx, rcnt, recho = (
                    v["read_idx"], v["read_cnt"], v["read_echo"]
                )
                # a slot staged twice in a round keeps its last stage
                # (a cancel after its stage): tuples first, in order
                for r, sl, rel, c in stages:
                    ridx[r, sl] = rel
                    rcnt[r, sl] = c
                for r, sl, pe in echoes:
                    recho[r, sl] |= 1 << pe
                if stage_blocks or echo_blocks:
                    self._stage_read_blocks(v, stage_blocks, echo_blocks)
                if not stage_blocks:
                    cells["read_idx"] = cells["read_cnt"] = [
                        (r, sl) for r, sl, _rel, _c in stages
                    ]
                if not echo_blocks:
                    cells["read_echo"] = [(r, sl) for r, sl, _pe in echoes]
            if kvents is not None and kvents[0].size:
                rr, sl, rel, key, val = kvents
                v["kv_idx"][rr, sl] = rel
                v["kv_key"][rr, sl] = key
                v["kv_val"][rr, sl] = val
            if kvreads is not None and kvreads[0].size:
                rr, sl, key = kvreads
                v["kv_rkey"][rr, sl] = key
            if obs is not None:
                # exactly what the program receives (upload_nbytes docstring)
                self._obs_upload += upload_nbytes(ing.buf)
        out = self._launch(
            _pk.quorum_step_dense,
            ing,
            do_tick=do_tick,
            track_contact=self.device_ticks or do_tick,
            has_votes=has_votes,
            has_reads=has_reads,
            has_kv=has_kv,
        )
        dp = self._devprof
        if dp is not None:
            dp.note_dispatch("dense", out.egress, rounds=1, live_rounds=1)
        return out

    @staticmethod
    def _stage_read_blocks(v, stage_blocks, echo_blocks) -> None:
        """Block-staged read stages and echoes into the dense read
        sections, through their index arrays."""
        for rr, sl, val, c in stage_blocks:
            v["read_idx"][rr, sl] = val
            v["read_cnt"][rr, sl] = c
        for rr, sl, pe in echo_blocks:
            np.bitwise_or.at(
                v["read_echo"], (rr, sl), np.left_shift(1, pe)
            )

    # ------------------------------------------------------------------
    # introspection (tests / debugging)
    # ------------------------------------------------------------------

    def _read(self, field_name: str, row: int):
        """Field value at a row: pending mirror edits win over device —
        including a staged in-program recycle, whose mirror row is the
        post-recycle truth while the device still holds the old tenant."""
        return self.read_rows(field_name, [row])[0]

    def read_rows(self, field_name: str, rows) -> np.ndarray:
        """``_read`` for many rows from at most one device gather
        (``sync_rows`` pulls them into the mirror, which is then the truth
        for every one of them until the next dispatch)."""
        self.sync_rows(rows)
        return self.mirror.arrays[field_name][np.asarray(rows, np.int64)]

    def committed_index(self, cluster_id: int) -> int:
        gi = self.groups[cluster_id]
        return int(gi.base) + int(self._read("committed", gi.row))

    def committed_snapshot(self, cids=None) -> Dict[int, int]:
        """Absolute committed indexes for ``cids`` (default: every
        registered group) from AT MOST one device→host transfer.
        ``committed_index`` costs a device readback per call; scale
        probes (bench rungs 4/5) sample through this instead.  Right
        after ``step()`` the
        egress cache is fresh and the call is zero-transfer — it indexes
        the vector the device produced for that round's egress.  Pass
        ``cids`` when sampling: building the full dict for 100k groups
        costs ~100k boxed ints per call (vectorized twin:
        ``committed_view``)."""
        self._harvest_inflight()
        self._refresh_committed_cache()
        committed = self._committed_cache
        mirror = self.mirror.arrays["committed"]
        dirty = self._dirty
        pend = self._churn_pending
        items = (
            self.groups.items()
            if cids is None
            else ((cid, self.groups[cid]) for cid in cids)
        )
        return {
            cid: int(gi.base)
            + int(
                mirror[gi.row]
                if gi.row in dirty or gi.row in pend
                else committed[gi.row]
            )
            for cid, gi in items
        }

    def peer_match(self, cluster_id: int, node_id: int) -> int:
        gi = self.groups[cluster_id]
        return int(gi.base) + int(self._read("match", gi.row)[gi.slots[node_id]])
