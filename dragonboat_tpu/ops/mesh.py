"""Mesh-sharded dispatch plane: per-shard engines, concurrent streams.

The GSPMD path (``BatchedQuorumEngine(sharding=NamedSharding(...))``)
partitions ONE program over the mesh — correct, but every dispatch is a
multi-device program: on the XLA CPU client each one is an
all-participant rendezvous on a shared per-device thread pool, which is
why multi-device dispatches used to serialize process-wide on the old
``_MULTIDEV_MU`` class lock.  One engine, one dispatch at a time, zero
dispatch concurrency from mesh hardware.

:class:`MeshQuorumEngine` takes the other branch the quorum math allows:
no data ever flows BETWEEN groups, so a mesh of N devices can run N
completely independent single-device programs — one
:class:`~.engine.BatchedQuorumEngine` per shard, each owning a
contiguous group partition, each with its own dispatch stream (a
dedicated launcher thread) and its own per-shard dispatch lock (a
single-device engine's lock is ``nullcontext`` — nothing to
rendezvous).  ``begin_round`` / ``step_rounds`` / ``harvest`` fan out to
every stream and join, so the pipelined double-buffer ingress/egress
runs per shard and the blocking egress transfers overlap instead of
queueing behind a global mutex.

The facade presents the single-engine API the coordinator speaks
(staging, round plane, warmup latches, obs/devprof attachment) plus a
group-sharded global ``dev`` view assembled zero-copy from the shard
states via ``jax.make_array_from_single_device_arrays`` — callers that
introspect sharding (``tests/test_sharding.py``,
``testing.run_sharded_stack_check``) see exactly the
``P(GROUP_AXIS)``-sharded state the GSPMD path produced.

Placement is live: groups land on the least-loaded shard at
registration, and :meth:`maybe_rebalance` migrates hot groups between
shards — stage-out on the source (sync + mirror-row capture), stage-in
on the target (fresh row + captured image + base restore), commit
watermarks preserved.  This is the cross-shard generalization of the
in-program membership-recycle path: same same-geometry tenant-swap
contract, but the row changes device, so the swap goes through the
mirror instead of the recycle kernel.
"""
from __future__ import annotations

import os
import threading
import time
from queue import Queue
from typing import Dict, List, Optional, Tuple

import numpy as np

from .engine import (
    DEFAULT_EVENT_CAP,
    BatchedQuorumEngine,
    MultiRoundResult,
    StepResult,
    WARM_K_BUCKETS,
)
from .state import QuorumState
from ..logger import get_logger

mlog = get_logger("mesh")

#: mirror fields excluded from the migration image: the read plane is
#: required quiescent at stage-out (pending reads die with transitions
#: anyway — scalar twin builds a fresh ReadIndex) and the devsm KV image
#: migrates through ``kv_restore`` (the applied-state restore path), so
#: copying the raw device-plane rows would only risk resurrecting stale
#: slot bookkeeping on the target.
_MIGRATE_SKIP = (
    "read_index", "read_count", "read_acks",
    "kv_value", "kv_ent_index", "kv_ent_key", "kv_ent_val",
)


class _ShardStream(threading.Thread):
    """One shard's dispatch stream: a dedicated launcher thread so every
    dispatch of shard *i* issues from the same thread, in program order,
    concurrently with every other shard's stream.  The facade submits
    one closure per shard per round and joins — the engines themselves
    are only ever touched by their stream while a fan-out is in flight,
    and only by the (coordinator-serialized) caller between fan-outs."""

    def __init__(self, idx: int):
        super().__init__(name=f"mesh-shard-{idx}", daemon=True)
        self.idx = idx
        self._jobs: Queue = Queue()
        self.start()

    def run(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            fn, out, done = job
            try:
                out["result"] = fn()
            except BaseException as e:  # joined and re-raised by caller
                out["error"] = e
            finally:
                done.set()

    def submit(self, fn):
        out: dict = {}
        done = threading.Event()
        self._jobs.put((fn, out, done))
        return out, done

    def stop(self) -> None:
        self._jobs.put(None)


class _MeshGroupInfo:
    """Facade view of a shard's ``GroupInfo`` in GLOBAL row space.

    Delegates to the owning shard's live record (mutations — rebase,
    membership — show through) and survives migration: the facade
    repoints ``_gi``/``_off`` when the group changes shard, so a held
    reference never goes stale."""

    __slots__ = ("_gi", "_off")

    def __init__(self, gi, off: int):
        self._gi = gi
        self._off = off

    @property
    def row(self) -> int:
        return self._off + self._gi.row

    @property
    def cluster_id(self) -> int:
        return self._gi.cluster_id

    @property
    def base(self) -> int:
        return self._gi.base

    @property
    def slots(self):
        return self._gi.slots

    @property
    def self_slot(self) -> int:
        return self._gi.self_slot

    @property
    def node_ids(self):
        return self._gi.node_ids


class MeshQuorumEngine:
    """N per-shard single-device engines behind the batched-engine API.

    ``n_groups`` must divide evenly over the shards (the coordinator
    rounds capacity up to a device multiple before constructing this).
    Global row numbering is ``shard * groups_per_shard + local_row``;
    cluster-id-keyed calls route through the live assignment table.
    """

    def __init__(
        self,
        n_groups: int,
        n_peers: int,
        event_cap: int = DEFAULT_EVENT_CAP,
        devices=None,
        device_ticks: bool = True,
        rebalance_ratio: float = 1.5,
        **engine_kwargs,
    ):
        import jax
        from jax.sharding import SingleDeviceSharding

        from .sharding import make_mesh

        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if len(devices) < 2:
            raise ValueError("mesh engine needs >= 2 devices")
        if n_groups % len(devices):
            raise ValueError(
                f"{n_groups} groups do not shard evenly over "
                f"{len(devices)} devices"
            )
        self.devices = devices
        self.n_shards = len(devices)
        self.n_groups = n_groups
        self.n_peers = n_peers
        self.event_cap = event_cap
        self.device_ticks = device_ticks
        self.shard_groups = n_groups // self.n_shards
        #: cost-driven placement knob: migrate only when the hottest
        #: shard's dispatch-cost EMA exceeds the coolest's by this factor
        self.rebalance_ratio = float(rebalance_ratio)
        self.mesh = make_mesh(np.array(devices))
        per_cap = max(event_cap // self.n_shards, 512)
        self.shards: List[BatchedQuorumEngine] = [
            BatchedQuorumEngine(
                self.shard_groups, n_peers, event_cap=per_cap,
                device_ticks=device_ticks,
                sharding=SingleDeviceSharding(d),
                **engine_kwargs,
            )
            for d in devices
        ]
        s0 = self.shards[0]
        self.n_read_slots = s0.n_read_slots
        self.n_kv_slots = s0.n_kv_slots
        self.n_kv_ents = s0.n_kv_ents
        self.n_kv_reads = s0.n_kv_reads
        self.groups: Dict[int, _MeshGroupInfo] = {}
        self._assign: Dict[int, int] = {}
        #: add_group kwargs per cid, replayed verbatim at stage-in (the
        #: voting/observer/witness split is not recoverable from the
        #: mirror masks alone)
        self._reg: Dict[int, dict] = {}
        self._streams = [_ShardStream(i) for i in range(self.n_shards)]
        #: per-shard dispatch-cost EMA (ms) — the facade's own cost
        #: attribution; devprof's sampled device_ms rides the same spans
        self._load_ms = np.zeros(self.n_shards, np.float64)
        self._migrations = 0
        self._fanout_mu = threading.Lock()
        self._inflight_n = 0
        self._inflight_peak = 0
        self._kv_hook = None
        self._kv_hook_mu = threading.Lock()
        for s in self.shards:
            s.kv_egress_hook = self._relay_kv_egress
        self._obs = None
        self._devprof = None
        self._warmup_mu = threading.Lock()
        self._warmup_thread: Optional[threading.Thread] = None
        self._warmup_cancel = threading.Event()
        # commit-rate snapshot for hot-group selection (maybe_rebalance)
        self._rate_base: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    # routing
    # ------------------------------------------------------------------

    def _shard_of(self, cluster_id: int) -> BatchedQuorumEngine:
        return self.shards[self._assign[cluster_id]]

    def _shard_of_row(self, row: int) -> Tuple[BatchedQuorumEngine, int]:
        return self.shards[row // self.shard_groups], row % self.shard_groups

    def shard_index(self, cluster_id: int) -> int:
        """Which shard currently owns the group (the assignment table)."""
        return self._assign[cluster_id]

    @property
    def free_rows(self) -> int:
        return sum(len(s._free) for s in self.shards)

    def assign_shard(self, cluster_id: int) -> int:
        """Placement decision for a NEW group: the least-loaded shard
        with a free row — load is the dispatch-cost EMA, group count the
        tie-break (both zero at startup → round-robin by count)."""
        best, best_key = -1, None
        for i, s in enumerate(self.shards):
            if not s._free:
                continue
            key = (len(s.groups), self._load_ms[i])
            if best_key is None or key < best_key:
                best, best_key = i, key
        if best < 0:
            raise RuntimeError("quorum engine full")
        return best

    # ------------------------------------------------------------------
    # group lifecycle
    # ------------------------------------------------------------------

    #: a mesh facade's shards have no idle columns wired: a quiesce group
    #: keeps scalar ticks here (``TpuQuorumCoordinator.quiesce_on_device``)
    quiesce_enabled = False

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
    ) -> _MeshGroupInfo:
        if cluster_id in self.groups:
            raise ValueError(f"group {cluster_id} already registered")
        idx = self.assign_shard(cluster_id)
        gi = self.shards[idx].add_group(
            cluster_id, node_ids, self_id,
            election_timeout=election_timeout,
            heartbeat_timeout=heartbeat_timeout,
            rand_timeout=rand_timeout,
            check_quorum=check_quorum,
            witnesses=witnesses,
            observers=observers,
        )
        self._assign[cluster_id] = idx
        self._reg[cluster_id] = dict(
            node_ids=list(node_ids), self_id=self_id,
            election_timeout=election_timeout,
            heartbeat_timeout=heartbeat_timeout,
            check_quorum=check_quorum,
            witnesses=tuple(witnesses), observers=tuple(observers),
        )
        mgi = _MeshGroupInfo(gi, idx * self.shard_groups)
        self.groups[cluster_id] = mgi
        if self._obs is not None:
            self._obs.placement(self.shard_counts())
        return mgi

    def remove_group(self, cluster_id: int) -> None:
        idx = self._assign.pop(cluster_id)
        self.groups.pop(cluster_id)
        self._reg.pop(cluster_id, None)
        self.shards[idx].remove_group(cluster_id)
        if self._obs is not None:
            self._obs.placement(self.shard_counts())

    # ------------------------------------------------------------------
    # migration (cost-driven placement)
    # ------------------------------------------------------------------

    def _quiescent(self, s: BatchedQuorumEngine, gi) -> bool:
        """Stage-out precondition: no pending device-plane work for the
        row.  Staged-but-undispatched acks/votes are droppable raft
        traffic (retransmits re-stage them) and die with the stage-out's
        ``remove_group`` purge; pending READS and buffered devsm entry
        ops are not droppable mid-flight, so a group carrying either
        stays put until they drain."""
        if s._read_plane_used and (
            s.read_slots_free(gi.cluster_id) < s.n_read_slots
        ):
            return False
        if s._devsm_used:
            if s._kv_queue.get(gi.row):
                return False
            if (s._kv_ent_rel[gi.row] >= 0).any():
                return False
        if gi.row in s._churn_pending or gi.row in s._churn_rows:
            return False
        return True

    def migrate_group(self, cluster_id: int, target: int) -> bool:
        """Move a group to ``target`` shard: stage-out on the source
        (harvest + row sync + mirror-image capture + remove), stage-in
        on the target (fresh row, captured image, base restore) —
        commit watermarks preserved to the index.  Returns False (and
        moves nothing) when the move is not currently safe."""
        if not (0 <= target < self.n_shards):
            raise ValueError(f"no shard {target}")
        src_idx = self._assign[cluster_id]
        if target == src_idx:
            return False
        src, tgt = self.shards[src_idx], self.shards[target]
        if not tgt._free:
            return False
        gi = src.groups[cluster_id]
        if not self._quiescent(src, gi):
            return False
        t0 = time.perf_counter()
        # stage-out: device row -> mirror, capture the image + base
        src.sync_rows([gi.row])
        img = src.mirror.row_image(gi.row, skip=_MIGRATE_SKIP)
        kv_img = src.kv_values(cluster_id) if src._devsm_used else None
        base = gi.base
        reg = self._reg[cluster_id]
        src.remove_group(cluster_id)
        # stage-in: fresh target row, then the captured image verbatim
        # (same geometry — the cross-shard twin of recycle_row), then
        # the base so relative indexes keep their absolute meaning
        ngi = tgt.add_group(
            cluster_id, rand_timeout=int(img["rand_timeout"]), **reg
        )
        tgt.mirror.restore_row(ngi.row, img)
        ngi.base = base
        tgt._row_base[ngi.row] = base
        tgt._dirty.add(ngi.row)
        if kv_img is not None:
            tgt.kv_restore(cluster_id, kv_img)
        mgi = self.groups[cluster_id]
        mgi._gi = ngi
        mgi._off = target * self.shard_groups
        self._assign[cluster_id] = target
        self._migrations += 1
        if self._obs is not None:
            self._obs.migration(
                cluster_id, src_idx, target,
                (time.perf_counter() - t0) * 1e3,
                self.shard_counts(),
            )
        mlog.debug(
            "migrated group %d: shard %d -> %d", cluster_id, src_idx, target
        )
        return True

    @property
    def migrations(self) -> int:
        return self._migrations

    def shard_counts(self) -> List[int]:
        return [len(s.groups) for s in self.shards]

    def shard_stats(self) -> List[dict]:
        """Per-shard placement/cost snapshot (health sampler food)."""
        return [
            {
                "groups": len(s.groups),
                "load_ms": round(float(self._load_ms[i]), 4),
                "fused_ready": bool(s.fused_ready),
            }
            for i, s in enumerate(self.shards)
        ]

    def maybe_rebalance(self, max_moves: int = 1) -> int:
        """Cost-driven placement pass: when the hottest shard's
        dispatch-cost EMA exceeds the coolest's by ``rebalance_ratio``
        (or its group count leads by more than one), migrate its hottest
        group — highest commit advance since the last pass — to the
        coolest shard.  Returns migrations performed."""
        moved = 0
        view = None
        for _ in range(max_moves):
            counts = np.array(self.shard_counts())
            hot = int(np.argmax(self._load_ms))
            cool = int(np.argmin(self._load_ms))
            cost_skew = (
                hot != cool
                and counts[hot] > 0
                and self._load_ms[hot]
                > self.rebalance_ratio * max(self._load_ms[cool], 1e-6)
            )
            count_skew = counts.max() - counts.min() > 1
            if count_skew and not cost_skew:
                hot = int(np.argmax(counts))
                cool = int(np.argmin(counts))
            elif not cost_skew:
                break
            cid = self._hottest_group(hot, view)
            if cid is None or not self.migrate_group(cid, cool):
                break
            moved += 1
        # re-baseline the commit-rate window every pass
        self._rate_base = np.concatenate(
            [s.committed_view() for s in self.shards]
        )
        return moved

    def _hottest_group(self, shard_idx: int, _view=None) -> Optional[int]:
        """The source shard's group with the largest commit advance since
        the last rebalance pass (ties -> first); None when the shard is
        empty."""
        s = self.shards[shard_idx]
        if not s.groups:
            return None
        view = s.committed_view()  # absolute (base included)
        off = shard_idx * self.shard_groups
        if self._rate_base is not None:
            base = (
                self._rate_base[off:off + self.shard_groups]
            )
            delta = view - base
        else:
            delta = view
        cids = s.row_cids()
        live = cids >= 0
        if not live.any():
            return None
        delta = np.where(live, delta, -1)
        return int(cids[int(np.argmax(delta))])

    # ------------------------------------------------------------------
    # staging (cid-routed pass-through)
    # ------------------------------------------------------------------

    def set_leader(self, cluster_id, term, term_start, last_index) -> None:
        self._shard_of(cluster_id).set_leader(
            cluster_id, term, term_start, last_index
        )

    def set_candidate(self, cluster_id, term) -> None:
        self._shard_of(cluster_id).set_candidate(cluster_id, term)

    def set_follower(self, cluster_id, term) -> None:
        self._shard_of(cluster_id).set_follower(cluster_id, term)

    def set_randomized_timeout(self, cluster_id, timeout) -> None:
        self._shard_of(cluster_id).set_randomized_timeout(
            cluster_id, timeout
        )

    def restore_progress(self, cluster_id, committed, last_index) -> None:
        self._shard_of(cluster_id).restore_progress(
            cluster_id, committed, last_index
        )

    def rebase(self, cluster_id) -> None:
        self._shard_of(cluster_id).rebase(cluster_id)

    def ack(self, cluster_id, node_id, index) -> None:
        self._shard_of(cluster_id).ack(cluster_id, node_id, index)

    def vote(self, cluster_id, node_id, granted) -> None:
        self._shard_of(cluster_id).vote(cluster_id, node_id, granted)

    def heartbeat_resp(self, cluster_id, node_id) -> None:
        self._shard_of(cluster_id).heartbeat_resp(cluster_id, node_id)

    def leader_contact(self, cluster_id) -> None:
        self._shard_of(cluster_id).leader_contact(cluster_id)

    def stage_read(self, cluster_id, count: int = 1, index=None) -> int:
        return self._shard_of(cluster_id).stage_read(
            cluster_id, count=count, index=index
        )

    def read_ack(self, cluster_id, node_id, slot) -> None:
        self._shard_of(cluster_id).read_ack(cluster_id, node_id, slot)

    def cancel_read(self, cluster_id, slot) -> None:
        self._shard_of(cluster_id).cancel_read(cluster_id, slot)

    def read_slots_free(self, cluster_id) -> int:
        return self._shard_of(cluster_id).read_slots_free(cluster_id)

    def stage_recycle(self, old_cid, new_cid, *args, **kwargs):
        """Same-shard in-program tenant swap (the recycle kernel path);
        the new tenant inherits the old one's shard — cross-shard moves
        go through :meth:`migrate_group`."""
        idx = self._assign[old_cid]
        gi = self.shards[idx].stage_recycle(old_cid, new_cid, *args, **kwargs)
        reg = self._reg.pop(old_cid, None)
        self._assign.pop(old_cid)
        self.groups.pop(old_cid)
        self._assign[new_cid] = idx
        if reg is not None:
            self._reg[new_cid] = reg
        self.groups[new_cid] = _MeshGroupInfo(gi, idx * self.shard_groups)
        return gi

    # devsm KV plane
    def stage_kv_op(self, cluster_id, *args, **kwargs):
        return self._shard_of(cluster_id).stage_kv_op(
            cluster_id, *args, **kwargs
        )

    def stage_kv_ops(self, cluster_id, indexes, keys, values) -> bool:
        return self._shard_of(cluster_id).stage_kv_ops(
            cluster_id, indexes, keys, values
        )

    def stage_kv_read(self, cluster_id, key) -> int:
        return self._shard_of(cluster_id).stage_kv_read(cluster_id, key)

    def kv_reads_free(self, cluster_id) -> int:
        return self._shard_of(cluster_id).kv_reads_free(cluster_id)

    def kv_values(self, cluster_id) -> np.ndarray:
        return self._shard_of(cluster_id).kv_values(cluster_id)

    def kv_restore(self, cluster_id, values) -> None:
        self._shard_of(cluster_id).kv_restore(cluster_id, values)

    def _relay_kv_egress(self, res) -> None:
        # shard harvests run on their streams; the caller-facing hook
        # fires serialized so a scalar-side consumer never re-enters
        hook = self._kv_hook
        if hook is not None:
            with self._kv_hook_mu:
                hook(res)

    @property
    def kv_egress_hook(self):
        return self._kv_hook

    @kv_egress_hook.setter
    def kv_egress_hook(self, fn) -> None:
        self._kv_hook = fn

    # ------------------------------------------------------------------
    # reads / views (global row space)
    # ------------------------------------------------------------------

    def _read(self, field_name: str, row: int):
        s, local = self._shard_of_row(row)
        return s._read(field_name, local)

    def sync_rows(self, rows) -> None:
        by_shard: Dict[int, list] = {}
        for r in rows:
            by_shard.setdefault(r // self.shard_groups, []).append(
                r % self.shard_groups
            )
        for i, local in by_shard.items():
            self.shards[i].sync_rows(local)

    def committed_index(self, cluster_id) -> int:
        return self._shard_of(cluster_id).committed_index(cluster_id)

    def peer_match(self, cluster_id, node_id) -> int:
        return self._shard_of(cluster_id).peer_match(cluster_id, node_id)

    def committed_snapshot(self, cids=None) -> Dict[int, int]:
        if cids is not None:
            by_shard: Dict[int, list] = {}
            for cid in cids:
                by_shard.setdefault(self._assign[cid], []).append(cid)
            out: Dict[int, int] = {}
            for i, part in by_shard.items():
                out.update(self.shards[i].committed_snapshot(part))
            return out
        out = {}
        for s in self.shards:
            out.update(s.committed_snapshot())
        return out

    def committed_view(self) -> np.ndarray:
        return np.concatenate([s.committed_view() for s in self.shards])

    def row_cids(self) -> np.ndarray:
        return np.concatenate([s.row_cids() for s in self.shards])

    def _upload_dirty(self) -> None:
        for s in self.shards:
            s._upload_dirty()

    @property
    def dev(self) -> QuorumState:
        """Global group-sharded view of the shard states, assembled
        zero-copy: per field, the N single-device arrays become ONE
        ``P(GROUP_AXIS)``-sharded global array over the facade's mesh.
        Point-in-time — the next dispatch donates the underlying
        buffers, so hold it only across a quiescent window (exactly the
        GSPMD engine's contract for externally-held state)."""
        import jax

        from .sharding import state_sharding

        shardings = state_sharding(self.mesh)
        views = [s.dev for s in self.shards]  # each shard's blocks, unpacked
        fields = {}
        for name in QuorumState._fields:
            pieces = [getattr(v, name) for v in views]
            global_shape = (self.n_groups,) + tuple(pieces[0].shape[1:])
            fields[name] = jax.make_array_from_single_device_arrays(
                global_shape, getattr(shardings, name), pieces
            )
        return QuorumState(**fields)

    # ------------------------------------------------------------------
    # round plane (fan-out / join over the shard streams)
    # ------------------------------------------------------------------

    def _fanout(self, jobs):
        """Run ``(shard_index, closure)`` jobs on their dispatch streams;
        join; track the concurrency high-water mark for the mesh
        histogram."""
        pending = []
        for i, fn in jobs:
            def wrapped(fn=fn):
                with self._fanout_mu:
                    self._inflight_n += 1
                    self._inflight_peak = max(
                        self._inflight_peak, self._inflight_n
                    )
                try:
                    return fn()
                finally:
                    with self._fanout_mu:
                        self._inflight_n -= 1
            pending.append(self._streams[i].submit(wrapped))
        results = []
        for out, done in pending:
            done.wait()
            if "error" in out:
                raise out["error"]
            results.append(out.get("result"))
        with self._fanout_mu:
            peak, self._inflight_peak = self._inflight_peak, 0
        if self._obs is not None:
            self._obs.concurrency(peak)
        return results

    def begin_round(self) -> None:
        for s in self.shards:
            s.begin_round()

    def pending_rounds(self) -> int:
        return max(s.pending_rounds() for s in self.shards)

    @staticmethod
    def _buf_empty(rb) -> bool:
        return (
            len(rb.rows) == 0 and not rb.votes and not rb.churn
            and rb.reads is None and rb.racks is None
            and rb.kvents is None and rb.kvreads is None
        )

    def _shard_idle(self, s) -> bool:
        """True when a tickless dispatch on this shard would be a pure
        no-op: nothing staged, nothing dirty, nothing in flight, and
        every closed round is empty (``begin_round`` fans out
        unconditionally, so quiet shards accumulate empty bufs)."""
        if (
            s._acks or s._ack_blocks or s._votes or s._churn or s._dirty
            or s._reads_pending() or s._kv_pending()
            or s._kv_ents_buffered() or s._inflight is not None
        ):
            return False
        return all(self._buf_empty(rb) for rb in s._round_blocks)

    def _live_shards(self, do_tick: bool) -> List[int]:
        """Shards a dispatch must reach.  Tick rounds reach every shard
        that owns groups (its clocks must advance); event rounds skip
        idle shards entirely — their all-empty staged rounds are
        discarded, the event-free dispatch they'd pad into never
        launches.  This is where mesh fan-out beats the single GSPMD
        program on cost: a one-group hot spot costs ONE shard dispatch,
        not a whole-mesh rendezvous."""
        live = []
        for i, s in enumerate(self.shards):
            if do_tick:
                if s.groups:
                    live.append(i)
                continue
            if self._shard_idle(s):
                s._round_blocks.clear()
            else:
                live.append(i)
        return live

    def step_rounds(
        self,
        do_tick: bool = False,
        pipelined: bool = False,
        pad_rounds_to: int = 0,
        tick_rounds: Optional[int] = None,
    ) -> Optional[MultiRoundResult]:
        live = self._live_shards(do_tick)
        if not live:
            return None
        t0 = [0.0] * self.n_shards

        def job(i):
            def run():
                t = time.perf_counter()
                r = self.shards[i].step_rounds(
                    do_tick=do_tick, pipelined=pipelined,
                    pad_rounds_to=pad_rounds_to, tick_rounds=tick_rounds,
                )
                t0[i] = (time.perf_counter() - t) * 1e3
                return r
            return run

        results = self._fanout([(i, job(i)) for i in live])
        self._note_load(t0)
        return self._merge(results)

    def step(self, do_tick: bool = True) -> StepResult:
        live = self._live_shards(do_tick)
        if not live:
            return StepResult()
        t0 = [0.0] * self.n_shards

        def job(i):
            def run():
                t = time.perf_counter()
                r = self.shards[i].step(do_tick)
                t0[i] = (time.perf_counter() - t) * 1e3
                return r
            return run

        results = self._fanout([(i, job(i)) for i in live])
        self._note_load(t0)
        merged = self._merge(results)
        return merged if merged is not None else StepResult()

    def drop_retired(self) -> None:
        """Every shard's ``drop_retired`` (the coordinator's call after
        its fan-out; no shard is launching by then)."""
        for s in self.shards:
            s.drop_retired()

    def harvest(self) -> Optional[MultiRoundResult]:
        live = [
            i for i, s in enumerate(self.shards) if s._inflight is not None
        ]
        if not live:
            return None
        results = self._fanout(
            [(i, (lambda s=self.shards[i]: s.harvest())) for i in live]
        )
        return self._merge(results)

    def _note_load(self, walls_ms) -> None:
        # EMA with a short horizon: placement should chase the current
        # hot set, not the boot transient
        self._load_ms = 0.9 * self._load_ms + 0.1 * np.asarray(walls_ms)

    def _merge(self, results):
        """Merge per-shard egress into one result.  Cluster-id-keyed
        egress concatenates verbatim (every shard already reports in
        absolute cid/index terms); row-keyed views offset into global
        row space."""
        live = [r for r in results if r is not None]
        if not live:
            return None
        multi = [r for r in live if isinstance(r, MultiRoundResult)]
        if multi:
            out = MultiRoundResult(max(r.rounds for r in multi))
        else:
            out = StepResult()
        for r in live:
            out.won.extend(r.won)
            out.lost.extend(r.lost)
            out.elect.extend(r.elect)
            out.heartbeat.extend(r.heartbeat)
            out.demote.extend(r.demote)
            out.kv_applied_ops += r.kv_applied_ops
        for field in ("_commit_cids", "_commit_abs"):
            parts = [
                getattr(r, field) for r in live
                if getattr(r, field) is not None
            ]
            if parts:
                setattr(out, field, np.concatenate(parts))
        for field in (
            "read_cids", "read_slots", "read_index_abs", "read_counts",
            "kv_cids", "kv_slots", "kv_vals", "kv_index_abs",
        ):
            parts = [
                getattr(r, field) for r in live
                if getattr(r, field) is not None
            ]
            if parts:
                setattr(out, field, np.concatenate(parts))
        if multi and len(multi) == len(results) and all(
            r.committed_rel is not None for r in multi
        ):
            out.committed_rel = np.concatenate(
                [r.committed_rel for r in multi]
            )
        if multi:
            rows_parts = [
                r.commit_rows + i * self.shard_groups
                for i, r in enumerate(results)
                if isinstance(r, MultiRoundResult)
                and r.commit_rows is not None
            ]
            if rows_parts:
                out.commit_rows = np.concatenate(rows_parts)
        return out

    # ------------------------------------------------------------------
    # staging-state gates (coordinator round policy)
    # ------------------------------------------------------------------

    @property
    def _acks(self) -> bool:
        return any(len(s._acks) for s in self.shards)

    @property
    def _ack_blocks(self) -> bool:
        return any(len(s._ack_blocks) for s in self.shards)

    @property
    def _votes(self) -> bool:
        return any(len(s._votes) for s in self.shards)

    @property
    def _churn(self) -> bool:
        return any(len(s._churn) for s in self.shards)

    @property
    def _round_blocks(self) -> bool:
        return any(len(s._round_blocks) for s in self.shards)

    @property
    def _dirty(self) -> bool:
        return any(s._dirty for s in self.shards)

    @property
    def _read_plane_used(self) -> bool:
        return any(s._read_plane_used for s in self.shards)

    @property
    def _devsm_used(self) -> bool:
        return any(s._devsm_used for s in self.shards)

    def _reads_pending(self) -> bool:
        return any(s._reads_pending() for s in self.shards)

    def _kv_pending(self) -> bool:
        return any(s._kv_pending() for s in self.shards)

    def _kv_ents_buffered(self) -> bool:
        return any(s._kv_ents_buffered() for s in self.shards)

    @property
    def last_span_seq(self) -> int:
        return max(s.last_span_seq for s in self.shards)

    # ------------------------------------------------------------------
    # warmup (per-shard program sets, one niced background walker)
    # ------------------------------------------------------------------

    @property
    def fused_ready(self) -> bool:
        return all(s.fused_ready for s in self.shards)

    @property
    def kv_fused_ready(self) -> bool:
        return all(s.kv_fused_ready for s in self.shards)

    def warmup_fused(
        self,
        k_buckets=WARM_K_BUCKETS,
        include_reads: bool = True,
        include_single: bool = True,
        background: bool = True,
        include_kv: bool = False,
    ):
        """Warm every shard's program set.  One background walker warms
        the shards sequentially (each shard's programs compile for ITS
        device) — N concurrent XLA compile storms would starve the round
        thread on a small host, and the single-device programs carry no
        collectives, so there is no rendezvous to order (the historical
        ``test_full_stack_sharded_engine`` wedge cannot recur here)."""
        args = (
            tuple(k_buckets), include_reads, include_single, include_kv
        )
        if not background:
            self._warmup_walk(*args)
            return self.warmup_stats
        with self._warmup_mu:
            if self._warmup_thread is not None and (
                self._warmup_thread.is_alive()
            ):
                return self._warmup_thread
            if self.fused_ready:
                return None
            self._warmup_cancel.clear()
            self._warmup_thread = threading.Thread(
                target=self._warmup_walk, args=args,
                name="mesh-warmup", daemon=True,
            )
            self._warmup_thread.start()
            return self._warmup_thread

    def _warmup_walk(
        self, k_buckets, include_reads, include_single, include_kv
    ) -> None:
        try:  # same deprioritization as the engine's warm thread
            if threading.current_thread() is self._warmup_thread:
                os.nice(10)
        except (OSError, AttributeError):
            pass
        for s in self.shards:
            if self._warmup_cancel.is_set():
                return
            s.warmup_fused(
                k_buckets=k_buckets, include_reads=include_reads,
                include_single=include_single, background=False,
                include_kv=include_kv,
            )

    def warmup_devsm(self, k_buckets=WARM_K_BUCKETS, background: bool = True):
        if not background:
            for s in self.shards:
                s.warmup_devsm(k_buckets=k_buckets, background=False)
            return self.warmup_stats
        t = threading.Thread(
            target=lambda: [
                s.warmup_devsm(k_buckets=k_buckets, background=False)
                for s in self.shards
            ],
            name="mesh-warmup-devsm", daemon=True,
        )
        t.start()
        return t

    def cancel_warmup(self) -> None:
        self._warmup_cancel.set()
        for s in self.shards:
            s.cancel_warmup()

    @property
    def warmup_stats(self) -> dict:
        """Aggregate warm-compile record across shards (per-shard stats
        stay on each shard engine)."""
        agg = {
            "seconds": 0.0, "programs": 0,
            "cache_hits": 0, "cache_misses": 0, "error": None,
        }
        for s in self.shards:
            st = s.warmup_stats
            agg["seconds"] += st["seconds"]
            agg["programs"] += st["programs"]
            agg["cache_hits"] += st["cache_hits"]
            agg["cache_misses"] += st["cache_misses"]
            if agg["error"] is None and st["error"] is not None:
                agg["error"] = st["error"]
        agg["shards_ready"] = sum(
            1 for s in self.shards if s.fused_ready
        )
        return agg

    # devprof program-registry hooks (walked on shard 0: the program
    # set is identical per shard, only the target device differs)
    def warm_plan(self, *args, **kwargs):
        return self.shards[0].warm_plan(*args, **kwargs)

    def lower_variant(self, *args, **kwargs):
        return self.shards[0].lower_variant(*args, **kwargs)

    def _variant_args(self, *args, **kwargs):
        return self.shards[0]._variant_args(*args, **kwargs)

    @staticmethod
    def variant_label(kind, arg, has_reads, has_kv):
        return BatchedQuorumEngine.variant_label(kind, arg, has_reads, has_kv)

    # ------------------------------------------------------------------
    # observability / profiling attachment
    # ------------------------------------------------------------------

    def enable_obs(self, recorder=None, registry=None, host=None):
        """Attach per-shard ``EngineObs`` (one shared recorder so all
        shards' dispatch spans interleave in one ring — the overlap
        evidence) plus the facade's ``dragonboat_mesh_*`` instruments.
        Same repeat-call contract as the engine: no-args is a no-op,
        explicit arguments rebind.  ``host`` tags every shard's spans
        with the owning NodeHost."""
        if self._obs is not None and recorder is None and registry is None:
            for s in self.shards:
                s.enable_obs(host=host)
            return self._obs
        from ..obs.instruments import MeshObs

        if recorder is None:
            if self._obs is not None:
                recorder = self._obs.recorder
            else:
                from .. import obs as _obs_mod

                recorder = _obs_mod.default_recorder()
        for i, s in enumerate(self.shards):
            s.enable_obs(recorder, registry, shard=i, host=host)
        self._obs = MeshObs(
            recorder, registry=registry, n_shards=self.n_shards
        )
        self._obs.placement(self.shard_counts())
        return self._obs

    def disable_obs(self) -> None:
        self._obs = None
        for s in self.shards:
            s.disable_obs()

    def set_span_parent(self, seq) -> None:
        """Every shard's dispatch spans name this coordinator round."""
        for s in self.shards:
            s.set_span_parent(seq)

    def enable_devprof(self, devprof) -> None:
        self._devprof = devprof
        for s in self.shards:
            s.enable_devprof(devprof)

    def disable_devprof(self) -> None:
        self._devprof = None
        for s in self.shards:
            s.disable_devprof()

    def enable_telem(self, topk: int | None = None) -> None:
        """Flip every shard's telemetry latch (ISSUE 20): each shard's
        dispatches fold ITS partition's aggregate with no cross-shard
        rendezvous (the kernels' no-collectives invariant), and
        :meth:`telem_snapshot` merges the per-shard aggregates host-side
        — O(shards) work, independent of the group count."""
        for s in self.shards:
            s.enable_telem(topk)

    @property
    def telem_enabled(self) -> bool:
        return any(s.telem_enabled for s in self.shards)

    @property
    def n_telem_topk(self) -> int:
        return self.shards[0].n_telem_topk

    def telem_snapshot(self) -> dict | None:
        """Mesh-wide rollup of the shard aggregates: histograms, state
        counts and occupancy totals SUM (disjoint group partitions); the
        top-K merges by taking the K worst of the concatenated per-shard
        top-Ks — exact, because each shard's list already holds its K
        worst and K is the same everywhere.  None until every telem-on
        shard has harvested at least one fold (a partial merge would
        under-report fleet totals)."""
        snaps = [s.telem_snapshot() for s in self.shards]
        snaps = [t for t in snaps if t is not None]
        if not snaps or len(snaps) != sum(
            1 for s in self.shards if s.telem_enabled
        ):
            return None
        k = self.n_telem_topk
        merged = {
            "seq": min(t["seq"] for t in snaps),
            "mono": min(t["mono"] for t in snaps),
            "rounds": max(t["rounds"] for t in snaps),
            "groups": sum(t["groups"] for t in snaps),
            "lag_hist": [
                sum(t["lag_hist"][i] for t in snaps)
                for i in range(len(snaps[0]["lag_hist"]))
            ],
            "state_counts": [
                sum(t["state_counts"][i] for t in snaps)
                for i in range(len(snaps[0]["state_counts"]))
            ],
            "stalled": sum(t["stalled"] for t in snaps),
            "read_slots": sum(t["read_slots"] for t in snaps),
            "kv_ents": sum(t["kv_ents"] for t in snaps),
            "topk": sorted(
                (pair for t in snaps for pair in t["topk"]),
                key=lambda p: (-p[1], p[0]),
            )[:k],
            "shards": len(snaps),
        }
        return merged

    @property
    def _obs_instance(self):
        return self._obs

    def stop(self) -> None:
        """Tear down the dispatch streams (idempotent)."""
        self.cancel_warmup()
        for stream in self._streams:
            stream.stop()
