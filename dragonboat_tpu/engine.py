"""Execution engine: the scheduler driving step/apply work across groups.

Reference: ``execengine.go`` — step/apply/snapshot worker pools with groups
partitioned to workers by ``clusterID % workerCount`` and per-worker
``workReady`` wakeups.  The Python build keeps the same structure with
smaller default pools (GIL), and this is exactly the seam the batched TPU
quorum engine replaces: ``process_steps``'s per-group loop becomes one
device dispatch per tick (SURVEY.md §7).
"""
from __future__ import annotations

import threading
import time as _time
from typing import Dict, List, Optional, Tuple, TYPE_CHECKING

from .logger import get_logger
from .obs.recorder import OFF as _OFF, annotate as _annotate
from .queue import ReadyCluster
from .server.partition import FixedPartitioner

if TYPE_CHECKING:
    from .node import Node

plog = get_logger("engine")


class _WorkReady:
    """Per-worker ready-set + wakeup (reference ``execengine.go:90-132``)."""

    def __init__(self, count: int):
        self.count = count
        self.partitioner = FixedPartitioner(count)
        self.ready = [ReadyCluster() for _ in range(count)]
        self.cv = [threading.Condition() for _ in range(count)]
        self.flag = [False] * count

    def notify(self, idx: int) -> None:
        with self.cv[idx]:
            self.flag[idx] = True
            self.cv[idx].notify()

    def cluster_ready(self, cluster_id: int) -> None:
        idx = self.partitioner.get_partition_id(cluster_id)
        self.ready[idx].set_ready(cluster_id)
        # a worker already flagged has not taken its ready set yet (it
        # clears the flag before it does): this group is in what it will
        # take, and the condition's lock need not be touched.  Every
        # message, flag and wake-up of a thousand groups comes through
        # here; on a saturated interpreter each contended lock costs the
        # caller a switch interval.
        if not self.flag[idx]:
            self.notify(idx)

    def all_ready(self, idx: int) -> None:
        self.notify(idx)

    def wait(self, idx: int, timeout: float = 1.0) -> None:
        with self.cv[idx]:
            if not self.flag[idx]:
                self.cv[idx].wait(timeout)
            self.flag[idx] = False

    def get_ready(self, idx: int):
        return self.ready[idx].get_ready()


class _Committer:
    """Per-step-worker LogDB commit pipeline (one per shard when shards are
    worker-aligned).

    The reference's step worker blocks in ``SaveRaftState``
    (``execengine.go:966``) — affordable with Go's goroutine count and an
    Optane fsync; here a synchronous fsync in the step loop serializes every
    group on the worker behind every commit.  Instead the worker hands
    ``(pairs, updates)`` off and keeps stepping other groups; this thread
    **coalesces everything queued into one fsynced write batch** (classic
    group commit — same effect as the reference's one-WriteBatch-per-round
    geometry, ``rdb.go:187-210``) and then runs the post-fsync half of the
    round (non-Replicate messages out, committed entries to apply,
    ``Peer.Commit``) in submission order.  Per-group ordering is preserved
    by the node's ``commit_inflight`` flag: a group is never stepped again
    until its previous update has been committed.  Committed entries that
    an earlier update made durable do not wait here: ``process_steps`` has
    handed them to the apply queue before the submit.
    """

    def __init__(self, engine: "Engine", idx: int):
        self.engine = engine
        self.idx = idx
        self._q: List = []
        self._cv = threading.Condition()
        # diagnostics (read by Engine.stats)
        self.cycles = 0
        self.merged = 0
        # durable write batches the cycles' log saves committed: one a
        # cycle where the LogDB has a shard per step worker
        self.sync_batches = 0
        self.commit_s = 0.0
        self.post_s = 0.0
        self._thread = threading.Thread(
            target=self._main, name=f"committer-{idx}", daemon=True
        )
        self._thread.start()

    def submit(self, pairs, updates) -> None:
        with self._cv:
            self._q.append((pairs, updates))
            self._cv.notify()

    def _main(self) -> None:
        stopped = self.engine._stopped
        while True:
            with self._cv:
                while not self._q and not stopped.is_set():
                    self._cv.wait(0.2)
                if stopped.is_set() and not self._q:
                    return
                batch, self._q = self._q, []
            try:
                self._commit(batch)
            except Exception:
                plog.exception("committer %d failed", self.idx)
                # clear flags AND re-arm the groups (their ready bits were
                # consumed before the submit) so they retry immediately
                # instead of stalling until the next tick
                for pairs, _ in batch:
                    for n, _ in pairs:
                        n.commit_inflight = False
                        self.engine.set_step_ready(n.cluster_id)

    def _commit(self, batch) -> None:
        t0 = _time.perf_counter()
        merged = [ud for _, updates in batch for ud in updates]
        tr = self.engine.tracer
        synced = 0
        if merged:
            hp = self.engine.hostplane
            # the log save + fsync, named for the profiler while the
            # tracer is on (an idle device gap then reads "wal_sync")
            with (_annotate("wal_sync") if tr is not None else _OFF):
                if hp is not None:
                    # cross-shard group-commit tier: the shared flusher
                    # merges this committer's batch with every other
                    # committer's into one fsync cycle; returns only once
                    # durable, then the post-fsync half below runs here,
                    # concurrently with the other committers' halves
                    # (per-group ordering untouched — a group only ever
                    # rides its owning committer)
                    hp.wal.flush(merged)
                else:
                    # how many shard batches it committed (a LogDB that
                    # does not say counts none)
                    synced = self.engine.logdb.save_raft_state(merged) or 0
        t1 = _time.perf_counter()
        if tr is not None and merged:
            # the merged batch is durable here — whichever tier fsynced
            # it (group-commit WAL or the classic per-committer save)
            tr.mark_updates(merged, "wal")
            tr.count_wal_cycle(synced, len(merged), t1 - t0)
        after = 0
        for pairs, _ in batch:
            for n, ud in pairs:
                after += n.process_raft_update(ud)
                n.commit_raft_update(ud)
                n.commit_inflight = False
                # re-check inputs that arrived while the commit was in
                # flight (the step worker skipped this group meanwhile)
                self.engine.set_step_ready(n.cluster_id)
        self.engine.count_apply_handoffs(self.idx, 0, after)
        self.cycles += 1
        self.merged += len(merged)
        self.sync_batches += synced
        self.commit_s += t1 - t0
        self.post_s += _time.perf_counter() - t1

    def join(self, timeout: float = 2.0) -> None:
        with self._cv:
            self._cv.notify()
        self._thread.join(timeout=timeout)


class Engine:
    """Reference ``execengine.go:637`` ``execEngine``."""

    def __init__(
        self,
        get_nodes,  # Callable[[], Tuple[int, Dict[int, Node]]] → (csi, map)
        logdb,
        step_workers: int = 4,
        apply_workers: int = 4,
        get_csi=None,  # cheap cluster-set-index read; avoids the locked
        # dict copy in get_nodes on every worker wakeup when nothing changed
        hostplane=None,  # compartmentalized host plane (hostplane.py):
        # committers persist through its shared group-commit flusher and
        # apply readiness routes to its dedicated pool; None keeps the
        # classic per-committer fsync + in-engine apply workers
    ):
        self.get_nodes = get_nodes
        self.get_csi = get_csi
        self.logdb = logdb
        self.hostplane = hostplane
        # cross-plane request tracer (obs/trace.py, ISSUE 9; set by
        # NodeHost): committers stamp the "wal" stage on sampled entries
        # after their fsync.  None keeps the commit path bit-identical.
        self.tracer = None
        self._stopped = threading.Event()
        self.step_ready = _WorkReady(step_workers)
        self.apply_ready = _WorkReady(apply_workers)
        self._threads: List[threading.Thread] = []
        # per-worker node-map cache, reloaded when the cluster-set index
        # changes (reference loadBucketNodes execengine.go:889)
        self._step_cache: List = [(-1, {}) for _ in range(step_workers)]
        self._apply_cache: List = [(-1, {}) for _ in range(apply_workers)]
        # diagnostics per step worker: [rounds, groups_stepped, skipped,
        # step_s, applied_early, applied_after_sync] (the last two: updates
        # whose committed entries went to the apply queue before their
        # persist / after it; one writer each, the step worker and its
        # committer)
        self._step_stats = [
            [0, 0, 0, 0.0, 0, 0] for _ in range(step_workers)
        ]
        self._committers = [_Committer(self, i) for i in range(step_workers)]
        # dedicated snapshot worker pool (reference execengine.go:240-635,
        # 64 workers): multi-second SM save/recover/stream work must never
        # block the apply workers — a slow user snapshot on one group would
        # stall every group sharing that apply worker
        import queue as _queue

        self._ss_q: "_queue.Queue" = _queue.Queue()
        self.snapshot_workers = max(2, min(8, step_workers * 2))
        # replica-plane instruments (obs/instruments.py ReplicaObs; set by
        # NodeHost): the pool's busy seconds and queue depth.  None keeps
        # the workers' loop untouched.
        self.replica_obs = None
        for i in range(self.snapshot_workers):
            t = threading.Thread(
                target=self._snapshot_worker_main,
                name=f"snapshot-worker-{i}", daemon=True,
            )
            t.start()
            self._threads.append(t)
        for i in range(step_workers):
            t = threading.Thread(
                target=self._step_worker_main, args=(i,),
                name=f"step-worker-{i}", daemon=True,
            )
            t.start()
            self._threads.append(t)
        # with the host plane attached, apply readiness routes to its
        # dedicated pool — the in-engine apply workers would never be
        # signalled, so don't spawn them (thread budget matters on the
        # 1-vCPU box)
        for i in range(0 if hostplane is not None else apply_workers):
            t = threading.Thread(
                target=self._apply_worker_main, args=(i,),
                name=f"apply-worker-{i}", daemon=True,
            )
            t.start()
            self._threads.append(t)

    # ---- wakeups (reference setStepReady / setApplyReady) ----

    def set_step_ready(self, cluster_id: int) -> None:
        self.step_ready.cluster_ready(cluster_id)

    def set_apply_ready(self, cluster_id: int) -> None:
        hp = self.hostplane
        if hp is not None:
            # decoupled apply executor (sharded by group, order preserved)
            hp.apply_pool.submit(cluster_id)
            return
        self.apply_ready.cluster_ready(cluster_id)

    def notify_all(self) -> None:
        for i in range(self.step_ready.count):
            self.step_ready.notify(i)
        for i in range(self.apply_ready.count):
            self.apply_ready.notify(i)

    def _worker_nodes(
        self, cache: List, idx: int, partitioner: FixedPartitioner
    ) -> Dict[int, "Node"]:
        cached_csi, cached = cache[idx]
        if self.get_csi is not None and self.get_csi() == cached_csi:
            return cached
        csi, nodes = self.get_nodes()
        if cached_csi == csi:
            return cached
        mine = {
            cid: n
            for cid, n in nodes.items()
            if partitioner.get_partition_id(cid) == idx
        }
        cache[idx] = (csi, mine)
        return mine

    def _rearm_unknown(self, ready, nodes, work_ready) -> None:
        """Defense in depth against lost wakeups: a ready bit consumed for
        a cid the worker's map does not know is RE-ARMED when the
        authoritative map knows it (a signal racing cluster registration
        would otherwise be dropped — consumed bit, no retry — and a
        one-shot wakeup like the initial-recovery task is lost forever).
        A cid unknown to the authoritative map (stopped cluster) stays
        dropped."""
        missing = [cid for cid in ready if cid not in nodes]
        if not missing:
            return
        _, all_nodes = self.get_nodes()
        for cid in missing:
            if cid in all_nodes:
                work_ready.cluster_ready(cid)

    # ---- step path (reference stepWorkerMain/processSteps :860-1010) ----

    def _step_worker_main(self, idx: int) -> None:
        import os

        if idx == 0 and os.environ.get("DBTPU_CPROFILE_STEP"):
            # diagnostics: profile one step worker, dump on engine stop
            import cProfile

            self._prof = cProfile.Profile()
            self._prof.enable()
        while not self._stopped.is_set():
            self.step_ready.wait(idx)
            if self._stopped.is_set():
                return
            nodes = self._worker_nodes(
                self._step_cache, idx, self.step_ready.partitioner
            )
            ready = self.step_ready.get_ready(idx)
            self._rearm_unknown(ready, nodes, self.step_ready)
            active = [nodes[cid] for cid in ready if cid in nodes]
            if active:
                try:
                    st = self._step_stats[idx]
                    t0 = _time.perf_counter()
                    # the step batch, named for the profiler while the
                    # tracer is on
                    with (
                        _annotate("raft_step") if self.tracer is not None
                        else _OFF
                    ):
                        stepped, skipped = self.process_steps(
                            active, self._committers[idx]
                        )
                    st[0] += 1
                    st[1] += stepped
                    st[2] += skipped
                    st[3] += _time.perf_counter() - t0
                except Exception:
                    plog.exception("step worker %d failed", idx)

    def process_steps(
        self, active: List["Node"], committer: Optional[_Committer] = None
    ) -> Tuple[int, int]:
        """The hot loop (reference ``processSteps`` ``execengine.go:923``):
        step → send replicates → apply what is already durable → one
        batched fsync → execute → commit.

        An update's committed entries that an EARLIER update saved
        (``Update.fast_apply``, ``raft/peer.py`` ``set_fast_apply``) go to
        the apply queue before this update's persist, as the reference's
        first ``applySnapshotAndUpdate(updates, nodes, true)`` does: the
        persist they would wait for adds nothing to them but the hard
        state's commit index, which a restart rebuilds.  The rest of the
        post-persist half keeps its place.

        Such an update that saves no entry and moves nothing of the hard
        state but ``commit`` (``Node.persists_commit_alone``: a leader's
        commit advance, a follower's from a heartbeat) completes inline
        like a message-only update, and its record goes to the committer
        with no post-fsync half: no message of it acknowledges anything its
        save makes durable, and holding the group ``commit_inflight`` for
        that save made the callers' next proposals wait out the WAL cycle
        the early hand-off had just taken off their acknowledgement.

        The fsync + post-fsync half is pipelined through the worker's
        committer (see :class:`_Committer`); groups whose previous update is
        still being committed are skipped and re-scheduled by the committer,
        so per-group round ordering is untouched.  Message-only updates
        (heartbeats) bypass the committer entirely — nothing to persist, no
        reason to ride behind an fsync.
        """
        pairs = []
        skipped = 0
        for n in active:
            if n.commit_inflight:
                skipped += 1
                continue
            ud = n.step_node()
            if ud is not None:
                pairs.append((n, ud))
        if not pairs:
            return len(pairs), skipped
        for n, ud in pairs:
            n.process_dropped(ud)
            n.send_replicate_messages(ud)  # before fsync (thesis §10.2.1)
        # only updates that can put a record on disk need the committer;
        # the rest complete inline
        persist = []  # wait for their save
        updates = []  # what the save carries
        inline = []
        early = 0
        for n, ud in pairs:
            if (
                ud.entries_to_save
                or not ud.state.is_empty()
                or (ud.snapshot is not None and not ud.snapshot.is_empty())
            ):
                updates.append(ud)
                fast = ud.fast_apply
                if (
                    fast
                    and not ud.entries_to_save
                    and n.persists_commit_alone(ud)
                ):
                    # nothing of it waits for its own save
                    inline.append((n, ud))
                else:
                    persist.append((n, ud))
                    if committer is not None:
                        n.commit_inflight = True
                if fast:
                    early += n.apply_committed(ud)
            else:
                inline.append((n, ud))
        for n, ud in inline:
            n.process_raft_update(ud)
            n.commit_raft_update(ud)
        if updates:
            idx = committer.idx if committer is not None else 0
            self.count_apply_handoffs(idx, early, 0)
            if committer is not None:
                committer.submit(persist, updates)
            else:
                tr = self.tracer
                with (_annotate("wal_sync") if tr is not None else _OFF):
                    self.logdb.save_raft_state(updates)
                if tr is not None:
                    tr.mark_updates(updates, "wal")
                after = 0
                for n, ud in persist:
                    after += n.process_raft_update(ud)
                    n.commit_raft_update(ud)
                self.count_apply_handoffs(idx, 0, after)
        return len(pairs), skipped

    def count_apply_handoffs(self, idx: int, early: int, after: int) -> None:
        """``early`` / ``after`` updates of step worker ``idx`` handed their
        committed entries to the apply queue before / after their persist:
        ``stats()`` always, the tracer's series by the second while it is
        on."""
        if early or after:
            st = self._step_stats[idx]
            st[4] += early
            st[5] += after
            tr = self.tracer
            if tr is not None:
                tr.count_apply_handoffs(early, after)

    def stats(self) -> dict:
        """Diagnostic counters (benchmarks; not part of the public API)."""
        return {
            "step_workers": [
                {
                    "rounds": s[0],
                    "groups_stepped": s[1],
                    "skipped_inflight": s[2],
                    "step_s": round(s[3], 3),
                    "applied_early": s[4],
                    "applied_after_sync": s[5],
                }
                for s in self._step_stats
            ],
            "committers": [
                {
                    "cycles": c.cycles,
                    "merged_updates": c.merged,
                    "sync_batches": c.sync_batches,
                    "commit_s": round(c.commit_s, 3),
                    "post_s": round(c.post_s, 3),
                }
                for c in self._committers
            ],
        }

    # ---- apply path (reference applyWorkerMain/processApplies :794-858) ----

    def _apply_worker_main(self, idx: int) -> None:
        while not self._stopped.is_set():
            self.apply_ready.wait(idx)
            if self._stopped.is_set():
                return
            nodes = self._worker_nodes(
                self._apply_cache, idx, self.apply_ready.partitioner
            )
            ready = self.apply_ready.get_ready(idx)
            self._rearm_unknown(ready, nodes, self.apply_ready)
            # the apply batch, named for the profiler while the tracer
            # is on
            with (
                _annotate("apply") if ready and self.tracer is not None
                else _OFF
            ):
                for cid in ready:
                    n = nodes.get(cid)
                    if n is None:
                        continue
                    try:
                        n.handle_apply_tasks()
                    except Exception:
                        plog.exception(
                            "apply worker %d failed on %d", idx, cid
                        )

    def submit_snapshot(self, fn) -> None:
        """Queue snapshot save/stream work onto the dedicated pool."""
        self._ss_q.put(fn)

    def _snapshot_worker_main(self) -> None:
        while True:
            fn = self._ss_q.get()
            if fn is None or self._stopped.is_set():
                return
            obs = self.replica_obs
            t0 = _time.perf_counter() if obs is not None else 0.0
            try:
                fn()
            except Exception:
                plog.exception("snapshot worker task failed")
            if obs is not None:
                obs.pool_task(t0, _time.perf_counter(), self._ss_q.qsize())

    def stop(self) -> None:
        import os

        if getattr(self, "_prof", None) is not None:
            self._prof.disable()
            path = os.environ.get("DBTPU_CPROFILE_STEP")
            try:
                self._prof.dump_stats(path)
            except Exception:
                pass
            self._prof = None
        self._stopped.set()
        self.notify_all()
        for _ in range(32):  # wake every snapshot worker
            self._ss_q.put(None)
        for c in self._committers:
            c.join()
        for t in self._threads:
            t.join(timeout=2)
