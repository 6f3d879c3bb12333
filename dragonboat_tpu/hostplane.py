"""Compartmentalized host plane: ingress batcher, group-commit WAL,
decoupled apply/egress executors.

The propose path's leaf profile is lock waits plus the
``commit_write_batch`` durability hop: every client proposal takes the
per-group ``entry_q`` lock and a step-ready condition-variable notify,
every persisting step-worker cycle rides its own fsync, and the apply
workers run the client-completion ``Event.set`` storm inline.  Following
"Scaling Replicated State Machines with Compartmentalization" (PAPERS.md),
this module splits the monolithic host path into independently-sharded
stages so host throughput scales with cores instead of being one raftMu
wide:

1. :class:`ProposalIngress` — the paper's proxy/batcher tier.  ``propose``
   / ``propose_batch`` append raw commands to a striped per-shard staging
   ring (one micro-lock, no per-group locks, no engine wakeup) and return
   their futures immediately; per-shard batcher threads drain whole rings
   and stage each group's burst under ONE ``entry_q`` lock acquisition and
   ONE step-ready signal per group per drain.

2. :class:`GroupCommitWAL` — the cross-shard group-commit tier.  Step
   workers' committers submit their write batches to ONE shared flusher
   that merges everything queued — across committers, groups and LogDB
   shards — into a single ``save_raft_state`` call per cycle (one fsync
   per touched shard per cycle instead of one per committer cycle), then
   releases each submitter to run its own post-fsync half concurrently.
   Nothing is acked before its fsync: a submitter only unblocks after the
   merged batch it rode is durable, and a flush failure re-raises in every
   rider (the committer's retry path re-arms the groups).

3. :class:`ApplyPool` / :class:`EgressPool` — decoupled executors.  Apply
   readiness routes to a dedicated pool (sharded by group, so per-group
   task order is untouched) and client-completion ``RequestState.notify``
   calls move off the apply workers onto egress workers, so
   step→replicate→persist never waits behind user SM code or the client
   wakeup storm.

Everything here is OFF by default (``ExpertConfig.host_compartments``);
with the switch off no object in this module is constructed and the
scalar host path is bit-identical to the pre-compartment build.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, TYPE_CHECKING

from .logger import get_logger
from .requests import SystemBusyError
from .settings import Soft
from .wire import Entry, EntryType

if TYPE_CHECKING:
    from .node import Node

plog = get_logger("hostplane")


class _IngressShard:
    """One staging ring + its batcher thread."""

    __slots__ = ("idx", "mu", "cv", "ring", "ncmds", "cap", "thread",
                 "mu_wait_s", "draining")

    def __init__(self, cap: int, idx: int = 0):
        self.idx = idx
        self.mu = threading.Lock()
        self.cv = threading.Condition(self.mu)
        self.ring: list = []
        self.ncmds = 0  # commands staged (the cap's unit — a ring item
        # is a whole burst, so len(ring) alone under-counts backpressure)
        self.cap = cap
        self.thread: Optional[threading.Thread] = None
        self.mu_wait_s = 0.0
        # True from ring swap until the swapped burst is fully staged —
        # singles arriving meanwhile must ring (ordering), not go inline
        self.draining = False


class ProposalIngress:
    """Striped MPSC proposal staging in front of the node runtime.

    ``submit``/``submit_one`` run on client threads: create the futures
    (key + deadline only — registration with the tracker is deferred to
    the batcher, which always runs before the entry can reach the apply
    path, so no completion can miss it), append to the owning shard's
    ring, wake the batcher.  A full ring raises :class:`SystemBusyError`
    exactly like a full ``entry_q`` on the direct path.

    The batcher drains the whole ring in one swap, groups by node, does
    the payload encoding (amortized off the client threads), bulk-registers
    the futures, and stages each group's burst with ONE lock acquisition —
    the native fast lane's ``propose_batch`` when enrolled, else one
    ``entry_q.add_batch`` — and ONE step-ready signal per group.
    """

    def __init__(
        self,
        shards: int = 2,
        ring_cap: int = 0,
        obs=None,
        hostproc=None,
    ):
        self.nshards = max(1, shards)
        cap = ring_cap or Soft.incoming_proposal_queue_length * 4
        self._shards = [
            _IngressShard(cap, idx=i) for i in range(self.nshards)
        ]
        # multi-process encode tier (hostproc, ISSUE 12): one
        # shared-memory encode lane per staging shard — the batcher
        # ships the whole drained burst's payload encode to a worker
        # process and stamps the ``ipc`` trace stage on return.  None
        # (host_workers=0, or a topology where the handoff cannot pay —
        # see HostProcPlane.offload_default) keeps the inline encode
        # bit-identical.
        self._encoders = (
            [hostproc.encode_lane(i) for i in range(self.nshards)]
            if hostproc is not None and hostproc.offload_default
            else None
        )
        self._stopped = False
        self._paused = False  # test hook: hold drains to observe ring caps
        self._obs = obs
        self.submitted = 0  # commands accepted into rings (GIL-counted)
        self.drains = 0
        self.drained = 0  # commands drained (batch size = drained/drains)
        for i, sh in enumerate(self._shards):
            t = threading.Thread(
                target=self._batcher_main, args=(sh,),
                name=f"ingress-batcher-{i}", daemon=True,
            )
            sh.thread = t
            t.start()

    # ---- client side ----

    def submit_one(self, node: "Node", session, cmd: bytes, timeout_s: float):
        return self.submit(node, session, (cmd,), timeout_s)[0]

    def submit_single_if_active(
        self, node: "Node", session, cmd: bytes, timeout_s: float
    ):
        """Adaptive single-proposal routing: ring the command only when
        the owning shard already has staged or draining work (the burst
        keeps it active, and ring order puts this proposal behind it);
        return None on a quiet shard so the caller stages inline with no
        thread handoff.  Caveat (documented in the differential suite):
        a thread that interleaves an UN-awaited ``propose_batch`` with a
        bare ``propose`` on the same group may see the two stage in
        either order — the same guarantee two independent clients get."""
        sh = self._shards[node.cluster_id % self.nshards]
        if not sh.ring and not sh.draining:
            return None
        return self.submit(node, session, (cmd,), timeout_s)[0]

    def submit(
        self, node: "Node", session, cmds, timeout_s: float
    ) -> list:
        """Stage a burst for ``node`` and return one future per command.

        The witness/payload precheck happened in the caller (``Node``
        keeps it synchronous so ``PayloadTooBigError`` /
        ``InvalidOperationError`` semantics match the direct path)."""
        pp = node.pending_proposals
        deadline = pp._clock.tick + node._timeout_ticks(timeout_s)
        from .requests import RequestState

        tr = node.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        states = []
        client_id, series_id = session.client_id, session.series_id
        responded_to = session.responded_to
        bits = pp._rng.getrandbits
        for _ in cmds:
            rs = RequestState(key=bits(64) or 1, deadline=deadline)
            rs.client_id = client_id
            rs.series_id = series_id
            states.append(rs)
        if tr is not None:
            # contexts attach BEFORE the ring append, so the ingress
            # stage measures the ring wait + batcher drain
            tr.attach_all(states, node.cluster_id, t0)
        sh = self._shards[node.cluster_id % self.nshards]
        with sh.mu:
            # cap is in COMMANDS; an oversized burst on an otherwise
            # empty ring is accepted (the direct path would accept it
            # too and let entry_q truncate the tail to DROPPED futures)
            if self._stopped or (
                sh.ncmds and sh.ncmds + len(cmds) > sh.cap
            ):
                if tr is not None:
                    # the rejected futures never reach a tracker, so no
                    # notify will ever finish their contexts — drop them
                    # from the in-flight index or they leak to the
                    # stall watchdog
                    tr.discard(states)
                raise SystemBusyError()
            sh.ring.append(
                (node, states, cmds, client_id, series_id, responded_to)
            )
            sh.ncmds += len(cmds)
            sh.cv.notify()
        self.submitted += len(cmds)
        obs = self._obs
        if obs is not None:
            obs.ingress_submit(len(cmds))
        return states

    # ---- batcher side ----

    def _batcher_main(self, sh: _IngressShard) -> None:
        while True:
            with sh.mu:
                while (not sh.ring or self._paused) and not self._stopped:
                    sh.cv.wait(0.2)
                if self._stopped and not sh.ring:
                    return
                if self._paused and not self._stopped:
                    continue
                burst, sh.ring = sh.ring, []
                sh.ncmds = 0
                sh.draining = True
            try:
                self._drain(burst, sh.idx)
            except Exception:
                plog.exception("ingress batcher drain failed")
                # resolve every future the failed drain may have
                # stranded: dropped() covers registered keys; a future
                # the failure preceded registration for is invisible to
                # the tracker (and its timeout GC) and must be notified
                # directly or the client blocks for its full timeout
                from .requests import RequestResult, RequestResultCode

                for node, states, *_ in burst:
                    for rs in states:
                        if not rs.done():
                            node.pending_proposals.dropped(rs.key)
                        if not rs.done():
                            rs.notify(
                                RequestResult(
                                    code=RequestResultCode.DROPPED
                                )
                            )
            finally:
                sh.draining = False

    def _drain(self, burst: list, shard_idx: int = 0) -> None:
        t0 = time.perf_counter() if self._obs is not None else 0.0
        by_node: Dict[int, list] = {}
        nodes: Dict[int, "Node"] = {}
        for item in burst:
            node = item[0]
            by_node.setdefault(node.cluster_id, []).append(item)
            nodes[node.cluster_id] = node
        n_cmds = 0
        for cid, items in by_node.items():
            n_cmds += self._stage_node(nodes[cid], items, shard_idx)
        self.drains += 1
        self.drained += n_cmds
        obs = self._obs
        if obs is not None:
            obs.ingress_drain(
                groups=len(by_node), cmds=n_cmds,
                wall_ms=(time.perf_counter() - t0) * 1e3,
                ring_depth=sum(len(s.ring) for s in self._shards),
            )

    def _stage_node(self, node: "Node", items: list,
                    shard_idx: int = 0) -> int:
        """Encode + register + stage one group's burst.  Returns the
        number of commands staged.  Ordering: ring order is preserved
        (one group always maps to one shard, so a client's back-to-back
        proposals stay ordered exactly like the direct path)."""
        from .rsm.encoded import get_encoded_payload

        pp = node.pending_proposals
        ct = node._entry_ct
        tr = node.tracer
        # hostproc encode tier: ship the burst's non-empty payloads to
        # the shard's worker lane in ONE round trip; a None return
        # (worker gone / ring busy) falls back to the inline encode —
        # same bytes, just on this thread.  ``ipc`` stamps the handoff
        # (ring enqueue -> worker dequeue -> encoded burst returned).
        enc_iter = None
        if self._encoders is not None:
            raw = [
                cmd
                for _n, _s, cmds, *_ in items
                for cmd in cmds
                if cmd
            ]
            if raw:
                encs = self._encoders[shard_idx].encode(int(ct), raw)
                if encs is not None:
                    enc_iter = iter(encs)
                    if tr is not None:
                        # only states whose command actually rode the
                        # encode worker — empty commands stage inline
                        # and must not inherit a handoff interval in
                        # the attribution table
                        for _n, states, cmds, *_ in items:
                            for rs, cmd in zip(states, cmds):
                                if cmd:
                                    tr.mark(rs, "ipc")
        entries: List[Entry] = []
        all_states: list = []
        runs: list = []  # (client_id, series_id, responded_to, start, end)
        for _node, states, cmds, client_id, series_id, responded_to in items:
            start = len(entries)
            for rs, cmd in zip(states, cmds):
                if cmd:
                    enc = (
                        next(enc_iter) if enc_iter is not None
                        else get_encoded_payload(ct, cmd)
                    )
                    etype = EntryType.ENCODED
                else:
                    enc = cmd
                    etype = EntryType.APPLICATION
                e = Entry(
                    key=rs.key, client_id=client_id, series_id=series_id,
                    cmd=enc,
                )
                e.type = etype
                e.responded_to = responded_to
                entries.append(e)
            all_states.extend(states)
            runs.append(
                (client_id, series_id, responded_to, start, len(entries))
            )
        if not entries:
            return 0
        # register BEFORE staging: completion (apply path) can only run
        # after the entry is staged, so registration is always visible
        # by the time ``applied`` looks the key up
        pp.register_batch(all_states)
        if node._stopped.is_set():
            for rs in all_states:
                pp.dropped(rs.key)
            return len(entries)
        staged_native = 0
        fl = node.fastlane
        if node.fast_lane and fl is not None:
            # per-session contiguous runs ride the native batch append
            # (indices assigned under one C++ lock); the first run the
            # native core refuses falls the remainder back to the scalar
            # queue so cross-run ordering is preserved
            for client_id, series_id, responded_to, start, end in runs:
                chunk = entries[start:end]
                etypes = {e.type for e in chunk}
                if len(etypes) == 1 and fl.nat.propose_batch(
                    node.cluster_id,
                    [e.key for e in chunk],
                    client_id, series_id, responded_to,
                    int(chunk[0].type),
                    _pack_blob(chunk),
                ):
                    staged_native = end
                    continue
                break
        rest = entries[staged_native:]
        if rest:
            accepted = node.entry_q.add_batch(rest)
            for e in rest[accepted:]:
                # queue full mid-burst: resolve like the direct
                # ``propose_batch`` (DROPPED futures, clients retry)
                pp.dropped(e.key)
        node.nh.engine.set_step_ready(node.cluster_id)
        if tr is not None:
            for rs in all_states:
                tr.mark(rs, "ingress")
        return len(entries)

    # ---- lifecycle / test hooks ----

    def pause(self) -> None:
        """Hold all batchers (tests: observe ring backpressure)."""
        self._paused = True

    def resume(self) -> None:
        self._paused = False
        for sh in self._shards:
            with sh.mu:
                sh.cv.notify()

    def stop(self) -> None:
        self._stopped = True
        for sh in self._shards:
            with sh.mu:
                sh.cv.notify()
        for sh in self._shards:
            if sh.thread is not None:
                sh.thread.join(timeout=2)

    def stats(self) -> dict:
        return {
            "shards": self.nshards,
            "submitted": self.submitted,
            "drains": self.drains,
            "drained": self.drained,
            "batch_avg": round(self.drained / self.drains, 2)
            if self.drains else 0.0,
        }


def _pack_blob(entries: List[Entry]) -> bytes:
    """Length-prefixed payload blob for the native batch append.  The
    header packer is cached per length — a pipelined burst is usually one
    payload size repeated, and ``struct.pack`` per entry was a measured
    term in the propose profile (ISSUE 8 satellite)."""
    from .node import _pack_len

    return b"".join(_pack_len(len(e.cmd)) + e.cmd for e in entries)


class GroupCommitWAL:
    """Cross-shard group commit: one fsync amortized across every step
    worker's write batches per flush cycle.

    Leader-based protocol (no dedicated flusher thread — a thread handoff
    on a saturated single-core box costs a scheduling quantum per hop,
    which is exactly the tax this tier exists to remove): the first
    committer to arrive while no flush is running becomes the LEADER,
    takes everything queued (its own submission plus every concurrent
    committer's), and persists the merged batch on its own thread; later
    arrivals become RIDERS and sleep until the leader completes them.
    Uncontended, a committer flushes inline with zero handoffs; under
    concurrency, one leader's single fsync covers all riders.

    The persist itself is ``save_raft_state_journaled`` when the LogDB
    supports the host journal (one journal fsync for ALL shards' batches
    — see ``logdb/journal.py``), else the classic per-shard fsynced save
    (still merged across committers).

    Per-group ordering is untouched: a group only ever rides its owning
    committer, which blocks here until the batch carrying it lands.
    Failure re-raises into EVERY participant of the failed cycle; the
    committer's exception path clears ``commit_inflight`` and re-arms the
    groups, so the updates are re-emitted and retried.  Nothing is acked
    before its fsync — leader and riders return strictly after the
    journal (or per-shard) fsync.
    """

    #: flush cycles between shard-store checkpoints (the journal's
    #: truncation cadence; each checkpoint costs one fsync per shard)
    CHECKPOINT_EVERY = 256

    def __init__(self, logdb, window_ms: float = 0.0, obs=None, fs=None,
                 journal_mode: str = "auto", hostproc=None):
        self.logdb = logdb
        self.window_s = max(0.0, window_ms) / 1e3
        self._cv = threading.Condition()
        self._q: list = []  # (updates, slot=[done, error])
        self._flushing = False
        self._stopped = False
        self._obs = obs
        self._fs = fs
        self.flushes = 0
        self.submissions = 0
        self.updates_flushed = 0
        # journal strategy (ExpertConfig.host_wal_journal): "auto" lets
        # the device probe below pick; "force" always journals (the
        # probe only paces the window); "off" never arms the journal
        self._mode = (
            journal_mode if journal_mode in ("auto", "force", "off")
            else "auto"
        )
        # cross-shard journal: when the LogDB supports it (durable
        # sharded backend), every flush cycle is ONE journal fsync for
        # ALL shards' batches; otherwise fall back to the per-shard
        # fsynced save (still merged across committers)
        self._journal = None
        enable = getattr(logdb, "enable_host_journal", None)
        if enable is not None and self._mode != "off":
            try:
                self._journal = enable(fs=fs)
            except OSError:
                plog.exception("host journal unavailable; per-shard fsync")
        self._since_checkpoint = 0
        self._single_streak = 0
        self._probes = 0
        # startup device probe (the box is quiet, so the measurement is
        # as GIL-clean as it gets — runtime persist walls are polluted
        # by GIL-reacquisition waits and cannot attribute device cost):
        # a slow durability device (ms-class barrier) engages the
        # cross-file journal and a short accumulation window, both of
        # which pay for themselves many times over there; a fast device
        # (sub-ms) keeps the classic per-shard fsynced save — merged
        # across committers by the leader protocol, but with zero extra
        # encode/write work.  The probe keeps the MIN over its samples:
        # GIL pollution only ever INFLATES a sample, so the min is the
        # robust device-cost estimator (a polluted mean could pin the
        # journal on a fast disk for the process lifetime).
        # ``journal.bytes > 0`` still forces the journaled path
        # regardless (replay-regression correctness rule, see
        # ShardedDB.save_raft_state_journaled).
        self._device_probe_s = self._probe_device(fs)
        if self._mode == "force" and self._journal is not None:
            # forced strategy (ISSUE 12 satellite): the probe no longer
            # picks the strategy, only the pacing window — RE-probe so
            # one polluted startup sample can't pin the window either
            self.reprobe()
            self._journal_engaged = True
        else:
            self._journal_engaged = (
                self._journal is not None
                and self._device_probe_s >= 0.0005
            )
        # WAL-worker sink (hostproc, ISSUE 12): the journal's
        # append+fsync cycle runs in a worker process; raw-OS path only
        # (a fault-injection vfs cannot cross the process boundary, and
        # must keep reaching the in-process durability point).  Gated
        # like the journal itself, by measurement: the cross-process
        # round trip costs ~1-2 scheduling quanta, so it pays only when
        # spare cores can hide it (hostproc.offload_default) or the
        # durability barrier dwarfs it — a sub-ms fsync on a single-core
        # box measured ~8x SLOWER through the worker.
        if (
            hostproc is not None and self._journal is not None
            and fs is None
            and (
                hostproc.offload_default
                or self._device_probe_s >= 0.0005
            )
        ):
            try:
                self._journal.sink = hostproc.wal_sink()
            except Exception:
                plog.exception("hostproc WAL sink unavailable")

    def _probe_device(self, fs, samples: int = 3) -> float:
        if self._journal is None:
            return 0.0
        import os as _os

        path = self._journal.path + ".probe"
        try:
            f = open(path, "ab") if fs is None else fs.open(path, "ab")
            try:
                cost = None
                for _ in range(samples):
                    t0 = time.perf_counter()
                    f.write(b"p")
                    f.flush()
                    if fs is None:
                        _os.fsync(f.fileno())
                    else:
                        fs.fsync(f)
                    dt = time.perf_counter() - t0
                    cost = dt if cost is None else min(cost, dt)
            finally:
                f.close()
                try:
                    (_os.unlink if fs is None else fs.remove)(path)
                except OSError:
                    pass
            self._probes += 1
            return cost or 0.0
        except OSError:
            return 0.0

    def reprobe(self) -> float:
        """Refresh the device probe (min-of-samples) and re-derive the
        strategy: mode "auto" re-decides engagement, mode "force" only
        re-paces the accumulation window.  Construction calls this for
        forced mode; tests/operators may call it whenever the device
        characteristics changed."""
        p = self._probe_device(self._fs, samples=5)
        self._device_probe_s = p
        if self._mode == "auto":
            self._journal_engaged = (
                self._journal is not None and p >= 0.0005
            )
        return p

    def status(self) -> dict:
        """Introspection (the ``lease_status`` pattern): which strategy
        the probe chose, what it measured, and where durability happens
        (worker sink vs in-process)."""
        j = self._journal
        snk = getattr(j, "sink", None) if j is not None else None
        return {
            "mode": self._mode,
            "engaged": self._journal_engaged,
            "probe_ms": round(self._device_probe_s * 1e3, 4),
            "probes": self._probes,
            "window_ms": round(self._adaptive_window_s() * 1e3, 4),
            "journal": j is not None,
            "journal_bytes": j.bytes if j is not None else 0,
            "journal_fsyncs": j.fsyncs if j is not None else 0,
            "worker_sink": bool(
                snk is not None and getattr(snk, "attached", False)
            ),
            "flushes": self.flushes,
            "amortization": round(self.amortization, 2),
        }

    def _adaptive_window_s(self) -> float:
        if self.window_s:
            return self.window_s
        if not self._journal_engaged:
            return 0.0
        # pace by half the device barrier cost, capped single-digit ms
        return min(self._device_probe_s / 2.0, 0.004)

    def flush(self, updates: list) -> None:
        """Persist ``updates`` (blocking until fsynced).  Raises whatever
        the merged persist raised."""
        if not self._journal_engaged and (
            self._journal is None or not self._journal.nonempty()
        ):
            # fast durability device: merging saves under one leader
            # measured as a net LOSS there (serializing sub-ms barriers
            # that would otherwise overlap across committers, while the
            # merge amortizes nothing) — take the classic concurrent
            # per-committer save, which is the uncompartmented path
            # exactly.  The leader protocol below engages only where the
            # device probe says barriers are worth amortizing.
            self.flushes += 1
            self.submissions += 1
            self.updates_flushed += len(updates)
            self.logdb.save_raft_state(updates)
            return
        slot = [False, None]
        with self._cv:
            if self._stopped:
                raise RuntimeError("group-commit WAL stopped")
            self._q.append((updates, slot))
            while True:
                if slot[0]:
                    # a leader completed us (rider path)
                    if slot[1] is not None:
                        raise slot[1]
                    return
                if not self._flushing:
                    self._flushing = True
                    break  # leadership: persist the queue ourselves
                self._cv.wait(0.2)
                if self._stopped and not slot[0]:
                    raise RuntimeError("group-commit WAL stopped")
            window = self._adaptive_window_s()
            if window:
                # accumulation window: trade up to this much commit
                # latency for deeper merge — worth it exactly when the
                # device barrier is the bottleneck (see
                # _adaptive_window_s; an explicit window_ms pins it)
                self._cv.wait(window)
            batch, self._q = self._q, []
        err = self._persist(batch)
        with self._cv:
            self._flushing = False
            for _, s in batch:
                s[0] = True
                s[1] = err
            self._cv.notify_all()
        if err is not None:
            raise err

    def _persist(self, batch: list) -> Optional[BaseException]:
        """Leader half, OUTSIDE the lock: one merged save (+fsync)."""
        merged = [ud for updates, _ in batch for ud in updates]
        t0 = time.perf_counter()
        err: Optional[BaseException] = None
        try:
            if merged:
                if self._journal is not None and (
                    self._journal_engaged or self._journal.nonempty()
                ):
                    if self.logdb.save_raft_state_journaled(merged):
                        self._since_checkpoint += 1
                        if len(batch) <= 1:
                            self._single_streak += 1
                        else:
                            self._single_streak = 0
                        # checkpoint on cadence, or when load has fallen
                        # back to single-rider cycles (drain the journal
                        # so quiet-period cycles return to the classic
                        # direct path — see save_raft_state_journaled's
                        # journal-empty rule)
                        if self._since_checkpoint >= self.CHECKPOINT_EVERY or (
                            self._single_streak >= 4
                        ):
                            self._since_checkpoint = 0
                            self._single_streak = 0
                            self.logdb.journal_checkpoint()
                else:
                    self.logdb.save_raft_state(merged)
        except Exception as e:  # noqa: BLE001 — re-raised in participants
            err = e
            plog.exception("group-commit flush cycle failed")
        self.flushes += 1
        self.submissions += len(batch)
        self.updates_flushed += len(merged)
        obs = self._obs
        if obs is not None:
            obs.wal_flush(
                riders=len(batch), updates=len(merged),
                wall_ms=(time.perf_counter() - t0) * 1e3,
                amortization=self.amortization,
            )
        return err

    @property
    def amortization(self) -> float:
        """Committer submissions per fsync cycle (>1 = amortizing)."""
        return self.submissions / self.flushes if self.flushes else 0.0

    def stop(self) -> None:
        # no thread to join — just refuse new work and wake any riders
        # whose leader died with them (their error marks the shutdown)
        with self._cv:
            self._stopped = True
            batch, self._q = self._q, []
            for _, slot in batch:
                if not slot[0]:
                    slot[0] = True
                    slot[1] = RuntimeError("group-commit WAL stopped")
            self._cv.notify_all()

    def stats(self) -> dict:
        return {
            "flushes": self.flushes,
            "submissions": self.submissions,
            "updates": self.updates_flushed,
            "amortization": round(self.amortization, 2),
        }


class ApplyPool:
    """Dedicated apply executors (sharded by group id so one group's task
    batches stay on one worker — ``Node.handle_apply_tasks`` additionally
    serializes against the fast lane's inline pump)."""

    def __init__(self, get_node: Callable[[int], Optional["Node"]],
                 workers: int = 2, obs=None):
        self.get_node = get_node
        self.count = max(1, workers)
        self._cvs = [threading.Condition() for _ in range(self.count)]
        self._ready: List[set] = [set() for _ in range(self.count)]
        self._stopped = False
        self._obs = obs
        self.batches = 0
        self._threads = []
        for i in range(self.count):
            t = threading.Thread(
                target=self._main, args=(i,),
                name=f"host-apply-{i}", daemon=True,
            )
            t.start()
            self._threads.append(t)

    def submit(self, cluster_id: int) -> None:
        idx = cluster_id % self.count
        cv = self._cvs[idx]
        with cv:
            self._ready[idx].add(cluster_id)
            cv.notify()

    def depth(self) -> int:
        """Groups queued for apply across all executors (health sample;
        GIL-atomic len reads — gauge-grade, no locks taken)."""
        return sum(len(s) for s in self._ready)

    def _main(self, idx: int) -> None:
        cv = self._cvs[idx]
        while True:
            with cv:
                while not self._ready[idx] and not self._stopped:
                    cv.wait(0.2)
                if self._stopped:
                    return
                ready, self._ready[idx] = self._ready[idx], set()
            for cid in ready:
                # get_node reads the AUTHORITATIVE live cluster dict (the
                # node is stored before any signal fires, nodehost's
                # start contract), so None here means stopped/removed —
                # unlike the engine's cached worker maps, there is no
                # stale-map window needing a _rearm_unknown defense
                node = self.get_node(cid)
                if node is None:
                    continue
                try:
                    node.handle_apply_tasks()
                except Exception:
                    plog.exception("host apply worker failed on %d", cid)
            self.batches += 1
            obs = self._obs
            if obs is not None:
                obs.apply_batch(groups=len(ready))

    def stop(self) -> None:
        self._stopped = True
        for cv in self._cvs:
            with cv:
                cv.notify()
        for t in self._threads:
            t.join(timeout=2)


class EgressPool:
    """Client-completion executors: ``RequestState.notify`` (the
    ``Event.set`` that wakes a client thread) moves off the apply workers
    onto these, batched per wakeup.  Sharded by request key so a single
    future is only ever notified from one worker; per-shard FIFO keeps
    completion order stable for one group's stream (group → committer →
    apply worker → same-key shard)."""

    #: two completions closer together than this are a storm — the
    #: second and later ones batch onto the worker (adaptive: an idle
    #: plane keeps the off-mode single-hop latency; a bursty one moves
    #: the client-wakeup storm off the apply worker)
    BURST_S = 0.0005

    def __init__(self, workers: int = 1, obs=None):
        self.count = max(1, workers)
        self._cvs = [threading.Condition() for _ in range(self.count)]
        self._qs: List[list] = [[] for _ in range(self.count)]
        self._busy = [False] * self.count
        self._stopped = False
        self._obs = obs
        self.notified = 0
        self.inline = 0
        self._last_notify = 0.0
        self._streak = 0
        self._threads = []
        for i in range(self.count):
            t = threading.Thread(
                target=self._main, args=(i,),
                name=f"host-egress-{i}", daemon=True,
            )
            t.start()
            self._threads.append(t)

    def __call__(self, rs, result) -> None:
        """The sink ``PendingProposal``/``PendingReadIndex`` call in place
        of ``rs.notify(result)``.  Adaptive: a quiet shard notifies
        inline (no handoff — the off-mode latency); once completions
        queue faster than the worker drains them, the storm batches onto
        the worker thread."""
        now = time.perf_counter()
        if now - self._last_notify < self.BURST_S:
            self._streak += 1
        else:
            self._streak = 0
        self._last_notify = now
        idx = rs.key % self.count
        cv = self._cvs[idx]
        with cv:
            # a SUSTAINED storm (3+ back-to-back completions) or an
            # already-engaged worker routes to the pool; occasional close
            # pairs stay inline — a lone handoff costs a scheduling
            # quantum and amortizes nothing
            if not self._stopped and (
                self._streak >= 2 or self._busy[idx] or self._qs[idx]
            ):
                self._qs[idx].append((rs, result))
                cv.notify()
                return
        self.inline += 1
        rs.notify(result)

    def depth(self) -> int:
        """Completions queued for delivery (health sample; gauge-grade
        GIL-atomic reads)."""
        return sum(len(q) for q in self._qs)

    def _main(self, idx: int) -> None:
        cv = self._cvs[idx]
        while True:
            with cv:
                self._busy[idx] = False
                while not self._qs[idx] and not self._stopped:
                    cv.wait(0.2)
                if self._stopped and not self._qs[idx]:
                    return
                batch, self._qs[idx] = self._qs[idx], []
                self._busy[idx] = True
            for rs, result in batch:
                try:
                    rs.notify(result)
                except Exception:
                    plog.exception("egress notify failed")
            self.notified += len(batch)
            obs = self._obs
            if obs is not None:
                obs.egress_batch(len(batch))

    def stop(self) -> None:
        self._stopped = True
        for cv in self._cvs:
            with cv:
                cv.notify()
        for t in self._threads:
            t.join(timeout=2)


class HostPlane:
    """The three tiers plus their wiring surface (built by NodeHost when
    ``ExpertConfig.host_compartments`` is on)."""

    def __init__(
        self,
        logdb,
        get_node: Callable[[int], Optional["Node"]],
        ingress_shards: int = 0,
        ingress_ring: int = 0,
        wal_window_ms: float = 0.0,
        apply_workers: int = 0,
        egress_workers: int = 0,
        fs=None,
        hostproc=None,
        wal_journal_mode: str = "auto",
    ):
        self._obs = None
        self.hostproc = hostproc
        self.ingress = ProposalIngress(
            shards=ingress_shards or 2, ring_cap=ingress_ring,
            hostproc=hostproc,
        )
        self.wal = GroupCommitWAL(
            logdb, window_ms=wal_window_ms, fs=fs,
            journal_mode=wal_journal_mode, hostproc=hostproc,
        )
        # default matches the engine's apply-worker count: fewer dedicated
        # executors than the engine pool they replace measured ~5% off on
        # the many-session axis (apply batches queued behind each other)
        self.apply_pool = ApplyPool(get_node, workers=apply_workers or 4)
        self.egress = EgressPool(workers=egress_workers or 1)
        self.logdb = logdb

    def enable_obs(self, registry=None, recorder=None):
        """Attach the ``dragonboat_host_*`` instruments (same
        ``is not None`` latch contract as the device plane: obs-off keeps
        every tier's hot path bit-identical)."""
        from .obs.instruments import HostObs

        if self._obs is None or registry is not None or recorder is not None:
            self._obs = HostObs(recorder=recorder, registry=registry)
            self.ingress._obs = self._obs
            self.wal._obs = self._obs
            self.apply_pool._obs = self._obs
            self.egress._obs = self._obs
        return self._obs

    def wake_nodes(self, nodes) -> None:
        """Coalesced step-ready fan-out for the device-plane coordinator:
        one signal per touched group per round instead of one per offload
        effect (the coordinator feeds the same ingress tier's wakeup
        discipline)."""
        for n in nodes:
            n.nh.engine.set_step_ready(n.cluster_id)

    def fsync_count(self) -> int:
        fn = getattr(self.logdb, "fsync_count", None)
        return fn() if fn is not None else 0

    def health_snapshot(self) -> dict:
        """Host-plane depths for the cluster health sampler (ISSUE 13):
        per-shard staging-ring occupancy, the WAL strategy/window, and
        the apply/egress queue depths — gauge-grade unlocked reads (the
        sampler must never queue behind a drain or a flush)."""
        ing = self.ingress
        shards = [
            {"ringed": sh.ncmds, "cap": sh.cap} for sh in ing._shards
        ]
        w = self.wal.status()
        return {
            "ingress": {
                "shards": shards,
                "ringed": sum(s["ringed"] for s in shards),
                "submitted": ing.submitted,
                "drains": ing.drains,
            },
            "wal": {
                "mode": w["mode"],
                "engaged": w["engaged"],
                "window_ms": w["window_ms"],
                "flushes": w["flushes"],
                "amortization": w["amortization"],
                "worker_sink": w["worker_sink"],
            },
            "apply_depth": self.apply_pool.depth(),
            "egress_depth": self.egress.depth(),
        }

    def stats(self) -> dict:
        out = {
            "ingress": self.ingress.stats(),
            "wal": self.wal.stats(),
            "wal_status": self.wal.status(),
            "apply_batches": self.apply_pool.batches,
            "egress_notified": self.egress.notified,
            "egress_inline": self.egress.inline,
            "fsyncs": self.fsync_count(),
        }
        if self.hostproc is not None:
            out["hostproc"] = self.hostproc.stats()
        return out

    def stop(self) -> None:
        self.ingress.stop()
        self.apply_pool.stop()
        self.egress.stop()
        self.wal.stop()
