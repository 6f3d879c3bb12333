"""Per-replica node runtime: binds Peer + RSM + snapshotter + queues.

Reference: ``node.go`` — ``stepNode`` pulls queued inputs into raft,
``processRaftUpdate``/``commitRaftUpdate`` execute the resulting ``Update``
(messages out before fsync, entries to LogDB, committed entries to the apply
queue), snapshot task lifecycle, log compaction, tick handling and the
``rsm.INode`` callbacks completing pending requests.
"""
from __future__ import annotations

import struct as _struct
import threading
import time
from functools import lru_cache
from typing import Dict, List, Optional

from .client import Session
from .config import Config
from .events import SystemEvent, SystemEventType
from .logdb import LogReader
from .logger import get_logger
from .obs.recorder import OFF as _OFF
from .queue import EntryQueue
from .quiesce import QuiesceManager
from .requests import (
    ClusterClosedError,
    InvalidOperationError,
    PayloadTooBigError,
    PendingConfigChange,
    PendingLeaderTransfer,
    PendingProposal,
    PendingReadIndex,
    PendingSnapshot,
    RequestResult,
    RequestResultCode,
    RequestState,
    SystemBusyError,
)
from .rsm import (
    MembershipState,
    SSReqType,
    SSRequest,
    StateMachine,
    Task,
    TaskQueue,
)
from .rsm.encoded import get_encoded_payload, to_dio_compression_type
from .rsm.statemachine import SnapshotIgnored
from .raft.peer import Peer, PeerAddress
from .server.message import MessageQueue
from .settings import Soft
from .snapshotter import Snapshotter
from .statemachine import Result
from .wire import (
    ConfigChange,
    ConfigChangeType,
    Entry,
    EntryType,
    Membership,
    Message,
    MessageType,
    Snapshot,
    State,
    SystemCtx,
    Update,
    is_empty_snapshot,
)

plog = get_logger("node")
MT = MessageType

# length-prefix header packer for native batch appends, cached per length:
# a pipelined burst is almost always one payload size repeated, and the
# per-entry ``struct.pack`` (plus the in-function ``import struct``) was a
# measured leaf of the propose path
_pack_len = lru_cache(maxsize=1024)(_struct.Struct("<I").pack)
# wire types the native fast lane serves (natraft.cpp handle_fast)
_FAST_WIRE_TYPES = frozenset(
    (MT.REPLICATE, MT.REPLICATE_RESP, MT.HEARTBEAT, MT.HEARTBEAT_RESP,
     MT.READ_INDEX, MT.READ_INDEX_RESP)
)


class Node:
    """Reference ``node.go:58`` ``node``."""

    def __init__(
        self,
        nh,  # NodeHost (duck-typed: send_message, send_snapshot_message, engine)
        config: Config,
        logdb,
        logreader: LogReader,
        snapshotter: Snapshotter,
        sm: StateMachine,
        tick_millisecond: int,
    ):
        self.nh = nh
        self.config = config
        self.cluster_id = config.cluster_id
        self.node_id = config.node_id
        self.logdb = logdb
        self.logreader = logreader
        self.snapshotter = snapshotter
        self.sm = sm
        self.tick_millisecond = tick_millisecond
        self._entry_ct = to_dio_compression_type(config.entry_compression)
        self.raft_mu = threading.RLock()
        self.peer: Optional[Peer] = None
        # input queues
        self.entry_q = EntryQueue(Soft.incoming_proposal_queue_length)
        self.mq = MessageQueue(Soft.received_message_queue_length)
        # pending request trackers
        self.pending_proposals = PendingProposal()
        self.pending_reads = PendingReadIndex()
        self.pending_config_change = PendingConfigChange()
        self.pending_snapshot = PendingSnapshot()
        self.pending_leader_transfer = PendingLeaderTransfer()
        # apply pipeline
        self.to_apply = TaskQueue()
        # highest entry index handed to ``to_apply``: an update's committed
        # entries may be handed over BEFORE its persist (``apply_committed``),
        # and an update whose persist failed is produced again by the next
        # ``get_update`` with the same committed entries
        self._applied_handed = 0
        self.quiesce_mgr = QuiesceManager(
            self.cluster_id, self.node_id, config.election_rtt, config.quiesce
        )
        # a group's sleep on the device tick plane (``quorum_engine="tpu"``,
        # set at registration where the coordinator ticks on one device):
        # the row's idle clock is the tick kernel's and ``quiesce_mgr`` is
        # off; what the host keeps is whether this replica sleeps (the
        # rule "a heartbeat is no activity while awake" needs it) and the
        # host tick it went to sleep at.  Both under raftMu.
        self.dev_quiesce = False
        self._asleep = False
        self._asleep_tick = 0
        self._active_tick = -(1 << 30)  # host tick of the last activity
        self._stopped = threading.Event()
        self._initialized = threading.Event()
        self.current_tick = 0
        # lazy tick delivery: nodes whose raft clock is NOT host-driven
        # (native fast lane, device tick kernel) skip the per-tick wakeup
        # from the tick worker and catch up on elapsed ticks — read from
        # the NodeHost's global tick counter — at their next step.  This
        # turns the tick worker's per-RTT Python cost from O(groups) into
        # O(scalar-clocked groups), the scaling axis the reference covers
        # with quiesce (quiesce.go) and the device engine covers with one
        # fused tick dispatch for the whole mass.
        self._seen_tick = nh.tick_count
        # True while this group's latest update sits in the engine's commit
        # pipeline; the step worker skips the group until the committer
        # clears it (per-group round ordering, see engine._Committer)
        self.commit_inflight = False
        # an Update has left ``step_node`` and is not committed yet (set
        # and cleared under raftMu): until then the raft state belongs to
        # it — the outbox it shares, the entries it saves — and the
        # batched heartbeat plane's legs leave the group alone
        self._update_out = False
        # native replication fast lane (fastlane.py / native/natraft.cpp):
        # while fast_lane is True the Python raft object is frozen and the
        # native core owns the group's steady-state data plane
        self.fastlane = None  # FastLaneManager, set by NodeHost
        self.fast_lane = False
        # compartmentalized host plane (hostplane.py, set by NodeHost when
        # ExpertConfig.host_compartments is on): propose/propose_batch
        # stage through the striped ingress batcher instead of taking the
        # entry_q lock + step wakeup per call.  None keeps the direct path
        # bit-identical.
        self.ingress = None
        # cross-plane request tracer (obs/trace.py, ISSUE 9; set by
        # NodeHost when NodeHostConfig.trace_sample_every > 0): propose/
        # read allocate a sampled trace context on the future and the
        # pipeline stages stamp it as the request passes.  None (default)
        # keeps every request path bit-identical.
        self.tracer = None
        # replication attribution plane (obs/replattr.py, ISSUE 14; set
        # by NodeHost alongside the tracer): sampled proposals' REPLICATE
        # fan-outs carry a ReplTrace context and the leader decomposes
        # each commit's quorum close per peer.  None (default) keeps the
        # send/ack paths bit-identical.
        self.replattr = None
        # device-engine effect flags (written by the coordinator round
        # thread, max-merged/idempotent, applied under raftMu by
        # _apply_offload_effects on a step worker).  _off_mu guards the
        # writer-vs-swap-and-clear race: without it a flag written between
        # the consumer's load and its clearing store is silently lost, and
        # the engine's edge-triggered commit reporting never resends it.
        # _off_pending is the one fact step_node's gate reads: every
        # offload_* sets it under _off_mu beside its own field, and only
        # the swap in _apply_offload_effects clears it, so an effect
        # flagged while a turn applies the last ones opens the next turn.
        self._off_mu = threading.Lock()
        self._off_pending = False
        self._off_commit = 0
        self._off_election = None
        self._off_hb = False
        self._off_elect = False
        self._off_demote = False
        self._off_quiesce = False
        # device read plane: quorum-confirmed ReadIndex ctxs awaiting the
        # scalar prefix release, and fallback echoes for ctxs the device
        # is not tracking (slot overflow / stale) — both applied under
        # raftMu with the leader/term guards intact
        self._off_reads: list = []
        self._off_read_echoes: list = []
        # device-plane observability (ISSUE 5): set by the coordinator
        # when obs is enabled; _apply_offload_effects counts delivered
        # effects under dragonboat_node_offload_applied_total{kind=...}.
        # None (the default) keeps the apply path untouched.
        self.obs_registry = None
        # replica-plane instruments (obs/instruments.py ReplicaObs, ISSUE
        # 37; set by NodeHost when its tracer or enable_metrics is on):
        # snapshot saves, compactions, InstallSnapshot, check-quorum
        # windows.  None keeps every site below untouched.
        self.replica_obs = None
        # applied index at which the next skipped periodic snapshot is
        # counted (kept only while replica_obs is attached)
        self._ss_refuse_from = 0
        # device state machine (devsm, ISSUE 11): set by NodeHost when a
        # DeviceKVStateMachine group registers (Config.device_kv on the
        # tpu engine).  None keeps every path below bit-identical.  The
        # release floor is the highest DEVICE commit watermark pending
        # reads have been released at — the plane's shadow fallback gates
        # host-apply catch-up on it.
        self.devsm_plane = None
        self.devsm_release_floor = 0
        # cluster health plane (obs/health.py, ISSUE 13): the sampler
        # flips _health_track on its first pass over this node, and
        # offload_commit then records the highest DEVICE commit
        # watermark seen (the sample's dev_commit column).  False (the
        # default, health plane off) keeps offload_commit bit-identical
        # but for this one latch check — the trace=None precedent.
        self._health_track = False
        self._dev_commit_seen = 0
        self._natsm_attached = False  # native C-ABI SM wired to the lane
        self._next_enroll_try = 0.0
        self._tick_count_pending = 0
        # last auto-compacted watermark, consumed by request_compaction
        # (reference snapshotState.compactedTo, swap-to-zero semantics);
        # the lock makes the swap atomic against _compact_log's store
        # (the reference uses atomic.SwapUint64)
        self._compacted_to = 0
        self._compacted_to_mu = threading.Lock()
        self._snapshotting = threading.Lock()
        self._apply_serial = threading.Lock()
        self.leader_id = 0
        self._delete_required = False

    # ---- startup (reference startRaft/replayLog node.go:292,573) ----

    def start(
        self,
        addresses: List[PeerAddress],
        initial: bool,
        new_node: bool,
        seed: Optional[int] = None,
    ) -> None:
        self.snapshotter.process_orphans()
        self.peer = Peer.launch(
            self.config, self.logreader, None, addresses, initial, new_node,
            seed=seed,
        )
        # metrics + LeaderUpdated forwarding (reference event.go:37)
        self.peer.raft.events = getattr(self, "peer_raft_events", None)
        # leader-lease instruments (ISSUE 10; set by NodeHost when
        # enable_metrics is on and the group has read_lease): the raft
        # lease hooks gate on obs `is not None`, so metrics-off hosts
        # never touch the registry
        lease_obs = getattr(self, "lease_obs", None)
        if lease_obs is not None and self.peer.raft.lease is not None:
            self.peer.raft.lease.obs = lease_obs
        # hierarchical-commit instruments (ISSUE 18; set by NodeHost when
        # enable_metrics is on and the group has hier_commit): same
        # gate-on-`is not None` discipline as the lease instruments
        hier_obs = getattr(self, "hier_obs", None)
        if hier_obs is not None and self.peer.raft.hier is not None:
            self.peer.raft.hier.obs = hier_obs
        # wall-clock lease guard (ISSUE 17; set by NodeHost for every
        # Config.read_lease group): the host's tick period in seconds —
        # validity also requires wall-fresh quorum acks, so tick
        # starvation expires the lease instead of extending it
        lease_wall_s = getattr(self, "lease_wall_s", None)
        if lease_wall_s is not None and self.peer.raft.lease is not None:
            self.peer.raft.lease.tick_interval_s = lease_wall_s
        # replication attribution (ISSUE 14): the raft-level ack/commit
        # hooks gate on `replattr is not None`, so trace-off hosts never
        # touch the plane
        if self.replattr is not None:
            self.peer.raft.replattr = self.replattr
        # TPU quorum plugin (ExpertConfig.quorum_engine): stage hot-path
        # tallying to the device engine and register this group's row
        coord = getattr(self, "quorum_coordinator", None)
        if coord is not None:
            self.peer.raft.offload = coord
            # device-tick mode: the tick kernel owns election/heartbeat/
            # check-quorum firing, and a quiesce group's idle clock and
            # sleep beside them (its row can sleep: ops/kernels.tick_step).
            # Only on a mesh-sharded coordinator, whose shards have no
            # idle columns wired, a quiesce group keeps scalar ticks and
            # the host-side ``quiesce_mgr``.
            if coord.drive_ticks and (
                not self.config.quiesce or coord.quiesce_on_device
            ):
                self.peer.raft.device_ticks = True
                if self.config.quiesce:
                    self.dev_quiesce = True
                    self.quiesce_mgr.enabled = False
            coord.register(self)
            # device state machine (devsm, ISSUE 11): Config.device_kv +
            # a DeviceKVStateMachine factory moves the group's apply
            # plane into the fused program — entry ops offload at append
            # (raft.device_kv) and reads serve from device state once the
            # plane binds at a leadership promotion
            dsm_sm = getattr(self, "devsm_sm", None)
            if dsm_sm is not None:
                plane = coord.devsm_plane()
                plane.register(self.cluster_id, dsm_sm)
                self.devsm_plane = plane
                self.peer.raft.device_kv = True
        # queue initial recovery so the apply worker restores the newest
        # local snapshot before any new entries apply.  The WAKEUP is the
        # caller's job AFTER registering the node (reference
        # nodehost.go:1584-1587 clusters.Store -> csi++ -> setApplyReady):
        # signalling here races the busy apply workers, which consume the
        # ready bit, find no node in their map, and silently drop it — the
        # node then never initializes (soak-caught restart wedge)
        self.to_apply.enqueue(
            Task(
                cluster_id=self.cluster_id,
                node_id=self.node_id,
                recover=True,
                initial=True,
                new_node=new_node,
            )
        )

    # ---- TPU quorum plugin appliers ----
    #
    # The coordinator round thread only FLAGS effects here (max-merged,
    # idempotent attribute writes under the GIL) and wakes the group; the
    # partitioned step workers apply them under raftMu via
    # _apply_offload_effects.  Applying effects synchronously on the round
    # thread serialized every leader's heartbeat broadcast behind one
    # thread — at 1,365 device-ticked leaders per host that thread needed
    # ~1s of raftMu work per 1s tick, heartbeats stalled, and followers
    # deposed freshly elected leaders (measured at the 4k-group rung).
    # Spreading application across step workers is exactly the
    # reference's partitioned-worker model (execengine.go:654-706).

    def offload_commit(self, q: int, wake: bool = True) -> None:
        """Flag a device-computed commit watermark (applied in
        ``_apply_offload_effects`` where ``log.try_commit`` re-applies the
        current-term rule, raft paper p8, so stale results are rejected
        and commit outputs stay bit-identical to the scalar path).
        ``wake=False`` lets a host-plane-fed coordinator coalesce the
        step wakeup to one per group per round."""
        with self._off_mu:
            if q > self._off_commit:
                self._off_commit = q
                self._off_pending = True
            if self._health_track and q > self._dev_commit_seen:
                self._dev_commit_seen = q
        if wake:
            self.nh.engine.set_step_ready(self.cluster_id)

    def offload_election(self, won: bool, term: int, wake: bool = True) -> None:
        """Flag a device-tallied election outcome.  ``term`` pins the
        outcome to the campaign it tallied: a flag staged before the
        campaign restarted at a higher term is discarded at apply time."""
        with self._off_mu:
            self._off_election = (won, term)
            self._off_pending = True
        if wake:
            self.nh.engine.set_step_ready(self.cluster_id)

    def offload_read_confirm(
        self, low: int, high: int, term: int, wake: bool = True
    ) -> None:
        """Flag a device-confirmed ReadIndex ctx (kernels.read_confirm
        reached quorum for its slot).  Applied in
        ``_apply_offload_effects`` through ``read_index.release`` — the
        scalar prefix pop — under raftMu with leader/term guards, so a
        stale confirmation (leadership moved since the echo quorum) is
        rejected, never applied."""
        with self._off_mu:
            self._off_reads.append((low, high, term))
            self._off_pending = True
        if wake:
            self.nh.engine.set_step_ready(self.cluster_id)

    def offload_read_echo(
        self, from_: int, low: int, high: int, wake: bool = True
    ) -> None:
        """Fallback: a heartbeat echo for a ctx the device read plane is
        NOT tracking (pending-read slot overflow, or the echo raced a
        confirmation).  Re-routed through the scalar tally, which is a
        no-op for unknown ctxs."""
        with self._off_mu:
            self._off_read_echoes.append((from_, low, high))
            self._off_pending = True
        if wake:
            self.nh.engine.set_step_ready(self.cluster_id)

    def offload_tick_elect(self, wake: bool = True) -> None:
        with self._off_mu:
            self._off_elect = True
            self._off_pending = True
        if wake:
            self.nh.engine.set_step_ready(self.cluster_id)

    def offload_tick_heartbeat(self, wake: bool = True) -> None:
        with self._off_mu:
            self._off_hb = True
            self._off_pending = True
        if wake:
            self.nh.engine.set_step_ready(self.cluster_id)

    def offload_tick_demote(self, wake: bool = True) -> None:
        with self._off_mu:
            self._off_demote = True
            self._off_pending = True
        if wake:
            self.nh.engine.set_step_ready(self.cluster_id)

    def offload_quiesce_enter(self, wake: bool = True) -> None:
        """The row's idle clock crossed its threshold and the row went to
        sleep (``TickFlags.quiesce_enter``): the replica's one step-worker
        turn of the sleep, which tells the peers."""
        with self._off_mu:
            self._off_quiesce = True
            self._off_pending = True
        if wake:
            self.nh.engine.set_step_ready(self.cluster_id)

    # ---- batched heartbeat plane (tpuquorum.py) ----
    #
    # The three legs of a heartbeat exchange, run by the coordinator for
    # every group of a block in one pass on ONE thread (the round thread,
    # the transport's delivery thread) instead of one step-worker turn a
    # group a leg.  Each takes raftMu without waiting and returns the
    # cause where the per-group path must be taken: the scalar handlers
    # then see exactly today's message.  A busy group (raftMu held, or
    # its update not committed yet) gets the leg's lock-free part only
    # (``_hb_block_raft``) and the answer "lite".

    def _hb_block_enter(self):
        """Take raftMu without waiting and bring the scalar clock up to
        now, as a step would have (``_catch_up_and_tick``: the guards and
        the pending-request timeouts read it).  Returns the cause that
        keeps this replica off the block plane, or None with raftMu HELD."""
        if self._update_out or not self.raft_mu.acquire(blocking=False):
            # mid-step, or its last update is not committed yet: nothing
            # may touch the raft state under the lock until it is (the
            # step workers skip the group for the same reason)
            return "busy"
        if self._update_out or self._hb_block_raft() is None:
            # (an update that left between the test and the lock)
            cause = "busy" if self._update_out else "state"
            self.raft_mu.release()
            return cause
        delta = self._catch_up_ticks()
        if delta:
            if self.has_pending_requests() or not self.peer.raft.tick_quiet(
                delta
            ):
                self._tick(delta, tracker_count=self._tracker_ticks(delta))
            else:
                # nothing pending, nothing a tick could fire: the clocks
                # in closed form (the request trackers' clocks are
                # relative: with nothing tracked they may stand)
                self.current_tick += delta
                self._update_leader_info()
        return None

    def _hb_block_leave(self) -> None:
        """Release raftMu; a tick that left a message behind (the rate
        limiter's) gets the step that flushes it.  (No update is in
        flight here, so the outbox was empty when the leg began.)"""
        pending = bool(self.peer.raft.msgs)
        self.raft_mu.release()
        if pending:
            self.nh.engine.set_step_ready(self.cluster_id)

    def _hb_block_raft(self):
        """The raft of a replica that is on the block plane at all: a
        device-ticked group (a quiesce group among them while it is awake:
        a sleeping leader's row raises no heartbeat).  None keeps it on
        the per-group path.

        Read without raftMu for a BUSY group (mid-step, or its update not
        committed yet): such a group cannot be touched under the lock
        now, and handing it the per-group message instead would queue a
        step-worker turn behind the very work that keeps it busy — on a
        loaded host that turns the cheap path into the expensive one
        exactly when it can least afford it.  What a heartbeat exchange
        must do for a busy group needs no lock: plain reads of ints, and
        stores any contact would make.  The rest (the commit index, a
        waiting remote's resume, a lagging remote's probe) is what the
        next tick's row does again."""
        p = self.peer
        if (
            p is None or self._stopped.is_set() or self.fast_lane
            or not p.raft.device_ticks
            or not self._initialized.is_set()
        ):
            return None
        return p.raft

    def hb_block_rows(self, demote: bool = False):
        """Leader leg: ``(term, [(to, commit), ...], demoted)`` of the
        heartbeats due now (``Raft.heartbeat_block_rows``), or ``(cause,
        n, False)`` with the number of heartbeats the per-group path will
        send instead.  ``demote``: the tick also closed the group's
        check-quorum window; the scalar CHECK_QUORUM runs here, after the
        heartbeats and under the same hold of raftMu as
        ``_apply_offload_effects`` has them, so no response to these
        heartbeats is counted into the window they close; ``demoted``
        says it ran (a busy group's rows are read without the lock, and
        its window is left to the per-group flag)."""
        cause = self._hb_block_enter()
        if cause is None:
            try:
                r = self.peer.raft
                rows = r.heartbeat_block_rows()
                if rows.__class__ is not str:
                    if r.lease is not None:
                        r.heartbeat_block_sent(rows)
                    r.heartbeat_tick = 0
                    term = r.term
                    if demote and r.check_quorum:
                        r.election_tick = 0
                        r.handle(
                            Message(from_=self.node_id, type=MT.CHECK_QUORUM)
                        )
                        obs = self.replica_obs
                        if obs is not None:
                            obs.checkq_window(not r.is_leader())
                        if not r.is_leader():  # the step flushes the rest
                            self.nh.engine.set_step_ready(self.cluster_id)
                    return term, rows, True
                cause = rows
            finally:
                self._hb_block_leave()
        elif cause == "busy":
            r = self._hb_block_raft()
            # (a lease group's sends are booked under raftMu or not made:
            # the ack of an unbooked send pops the NEXT booked one, a
            # newer tick than it answers, the direction ``PENDING_CAP``
            # exists to exclude; a busy lease group takes the per-group
            # heartbeat, whose step books it)
            if r is not None and r.lease is None:
                try:
                    term = r.term
                    rows = r.heartbeat_block_rows()  # reads only
                    if rows.__class__ is not str and r.term == term:
                        return term, rows, False
                except RuntimeError:  # the membership moved under the read
                    pass
        p = self.peer  # unlocked: a count for the books, nothing else
        return cause, (len(p.raft.remotes) - 1 if p is not None else 0), False

    def hb_block_contact(self, from_: int, term: int, commit: int):
        """Follower leg (``Raft.heartbeat_block_contact``); a commit index
        that moved wakes the group so the entries get applied.  Returns
        None (handled), ``"lite"`` (a busy group: its clock reset, its
        commit index left to the next row) or the cause for the per-group
        message."""
        cause = self._hb_block_enter()
        if cause is not None:
            if cause == "busy":
                r = self._hb_block_raft()
                if (
                    r is not None and r.term == term and r.is_follower()
                    and r.leader_id == from_
                ):
                    r.election_tick = 0
                    return "lite"
            return cause
        try:
            r = self.peer.raft
            if self._asleep:
                self._activity(MT.HEARTBEAT)
            before = r.log.committed
            cause = r.heartbeat_block_contact(from_, term, commit)
            if cause is None and r.log.committed != before:
                self.nh.engine.set_step_ready(self.cluster_id)
            return cause
        except RuntimeError:
            return "state"  # a commit past the log: the scalar path's to judge
        finally:
            self._hb_block_leave()

    def hb_block_resp(self, from_: int, term: int):
        """Leader leg for a response row (``Raft.heartbeat_block_resp``);
        None, ``"lite"`` (a busy group: the contact marked, a waiting or
        lagging remote left to the next row) or the cause."""
        cause = self._hb_block_enter()
        if cause is not None:
            if cause == "busy":
                r = self._hb_block_raft()
                if r is not None and r.term == term and r.is_leader():
                    rp = r.remotes.get(from_)
                    if rp is not None and r.lease is None:
                        rp.active = True
                        return "lite"
            return cause
        try:
            if self._asleep:
                self._activity(MT.HEARTBEAT_RESP)
            return self.peer.raft.heartbeat_block_resp(from_, term)
        finally:
            self._hb_block_leave()

    def _apply_offload_effects(self) -> None:
        """Apply flagged device-engine effects (under raftMu, from a step
        worker).  Every effect re-runs its scalar guards, so a stale flag
        is rejected, never applied."""
        r = self.peer.raft
        with self._off_mu:
            self._off_pending = False
            commit_q, self._off_commit = self._off_commit, 0
            election, self._off_election = self._off_election, None
            hb, self._off_hb = self._off_hb, False
            elect, self._off_elect = self._off_elect, False
            demote, self._off_demote = self._off_demote, False
            qenter, self._off_quiesce = self._off_quiesce, False
            reads, self._off_reads = self._off_reads, []
            echoes, self._off_read_echoes = self._off_read_echoes, []
        m = self.obs_registry
        if m is not None:
            # effects DELIVERED to the apply path (the scalar guards
            # below may still reject stale ones — delivered minus the
            # engine's egress counters bounds the rejection rate)
            name = "dragonboat_node_offload_applied_total"
            if commit_q:
                m.counter_add(name, labels={"kind": "commit"})
            if election is not None:
                m.counter_add(name, labels={"kind": "election"})
            if reads:
                m.counter_add(name, len(reads), labels={"kind": "read_confirm"})
            if echoes:
                m.counter_add(name, len(echoes), labels={"kind": "read_echo"})
            if elect or hb or demote or qenter:
                m.counter_add(name, labels={"kind": "tick"})
        if self.fast_lane:
            return  # native core owns the group; flags are stale
        if qenter and self.dev_quiesce and (
            # the scalar guard of this flag: a row crosses only after a
            # threshold of ticks without a DRAINED mark, so activity
            # newer than half of that was staged behind the round that
            # raised the flag; its mark wakes the row with the next round
            # and this replica never slept
            self.nh.tick_count - self._active_tick
            >= self.quiesce_mgr.threshold // 2
        ):
            self._sleep(own=True)
        if commit_q and r.is_leader() and r.log.try_commit(commit_q, r.term):
            # device-plane commits attribute too (ISSUE 14): the same
            # close hook the scalar commit site runs, under raftMu with
            # the current voter set — the coordinator already linked the
            # releasing round's span seq via replattr.note_device_round
            r._note_commit()
            if r.hier is not None:
                # hier close attribution (ISSUE 18): scalar matches stay
                # current in offload mode (rp.try_update precedes
                # offload.ack), so the classic kth-largest recomputes
                # here to tell a sub-quorum close from a full-quorum one
                voters = r.voting_members()
                match_of = {nid: rm.match for nid, rm in voters.items()}
                m_sorted = sorted(match_of.values())
                q_classic = m_sorted[len(m_sorted) - r.quorum()]
                r.hier.note_close(via_sub=commit_q > q_classic)
                r.hier.note_far_lag(
                    match_of, voters.keys(), r.log.committed
                )
            r.broadcast_replicate_message()
        if (
            commit_q
            and self.devsm_plane is not None
            and self.devsm_plane.bound(self.cluster_id)
        ):
            # devsm read-release gate (ISSUE 11): on the device plane
            # apply == commit — the fold runs inside the dispatch that
            # advanced this watermark — so pending reads release HERE, at
            # the device watermark, and their lookups serve from device
            # state.  Host apply (which only keeps the shadow warm) is
            # off the read path entirely; the plane's shadow fallback
            # gates on the floor recorded below.
            if commit_q > self.devsm_release_floor:
                self.devsm_release_floor = commit_q
            self.pending_reads.applied(commit_q)
        # the coordinator's sampled ReadIndex contexts (ISSUE 39): empty
        # while nothing is sampled, and nothing below reads a clock then
        rt = (
            getattr(r.offload, "_read_traces", None)
            if reads or echoes else None
        )
        if reads and r.is_leader():
            for low, high, term in reads:
                # term-pinned like offload_election: a confirmation tallied
                # before leadership moved must not release reads under the
                # new term (become_* rebuilt read_index, so the release is
                # a no-op then anyway — the guard keeps intent explicit)
                if r.term != term:
                    continue
                ctx = SystemCtx(low=low, high=high)
                ris = r.read_index.release(ctx)
                r.apply_read_releases(ris)
                if rt:
                    r.offload.read_released(self.cluster_id, ris)
        if echoes and r.is_leader():
            for from_, low, high in echoes:
                ris = r.handle_read_index_leader_confirmation(
                    Message(from_=from_, hint=low, hint_high=high)
                )
                if rt and ris:
                    r.offload.read_released(
                        self.cluster_id, ris, scalar=True
                    )
        if election is not None:
            won, term = election
            if r.is_candidate() and r.term == term:
                # hier vote rule (raft/hier.py): the device `won` flag is
                # the classic quorum only — the scalar votes dict (always
                # maintained, handle_vote_resp runs before the offload
                # gate) re-verifies domain intersection here.  The flag
                # re-fires on later rounds, so a held promotion lands
                # once the intersecting grant arrives.
                if won and r.hier_election_ok():
                    r.become_leader()
                    r.broadcast_replicate_message()
                elif won:
                    if r.hier is not None:
                        r.hier.note_election_hold()
                else:
                    r.become_follower(r.term, 0)
        if (elect or hb or demote) and r.device_ticks:
            self._catch_up_and_tick()
        if (
            elect
            and r.device_ticks
            and not r.is_leader()
            and not r.is_observer()
            and not r.is_witness()
            and not r.self_removed()
            and not self.quiesced()
            # scalar clock must agree: it resets synchronously under
            # raftMu on leader contact, so a device row whose staged
            # contact reset is still riding a round cannot disrupt a
            # healthy leader (same pattern as the commit term guard)
            and r.time_for_election()
        ):
            r.election_tick = 0
            r.handle(Message(from_=self.node_id, type=MT.ELECTION))
        if hb and r.device_ticks and r.is_leader():
            r.heartbeat_tick = 0
            r.handle(Message(from_=self.node_id, type=MT.LEADER_HEARTBEAT))
        if demote and r.device_ticks and r.is_leader() and r.check_quorum:
            r.election_tick = 0
            r.handle(Message(from_=self.node_id, type=MT.CHECK_QUORUM))
            obs = self.replica_obs
            if obs is not None:
                obs.checkq_window(not r.is_leader())

    def _publish_event(
        self, type: SystemEventType, index: int = 0, from_: int = 0
    ) -> None:
        self.nh.sys_events.publish(
            SystemEvent(
                type=type,
                cluster_id=self.cluster_id,
                node_id=self.node_id,
                index=index,
                from_=from_,
            )
        )

    def initialized(self) -> bool:
        return self._initialized.is_set()

    def wait_initialized(self, timeout: float = 30.0) -> bool:
        return self._initialized.wait(timeout)

    # ---- user request entry points ----

    def _timeout_ticks(self, timeout_s: float) -> int:
        ticks = int(timeout_s * 1000 / self.tick_millisecond)
        return max(ticks, 1)

    # non-cmd entry fields bound (reference settings.EntryNonCmdFieldsSize:
    # 16 u64 fields) used by the payload-size guard
    _ENTRY_NON_CMD_FIELDS_SIZE = 16 * 8

    def _check_user_op(self, payload_len: int = 0) -> None:
        """Witness replicas serve NO user operations (reference
        ``ErrInvalidOperation``, node.go:352-442), and a payload that
        cannot fit ``max_in_mem_log_size`` can never be appended
        (``ErrPayloadTooBig``, node.go:363-367)."""
        if self.config.is_witness:
            raise InvalidOperationError()
        limit = self.config.max_in_mem_log_size
        if limit and payload_len + self._ENTRY_NON_CMD_FIELDS_SIZE > limit:
            raise PayloadTooBigError()

    def propose(
        self, session: Session, cmd: bytes, timeout_s: float
    ) -> RequestState:
        self._check_user_op(len(cmd))
        ing = self.ingress
        if ing is not None:
            # host-plane ingress tier, adaptive for singles: a shard with
            # staged or draining work coalesces this proposal into the
            # batcher's next burst (ordered behind the in-flight ring);
            # a QUIET shard returns None and the proposal stages inline
            # below — the direct path, so a low-rate client never pays
            # the extra thread handoff (the measured on/off latency tax
            # of an always-on ring at window-1 arrival).  The precheck
            # above keeps witness/payload semantics synchronous either
            # way.
            rs = ing.submit_single_if_active(self, session, cmd, timeout_s)
            if rs is not None:
                return rs
        # non-empty commands are stored as ENCODED entries: 1-byte
        # version/compression header (+ snappy when configured) — reference
        # requests.go:1038-1042 + rsm/encoded.go
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        entry_type = EntryType.APPLICATION
        if cmd:
            cmd = get_encoded_payload(self._entry_ct, cmd)
            entry_type = EntryType.ENCODED
        rs, entry = self.pending_proposals.propose(
            session.client_id, session.series_id, cmd,
            self._timeout_ticks(timeout_s),
        )
        entry.type = entry_type
        entry.responded_to = session.responded_to
        if tr is not None:
            tr.attach_one(rs, self.cluster_id, t0)
        # native fast lane: the index is assigned and the entry staged for
        # replication + WAL entirely in C++ (completion still arrives via
        # the normal apply -> pending_proposals.applied path).  A 0 return
        # means not-enrolled/ejecting: fall back to the scalar queue.
        if self.fast_lane and self.fastlane is not None:
            if self.fastlane.nat.propose(
                self.cluster_id, entry.key, entry.client_id, entry.series_id,
                entry.responded_to, int(entry.type), cmd,
            ):
                if tr is not None:
                    tr.mark(rs, "ingress")
                return rs
        if not self.entry_q.add(entry):
            self.pending_proposals.dropped(entry.key)
            raise SystemBusyError()
        self.nh.engine.set_step_ready(self.cluster_id)
        if tr is not None:
            tr.mark(rs, "ingress")
        return rs

    def propose_batch(
        self, session: Session, cmds: List[bytes], timeout_s: float
    ) -> List[RequestState]:
        """Propose a burst of commands in one pass — semantically identical
        to N :meth:`propose` calls (one entry + one completion future per
        command), amortizing the per-request tracking and, on the native
        fast lane, appending the whole burst under one lock.  Pipelined
        clients (and the e2e benchmark) refill their windows through this;
        the per-request propose path is a first-order cost once replication
        itself is native.  One deviation from the N-calls equivalence:
        the witness/payload precheck is atomic over the whole batch — one
        oversized command rejects the batch up front (nothing partial is
        enqueued), where N calls would submit the small ones first."""
        self._check_user_op(max((len(c) for c in cmds), default=0))
        if not cmds:
            return []
        ing = self.ingress
        if ing is not None:
            # bursts always ride the batcher: they are throughput-driven
            # (pipelined window refills), tolerate the one handoff, and
            # keep the shard active so concurrent singles coalesce
            return ing.submit(self, session, cmds, timeout_s)
        # encode in one pass — empty commands are never re-encoded, and
        # the separate any(enc) scan collapsed into the same loop
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        ct = self._entry_ct
        enc: List[bytes] = []
        has_encoded = False
        for c in cmds:
            if c:
                enc.append(get_encoded_payload(ct, c))
                has_encoded = True
            else:
                enc.append(c)
        entry_type = EntryType.ENCODED if has_encoded else EntryType.APPLICATION
        states, entries = self.pending_proposals.propose_batch(
            session.client_id, session.series_id, enc,
            self._timeout_ticks(timeout_s),
        )
        for e in entries:
            e.type = entry_type if e.cmd else EntryType.APPLICATION
            e.responded_to = session.responded_to
        if tr is not None:
            tr.attach_all(states, self.cluster_id, t0)
        if self.fast_lane and self.fastlane is not None and all(
            e.type == entry_type for e in entries
        ):
            blob = b"".join(
                _pack_len(len(e.cmd)) + e.cmd for e in entries
            )
            if self.fastlane.nat.propose_batch(
                self.cluster_id, [e.key for e in entries], session.client_id,
                session.series_id, session.responded_to, int(entry_type),
                blob,
            ):
                if tr is not None:
                    for rs in states:
                        tr.mark(rs, "ingress")
                return states
        ok = True
        for i, e in enumerate(entries):
            if ok and not self.entry_q.add(e):
                ok = False
            if not ok:
                # queue full mid-burst: drop the remainder; each dropped
                # future resolves like a single propose hitting a full queue
                self.pending_proposals.dropped(e.key)
        self.nh.engine.set_step_ready(self.cluster_id)
        if tr is not None:
            for rs in states:
                tr.mark(rs, "ingress")
        return states

    def propose_session(self, session: Session, timeout_s: float) -> RequestState:
        self._check_user_op()
        rs, entry = self.pending_proposals.propose(
            session.client_id, session.series_id, b"",
            self._timeout_ticks(timeout_s),
        )
        # register/unregister ride the fast lane like any proposal when
        # the native session store is attached (the native apply handles
        # sid 0 / sid ~0); otherwise the apply-side would eject per
        # session op, so go scalar directly
        if (
            self.fast_lane
            and self._natsm_attached
            and self.fastlane is not None
            and self.fastlane.nat.propose(
                self.cluster_id, entry.key, entry.client_id,
                entry.series_id, entry.responded_to, int(entry.type), b"",
            )
        ):
            return rs
        if not self.entry_q.add(entry):
            self.pending_proposals.dropped(entry.key)
            raise SystemBusyError()
        self.nh.engine.set_step_ready(self.cluster_id)
        return rs

    def read(self, timeout_s: float) -> RequestState:
        self._check_user_op()
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        rs = self.pending_reads.read(self._timeout_ticks(timeout_s))
        if tr is not None:
            tr.attach_one(rs, self.cluster_id, t0, kind="read")
            tr.mark(rs, "ingress")
        fl = self.fastlane
        if self.fast_lane and fl is not None:
            # native ReadIndex (natraft.cpp): the context rides hinted
            # heartbeats; a quorum of echoes confirms it and the read pump
            # completes the batch.  The ctx covers every read pending at
            # take time (the scalar batching semantics).
            ctx = self.pending_reads.next_ctx()
            if not self.pending_reads.take_pending(ctx):
                return rs  # a concurrent reader's context covers this one
            if fl.nat.read_index(self.cluster_id, ctx.low, ctx.high):
                return rs
            # not the leader: forward natively (READ_INDEX to the leader,
            # confirmation returns as READ_INDEX_RESP through the read
            # pump) so follower reads stay in the lane instead of costing
            # an eject/re-enroll cycle
            if fl.nat.read_fwd(self.cluster_id, ctx.low, ctx.high):
                return rs
            # native cannot serve (ejecting / no current-term commit yet):
            # hand back to scalar raft, which runs the full protocol
            self._count_eject("read")
            self.fast_eject()
            with self.raft_mu:
                if self.peer is not None:
                    self._read_index(ctx)
        self.nh.engine.set_step_ready(self.cluster_id)
        return rs

    def request_config_change(
        self, cc: ConfigChange, timeout_s: float
    ) -> RequestState:
        self._check_user_op()
        if self.fast_lane:
            self.fast_eject()
        rs = self.pending_config_change.request(
            cc, self._timeout_ticks(timeout_s)
        )
        self.nh.engine.set_step_ready(self.cluster_id)
        return rs

    def request_snapshot(self, req: SSRequest, timeout_s: float) -> RequestState:
        self._check_user_op()
        if self.fast_lane:
            self.fast_eject()
        rs = self.pending_snapshot.request(req, self._timeout_ticks(timeout_s))
        self.nh.engine.set_step_ready(self.cluster_id)
        return rs

    def request_leader_transfer(self, target: int, timeout_s: float) -> RequestState:
        self._check_user_op()
        if self.fast_lane:
            self.fast_eject()
        rs = self.pending_leader_transfer.request(
            target, self._timeout_ticks(timeout_s)
        )
        self.nh.engine.set_step_ready(self.cluster_id)
        return rs

    def stale_read(self, query):
        # a witness SM never applies payloads — a lookup would return a
        # silently empty answer for keys committed cluster-wide
        # (reference StaleRead: ErrInvalidOperation on a witness)
        if self.config.is_witness:
            raise InvalidOperationError()
        return self.sm.lookup(query)

    # ---- inbound messages ----

    def enqueue_message(self, m: Message) -> bool:
        """Queue an inbound message WITHOUT the step-ready ping; the router
        signals once per touched group after draining the whole batch."""
        if self._stopped.is_set():
            return False
        if m.type == MT.INSTALL_SNAPSHOT:
            return self.mq.must_add(m)
        return self.mq.add(m)

    def handle_message_batch(self, m: Message) -> None:
        if self.enqueue_message(m):
            self.nh.engine.set_step_ready(self.cluster_id)

    def request_tick(self) -> None:
        """Reference ``nodehost.go`` sendTickMessage: one LocalTick per RTT."""
        self.mq.add(Message(type=MT.LOCAL_TICK))
        self.nh.engine.set_step_ready(self.cluster_id)

    # ---- lazy tick delivery (tick-lite) ----

    def tick_lite(self) -> bool:
        """True when the tick worker may skip this node's per-tick wakeup:
        the raft clock is owned by the native fast lane or the device tick
        kernel, so the only per-tick host work left (pending-request
        timeout GC, tick counters) tolerates batched delivery at the next
        step (``_catch_up_ticks``)."""
        if not self._initialized.is_set():
            return False
        if self.fast_lane:
            return True
        p = self.peer
        return p is not None and p.raft.device_ticks

    def has_pending_requests(self) -> bool:
        """Cheap unlocked check used by the tick worker's staleness sweep:
        a lite node with possibly-timed-out requests gets a wakeup so GC
        runs.  New requests always arrive with their own step wakeup, so a
        racy miss here only delays GC by one sweep period."""
        return (
            self.pending_proposals.has_pending()
            or self.pending_reads.has_pending()
            or self.pending_config_change.pending() is not None
            or self.pending_snapshot.pending() is not None
            or self.pending_leader_transfer.pending() is not None
        )

    def _catch_up_ticks(self) -> int:
        """Elapsed global ticks since this node last stepped (under
        raftMu).  Capped so a long stall delivers enough ticks to fire any
        timeout-driven behavior without looping unboundedly."""
        nt = self.nh.tick_count
        delta = nt - self._seen_tick
        if delta <= 0:
            return 0
        self._seen_tick = nt
        return min(delta, max(4 * self.config.election_rtt, 16))

    def _tracker_ticks(self, delta: int) -> int:
        """How many of a catch-up delta the pending-request clocks get.

        While requests are pending the sweep wakes the node within
        ``lazy_tick_sweep_ticks``, so the tracker clock never lags real
        time by more than that; a larger delta means the backlog predates
        every live request, and delivering it would erode a
        just-registered request's deadline by idle time during which it
        did not exist (it can even expire it instantly)."""
        return min(delta, Soft.lazy_tick_sweep_ticks)

    def _catch_up_and_tick(self) -> None:
        """Shared preamble of the offload_tick_* handlers (under raftMu):
        an idle device-ticked group's scalar clock only advances at step
        time, and the device flag may be its first step in many ticks —
        catch up so the scalar-agreement guards compare a current clock."""
        if self.peer.raft.device_ticks and self.initialized():
            delta = self._catch_up_ticks()
            if delta:
                self._tick(delta, tracker_count=self._tracker_ticks(delta))

    def request_campaign(self) -> None:
        """Immediately start an election on this replica (etcd's
        ``raft.Campaign`` / MsgHup; our ``MT.ELECTION`` is the same local
        message ``raft.go:395`` injects on election timeout).  Used by
        benchmarks/tests for deterministic, fast leader placement instead of
        waiting out a randomized election timeout."""
        self.mq.add(Message(type=MT.ELECTION, from_=self.node_id))
        self.nh.engine.set_step_ready(self.cluster_id)

    def handle_snapshot_status(self, node_id: int, failed: bool) -> bool:
        """Returns True when queued (the feedback tracker retries on
        False — reference pushfunc feedback.go:36)."""
        if not self.mq.add(
            Message(type=MT.SNAPSHOT_STATUS, from_=node_id, reject=failed)
        ):
            return False
        self.nh.engine.set_step_ready(self.cluster_id)
        return True

    def handle_unreachable(self, node_id: int) -> None:
        self.mq.add(Message(type=MT.UNREACHABLE, from_=node_id))
        self.nh.engine.set_step_ready(self.cluster_id)

    # ---- step path (reference stepNode node.go:1099) ----

    def step_node(self) -> Optional[Update]:
        with self.raft_mu:
            if self._stopped.is_set() or self.peer is None:
                return None
            if not self.initialized():
                return None
            if self._off_pending:
                self._apply_offload_effects()
            delta = self._catch_up_ticks()
            if self.fast_lane:
                if not self._fast_lane_step(delta):
                    return None
                delta = 0  # consumed by the fast-lane step
            elif not self.peer.raft.device_ticks:
                # scalar-clocked groups receive real LOCAL_TICK messages;
                # the counter sync above only prevents a stale delta from
                # double-delivering after a lite→scalar transition
                delta = 0
            self._handle_events(extra_ticks=delta)
            more = self.to_apply.more_entries_to_apply()
            if self.peer.has_update(more):
                # after a failed persist the state machine may be ahead of
                # the log's ``processed`` (its entries were handed over
                # early); the log never hears of an applied index above it
                ud = self.peer.get_update(
                    more,
                    min(
                        self.sm.get_last_applied(),
                        self.peer.raft.log.processed,
                    ),
                )
                self._update_out = True
                return ud
            self._maybe_enroll()
            return None

    # ---- native fast lane (fastlane.py) ----

    def _fast_lane_step(self, extra_ticks: int = 0) -> bool:
        """Enrolled-mode step (under raftMu): ticks feed only the pending
        trackers (the native core owns heartbeat/election clocks); queued
        proposals and in-flight fast-path messages are fed to the native
        core directly; anything else forces an eject.  Returns True when
        the caller should continue into the normal scalar step."""
        fl = self.fastlane
        ticks = 0
        others: List[Message] = []
        cur_term = self.peer.raft.term  # frozen while enrolled (any native
        # term change ejects), so this is the native group's term too
        for m in self.mq.get():
            if m.type == MT.LOCAL_TICK:
                ticks += 1
            elif m.type in _FAST_WIRE_TYPES and fl.ingest_message(m):
                pass  # consumed natively (in-flight at enrollment)
            elif (
                m.type == MT.REQUEST_VOTE_RESP and m.term <= cur_term
            ):
                # straggler from the election that preceded enrollment: an
                # enrolled group is never a candidate, so scalar raft would
                # no-op it — ejecting for it (round 3: router ejects) cost
                # enrollment stability for nothing
                fl.count_drop("stale-vote-resp")
            else:
                others.append(m)
        if ticks or extra_ticks:
            self.current_tick += ticks + extra_ticks
            self._tick_trackers(ticks + self._tracker_ticks(extra_ticks))
        # reads registered while (re)enrolling are served natively here
        # (the same protocol Node.read drives; ejecting for them would
        # defeat the native ReadIndex path)
        while self.pending_reads.peep():
            ctx = self.pending_reads.next_ctx()
            if not self.pending_reads.take_pending(ctx):
                break
            if not (
                fl.nat.read_index(self.cluster_id, ctx.low, ctx.high)
                or fl.nat.read_fwd(self.cluster_id, ctx.low, ctx.high)
            ):
                self._count_eject("read-fallback")
                self.fast_eject()
                self._read_index(ctx)
                return True
        # proposals racing an enrollment land in the scalar queue; route
        # them into the native lane in order (indices assigned there)
        entries = self.entry_q.get()
        rest: List[Entry] = []
        for e in entries:
            if rest or e.is_config_change() or not fl.nat.propose(
                self.cluster_id, e.key, e.client_id, e.series_id,
                e.responded_to, int(e.type), e.cmd,
            ):
                rest.append(e)
        if not (others or rest or self._fast_slow_inputs()):
            return False
        self._count_eject(
            "step-msgs:" + ",".join(sorted({m.type.name for m in others}))
            if others else ("step-entries" if rest else "step-slow-input")
        )
        self.fast_eject()
        if rest:
            self.peer.propose_entries(rest)
        if others:
            self._process_messages(others)
        return True

    def _fast_slow_inputs(self) -> bool:
        """Inputs the fast lane cannot serve (checked each enrolled step;
        the user-facing entry points also eject eagerly)."""
        if (
            self.pending_config_change.pending() is not None
            or self.pending_snapshot.pending() is not None
            or self.pending_leader_transfer.pending() is not None
        ):
            return True
        if self.snapshot_due():
            if self._natsm_attached:
                # enrolled native-SM groups snapshot IN PLACE: the native
                # core captures a consistent kv+session image at its
                # applied index (natr_capture_sm) and the save runs on
                # the snapshot pool — no eject, no scalar exile.  The
                # reference's concurrent SMs never stall apply for a save
                # either (statemachine.go:552-814); this is the regular-
                # SM analog for the fast lane.
                self._save_snapshot_required()
            else:
                return True
        return False

    def snapshot_due(self) -> bool:
        """Applied delta crossed ``snapshot_entries`` (reference
        ``saveSnapshotRequired``) — the one predicate shared by the
        periodic-save trigger, the enrollment gate, and the fast lane's
        completion-pump eject trigger; a divergence between those sites
        would desynchronize eject from re-enroll."""
        se = self.config.snapshot_entries
        return bool(
            se
            and self.sm.get_last_applied() - self.sm.get_snapshot_index()
            >= se
        )

    def _maybe_enroll(self) -> None:
        """Enroll this group into the native fast lane (under raftMu, at a
        step instant with no pending raft Update — so the in-memory log is
        fully persisted and there are no queued messages).  Mid-flight
        state is allowed: the uncommitted/unapplied tail, per-peer progress
        and the apply watermark are captured into the native core
        (natraft.cpp's enrollment contract), so groups re-enter the lane
        under live load after an eject."""
        fl = self.fastlane
        if fl is None or not fl.enabled or self.fast_lane:
            return
        import time as _time

        now = _time.monotonic()
        if now < self._next_enroll_try:
            return
        self._next_enroll_try = now + 0.1
        r = self.peer.raft
        if not (r.is_leader() or (r.is_follower() and r.leader_id != 0)):
            return
        # observer/witness-BEARING groups enroll (observers become
        # non-voting native replication targets; witnesses vote natively
        # and receive metadata-only entries); observer/witness REPLICAS
        # themselves stay on the scalar path
        if r.is_observer() or r.is_witness():
            return
        if len(r.remotes) < 2:
            return
        if len(r.remotes) + len(r.observers) + len(r.witnesses) > 16:
            return
        if (
            r.has_pending_config_change()
            or r.leader_transfering()
            or self.config.quiesce
        ):
            return
        log = r.log
        li = log.last_index()
        if log.entries_to_save() or log.inmem.snapshot is not None:
            return
        if r.msgs or r.dropped_entries or r.dropped_read_indexes or r.ready_to_read:
            return
        # a ReadIndex context mid-confirmation in scalar raft (e.g. one
        # re-driven by a previous eject) would freeze until timeout if the
        # group enrolled now — its confirmation runs through scalar steps
        if r.read_index.has_pending_request():
            return
        if self._fast_slow_inputs() or self.pending_reads.peep():
            return
        if self._snapshotting.locked():
            return
        committed, processed = log.committed, log.processed
        try:
            # every index a native tally can newly commit must carry the
            # current term (raft paper p8 holds structurally in the core)
            if committed < li and (
                log.term(committed + 1) != r.term or log.term(li) != r.term
            ):
                return
        except Exception:
            return
        from .raft.remote import RemoteState

        peers = []
        min_next = li + 1
        # role: 1 = voter, 0 = observer, 2 = witness (natr_enroll contract)
        members = [(nid, r.remotes[nid], 1) for nid in sorted(r.remotes)]
        members += [
            (nid, r.observers[nid], 0) for nid in sorted(r.observers)
        ]
        members += [
            (nid, r.witnesses[nid], 2) for nid in sorted(r.witnesses)
        ]
        for nid, rp, role in members:
            if nid == self.node_id:
                continue
            if rp.state == RemoteState.SNAPSHOT or rp.match > li:
                return
            addr = self.nh.node_registry.resolve(self.cluster_id, nid)
            if addr is None:
                return
            slot = fl.slot_for(addr)
            if slot < 0:
                return
            nxt = min(max(rp.next, rp.match + 1), li + 1)
            min_next = min(min_next, nxt)
            peers.append((nid, slot, rp.match, nxt, role))
        # the native log must cover everything a resend or an apply
        # hand-off can still need
        log_first = min(processed + 1, min_next)
        if log_first < log.first_index():
            return  # tail partially compacted away: wait for idle
        try:
            prev_term = log.term(log_first - 1) if log_first > 1 else 0
        except Exception:
            return
        tail_entries = (
            log.get_entries(log_first, li + 1, 1 << 62) if li >= log_first else []
        )
        if len(tail_entries) != li - log_first + 1:
            return
        from .wire.codec import encode_entry_into

        buf = bytearray()
        for e in tail_entries:
            encode_entry_into(buf, e)
        hb_ms = max(1, self.config.heartbeat_rtt * self.tick_millisecond)
        elect_ms = max(10, 2 * self.config.election_rtt * self.tick_millisecond)
        # register BEFORE enroll: the native round thread may emit an apply
        # span for this group the instant enroll inserts it (enrolling with
        # committed > processed re-emits the unapplied window), and a span
        # arriving before registration would be dropped — wedging applied
        # below commit and timing out every later linearizable read (the
        # round-3 chaos failure)
        fl.register_node(self)
        ok = fl.nat.enroll(
            self.cluster_id,
            self.node_id,
            term=r.term,
            vote=r.vote,
            leader_id=r.leader_id,
            is_leader=r.is_leader(),
            last_index=li,
            commit=committed,
            processed=processed,
            log_first=log_first,
            prev_term=prev_term,
            shard=self.cluster_id % fl.n_shards,
            hb_period_ms=hb_ms,
            elect_timeout_ms=elect_ms,
            term_commit_ok=(
                r.is_leader() and r.has_committed_entry_at_current_term()
            ),
            peers=peers,
            tail=bytes(buf),
        )
        if ok:
            self.fast_lane = True
            fl.note_enrolled(self.cluster_id)
            self._maybe_attach_native_sm(fl)
        else:
            fl.unregister_node(self)

    def _maybe_attach_native_sm(self, fl) -> None:
        """If the user SM is a native C-ABI instance (natsm.py), let the
        enrolled group apply committed entries in C++ — the apply/notify
        rim was the measured ~40us/write Python cost (PERF.md)."""
        if self.sm.on_disk:
            return
        user = getattr(self.sm.managed, "sm", None)
        handle = getattr(user, "natsm_handle", 0)
        fn = getattr(user, "natsm_update_fn", 0)
        if handle and fn:
            # flag BEFORE attach, applied-read AFTER the flag: an apply
            # finishing in the window then still calls note_applied (the
            # native side takes max, so a racing lift is never clobbered);
            # flag-first with a late read closes the barrier-never-lifts
            # TOCTOU
            self._natsm_attached = True
            if not fl.nat.attach_sm(
                self.cluster_id, handle, fn, self.sm.get_last_applied(),
                # session store: lets session-managed entries (register/
                # dedup/unregister) apply natively too — the RSM manager
                # already fronts the same store for the scalar plane
                getattr(user, "natsm_sess_handle", 0),
                getattr(user, "natsm_sess_apply_fn", 0),
                # image serializers: periodic snapshots capture natively
                # (natr_capture_sm) instead of ejecting the group
                getattr(user, "natsm_save_fn", 0),
                getattr(user, "natsm_sess_save_fn", 0),
            ):
                self._natsm_attached = False

    def _count_eject(self, reason: str) -> None:
        if self.fastlane is not None:
            self.fastlane.count_eject(reason)

    def fast_eject(
        self, contact_lost: bool = False, reenroll_backoff: bool = False
    ) -> None:
        """Hand the group back from the native core to scalar raft.

        Rebuilds exactly the state the Python raft object would have had:
        log watermarks (committed/processed), a fresh saved in-memory tail,
        the stable-log window in the LogReader (entries were persisted by
        the native core), per-remote progress, and the persisted-state
        caches (the native core wrote State/MaxIndex records directly, so
        the Python rdbcache must be refreshed to match the disk)."""
        fl = self.fastlane
        if fl is None:
            return
        with self.raft_mu:
            if not self.fast_lane:
                return
            try:
                st = fl.eject_locked(self)
            except IOError:
                # WAL tail flush failed during the handoff: the LogDB holds
                # records the scalar state cannot account for.  Resuming
                # would reuse persisted indices — fail the replica instead
                # (the rest of the group continues; restart replays the log)
                plog.critical(
                    "%s fast-lane eject failed on WAL error; stopping replica",
                    self.describe(),
                )
                self.fast_lane = False
                self._natsm_attached = False
                fl.note_ejected(self.cluster_id)
                self._stopped.set()
                return
            self.fast_lane = False
            was_natsm = self._natsm_attached
            self._natsm_attached = False
            fl.note_ejected(self.cluster_id)
            if st is None or self.peer is None:
                return
            if was_natsm:
                # native applies bypassed notify_raft_last_applied; catch
                # raft's applied view up or has_config_change_to_apply()
                # (committed > applied) would silently refuse every
                # campaign after the eject — the failover wedge
                self.peer.notify_raft_last_applied(self.sm.get_last_applied())
            r = self.peer.raft
            log = r.log
            # stable window: native entries are in the LogDB already
            _, prev_last = self.logreader.get_range()
            if st.last_index > prev_last:
                self.logreader.set_range(
                    prev_last + 1, st.last_index - prev_last
                )
            from .raft.inmemory import InMemory
            from .raft.remote import RemoteState

            log.inmem = InMemory(st.last_index, log.inmem.rl)
            log.committed = st.commit
            log.processed = st.commit
            for nid, (match, _next) in st.peers.items():
                # observers/witnesses enroll as flagged peers; restore
                # their progress into the matching membership dict
                rp = (
                    r.remotes.get(nid)
                    or r.observers.get(nid)
                    or r.witnesses.get(nid)
                )
                if rp is None:
                    continue
                rp.match = match
                rp.next = match + 1
                rp.state = RemoteState.RETRY
                rp.active = True
            selfrp = r.remotes.get(self.node_id)
            if selfrp is not None:
                selfrp.try_update(st.last_index)
            r.reset_match_value_array()
            self.peer.prev_state = State(
                term=st.term, vote=st.vote, commit=st.commit
            )
            # refresh the Python-side persisted-state caches to the records
            # the native core wrote (else a later suppressed write would
            # leave disk stale, or a redundant one would be re-issued)
            self.logdb.refresh_cached_state(
                self.cluster_id,
                self.node_id,
                st.term,
                st.vote,
                st.commit,
                st.last_index,
            )
            # the device quorum row (if the TPU plugin is live) went stale
            # while the native core advanced commits; rebuild it
            coord = getattr(self, "quorum_coordinator", None)
            if coord is not None:
                coord.register(self)
            # pending native ReadIndex contexts died with the native
            # group; re-drive them through the scalar protocol (duplicate
            # confirmations are harmless) so in-flight reads don't strand
            if r.is_leader():
                for ctx in self.pending_reads.pending_ctxs():
                    self._read_index(ctx)
            if contact_lost or reenroll_backoff:
                # the native clock already waited out the election window
                # with zero leader contact — without this the group would
                # re-enroll (leader_id still set, log quiescent), reset the
                # native contact clock and ping-pong forever instead of
                # ever campaigning.  reenroll_backoff ejects (commit-stall
                # watchdog, inbound REQUEST_VOTE) need the same grace: on
                # a netsplit follower the watchdog fires BEFORE the
                # contact-loss eject (the readers_live gate defers contact
                # loss while no bytes flow anywhere), and a peer's vote
                # request is dropped by the §6 lease while the frozen
                # election clock still reads "leader heard recently" — in
                # both shapes an instant re-enroll resets every native
                # liveness clock and the group ping-pongs forever with
                # the election clock never running (the partition_tcp
                # no-leader stall)
                import time as _time

                self._next_enroll_try = _time.monotonic() + 2.0 * (
                    2 * self.config.election_rtt * self.tick_millisecond
                ) / 1000.0
                if contact_lost and r.is_follower():
                    # zero leader contact is proven; scalar raft may
                    # campaign immediately.  NOT on the backoff-only
                    # shapes: the leader may be alive (flow-control
                    # wedge), and the grace window alone lets the scalar
                    # clock age past the vote-drop lease — heartbeats
                    # keep resetting it if the leader is actually there
                    r.election_tick = r.randomized_election_timeout
        self.nh.engine.set_step_ready(self.cluster_id)

    def _handle_events(self, extra_ticks: int = 0) -> None:
        self._handle_received_messages(extra_ticks)
        self._handle_read_index()
        self._handle_config_change()
        self._handle_proposals()
        self._handle_leader_transfer()
        self._handle_snapshot_request()

    def _handle_received_messages(self, extra_ticks: int = 0) -> None:
        self._process_messages(self.mq.get(), extra_ticks)

    def _process_messages(self, msgs, extra_ticks: int = 0) -> None:
        # lazy catch-up ticks represent time that elapsed BEFORE this step
        # — deliver them ahead of the messages so term-filter guards that
        # read the election clock (the section-6 vote-drop lease,
        # raft.py drop_request_vote_from_high_term_node) compare a current
        # clock, exactly as the offload_tick_* handlers do
        if extra_ticks:
            self._tick(
                extra_ticks, tracker_count=self._tracker_ticks(extra_ticks)
            )
        ticks = 0
        for m in msgs:
            if m.type == MT.LOCAL_TICK:
                ticks += 1
            elif m.type == MT.QUIESCE:
                if self.dev_quiesce:
                    self._sleep(own=False)
                else:
                    self.quiesce_mgr.try_enter_quiesce()
            elif m.type == MT.UNREACHABLE:
                # local report from the transport, not a wire message
                # (reference node.go:1257-1286 handleReceivedMessages)
                self.peer.report_unreachable_node(m.from_)
            elif m.type == MT.SNAPSHOT_STATUS:
                self.peer.report_snapshot_status(m.from_, m.reject)
            elif m.type == MT.ELECTION:
                # local campaign request (request_campaign); must go through
                # Peer.campaign — Peer.handle rejects local message types.
                # Only honored when locally injected: a wire message must
                # not be able to force a follower to campaign against a
                # healthy leader (reference treats ELECTION as local-only)
                if m.from_ == self.node_id:
                    self._activity(m.type)
                    self.peer.campaign()
            else:
                if self.config.quiesce:
                    self._activity(m.type)
                if m.type == MT.INSTALL_SNAPSHOT and m.snapshot is not None:
                    self._handle_install_snapshot(m)
                else:
                    self.peer.handle(m)
        if ticks:
            obs = self.replica_obs
            if obs is not None:
                obs.scalar_ticks(ticks)
            self._tick(ticks)
        if self.quiesce_mgr.just_entered_quiesce():
            self._broadcast_quiesce()

    def _handle_install_snapshot(self, m: Message) -> None:
        # record arrival; raft decides whether to accept (restore path)
        obs = self.replica_obs
        if obs is not None:
            obs.install("received")
        self.peer.handle(m)

    # ---- a group's sleep (Config.quiesce) ----
    #
    # Scalar engine: ``quiesce_mgr`` (quiesce.py), ticked by ``_tick``.
    # Device tick plane (``dev_quiesce``): the row's idle clock and its
    # sleep are the tick kernel's; the host keeps ``_asleep`` beside it
    # and tells the row what the kernel cannot see: activity (which also
    # ends a sleep) and a peer's QUIESCE.  All under raftMu.

    def quiesced(self) -> bool:
        """Whether this replica sleeps."""
        if self.dev_quiesce:
            return self._asleep
        return self.quiesce_mgr.quiesced()

    def _activity(self, msg_type) -> Optional[str]:
        """A message or a request reached this replica
        (``QuiesceManager.record_activity``'s rule: anything but a
        heartbeat or its response while awake resets the idle clock,
        anything at all ends a sleep).  Where it woke the replica, the
        role it woke in (``leader`` / ``follower``), else None.  A woken
        replica's election clock then starts from the wake, not from the ticks
        the sleep counted, so a woken follower does not campaign against
        a leader it has not heard from YET (a leader that died during the
        sleep is replaced one election timeout after the wake)."""
        if not self.dev_quiesce:
            self.quiesce_mgr.record_activity(msg_type)
            return None
        hb = msg_type == MT.HEARTBEAT or msg_type == MT.HEARTBEAT_RESP
        coord = self.quorum_coordinator
        if not self._asleep:
            if not hb:
                self._active_tick = self.nh.tick_count
                coord.quiesce_activity(self.cluster_id)
            return None
        if hb and (
            self.nh.tick_count - self._asleep_tick < self.config.election_rtt
        ):
            # new to its sleep: a heartbeat that was on its way when the
            # group went to sleep (the leader falls asleep a message
            # later) wakes nobody, or the woken follower would wait out
            # an election timeout beside a sleeping leader and depose it
            return None
        self._asleep = False
        self._active_tick = self.nh.tick_count
        r = self.peer.raft
        role = "leader" if r.is_leader() else "follower"
        if role == "follower":
            r.election_tick = 0
        coord.quiesce_woke(self.cluster_id)
        return role

    def _sleep(self, own: bool) -> None:
        """This replica goes to sleep: its own row's idle clock crossed
        the threshold (``own``: the peers are told, once) or a peer said
        so (the row is put to sleep with the next round)."""
        if self._asleep:
            return
        self._asleep = True
        self._asleep_tick = self.nh.tick_count
        self.quorum_coordinator.quiesce_slept(self.cluster_id, own)
        if own:
            self._broadcast_quiesce()

    def _broadcast_quiesce(self) -> None:
        for nid in list(self.peer.raft.remotes):
            if nid != self.node_id:
                self.nh.send_message(
                    Message(
                        type=MT.QUIESCE,
                        cluster_id=self.cluster_id,
                        from_=self.node_id,
                        to=nid,
                    )
                )

    def _tick(self, count: int, tracker_count: Optional[int] = None) -> None:
        for _ in range(count):
            self.current_tick += 1
            self.quiesce_mgr.increase_quiesce_tick()
            if self.quiesce_mgr.quiesced():
                self.peer.quiesced_tick()
            else:
                self.peer.tick()
        self._tick_trackers(count if tracker_count is None else tracker_count)
        self._update_leader_info()

    def _tick_trackers(self, count: int) -> None:
        """Advance the pending-request timeout clocks only — the raft clock
        itself is owned by the native core while the group is enrolled."""
        for _ in range(count):
            self.pending_proposals.tick()
            self.pending_reads.tick()
            self.pending_config_change.tick()
            self.pending_snapshot.tick()
            self.pending_leader_transfer.tick()

    def _update_leader_info(self) -> None:
        lid = self.peer.raft.leader_id
        if lid != self.leader_id:
            self.leader_id = lid

    def _handle_proposals(self) -> None:
        entries = self.entry_q.get()
        if entries:
            woke = self._activity(MT.PROPOSE) if self.config.quiesce else None
            self.peer.propose_entries(entries)
            tr = self.tracer
            if tr is not None:
                tr.mark_entries(entries, "raft_step")
                if woke is not None:
                    tr.mark_woke(entries, woke)

    def _handle_read_index(self) -> None:
        if self.pending_reads.peep():
            ctx = self.pending_reads.next_ctx()
            if self.pending_reads.take_pending(ctx):
                woke = (
                    self._activity(MT.READ_INDEX) if self.config.quiesce
                    else None
                )
                self._read_index(ctx)
                if woke is not None and self.tracer is not None:
                    self.pending_reads.trace_woke(ctx, woke)

    def _read_index(self, ctx: SystemCtx) -> None:
        """Hand a taken ReadIndex ctx to raft (under raftMu).  Where the
        tracer is on and the batch holds a sampled request, the READ_INDEX
        carries that request's identifier to the leader's step, which
        follows the ctx through its read plane (ISSUE 39)."""
        wire = None
        if self.tracer is not None:
            wire = self.pending_reads.trace_ctx(
                ctx, "local" if self.peer.raft.is_leader() else "forwarded"
            )
        self.peer.read_index(ctx, wire)

    def _handle_config_change(self) -> None:
        cc = self.pending_config_change.take()
        if cc is not None:
            rs = self.pending_config_change.pending()
            key = rs.key if rs is not None else 0
            self._activity(MT.CONFIG_CHANGE_EVENT)
            self.peer.propose_config_change(cc, key)

    def _handle_leader_transfer(self) -> None:
        target = self.pending_leader_transfer.take()
        if target is not None:
            self.peer.request_leader_transfer(target)
            # completion is observed via leader change, not a raft ack
            self.pending_leader_transfer.notify(
                RequestResult(code=RequestResultCode.COMPLETED)
            )

    def _handle_snapshot_request(self) -> None:
        req = self.pending_snapshot.take()
        if req is not None:
            self.to_apply.enqueue(
                Task(
                    cluster_id=self.cluster_id,
                    node_id=self.node_id,
                    save=True,
                    ss_request=req,
                    queued_at=self._obs_now(),
                )
            )
            self.nh.engine.set_apply_ready(self.cluster_id)

    # ---- update execution (reference processRaftUpdate node.go:1058) ----

    def process_dropped(self, ud: Update) -> None:
        for e in ud.dropped_entries:
            if e.is_config_change():
                # reference node.go: dropped config changes notify their
                # own single-slot tracker so Sync* wrappers can retry
                rs = self.pending_config_change.pending()
                if rs is not None and rs.key == e.key:
                    self.pending_config_change.notify(
                        RequestResult(code=RequestResultCode.DROPPED)
                    )
            else:
                self.pending_proposals.dropped(e.key)
        if ud.dropped_read_indexes:
            self.pending_reads.dropped(ud.dropped_read_indexes)

    def send_replicate_messages(self, ud: Update) -> None:
        """Replicate messages go out BEFORE the fsync (thesis §10.2.1,
        reference ``execengine.go:954-961``)."""
        ra = self.replattr
        if ra is not None and self.fastlane is None:
            # replication tracing (ISSUE 14): sampled proposals' fan-out
            # messages get a per-peer ReplTrace context and open a
            # commit record.  Gated off under the native fast lane —
            # its C readers own the wire and do not speak the trace
            # extension (enrolled groups bypass this path anyway).
            tr = self.tracer
            if tr is not None:
                ra.attach_sends(self.cluster_id, ud.messages, tr)
        for m in ud.messages:
            if m.type == MT.REPLICATE:
                self.nh.send_message(m)

    def process_raft_update(self, ud: Update) -> bool:
        """The half of an update that waits for its persist; True where it
        handed committed entries to the apply queue (they were not handed
        over before the persist: ``apply_committed``)."""
        # a restore update can carry BOTH the snapshot and the log tail
        # past it: the snapshot must move the logreader window FIRST or the
        # append trips the gap check and the committer retries the same
        # update forever (soak-caught: restarted follower wedged with
        # "gap in log" after a streamed snapshot install).  Reference
        # ordering: node.go applySnapshotAndUpdate runs the snapshot half
        # before entry processing.
        if not is_empty_snapshot(ud.snapshot):
            try:
                self.logreader.apply_snapshot(ud.snapshot)
            except Exception as e:  # SnapshotOutOfDate
                plog.warning("%s apply_snapshot: %s", self.describe(), e)
        self.logreader.append(ud.entries_to_save)
        for m in ud.messages:
            if m.type == MT.REPLICATE:
                continue
            if m.type == MT.INSTALL_SNAPSHOT:
                obs = self.replica_obs
                if obs is not None:
                    obs.install("sent")
                self.nh.send_snapshot_message(m)
            else:
                ctx = m.trace
                if ctx is not None and ctx.t_append:
                    # follower half of a sampled replication (ISSUE 14):
                    # this loop runs AFTER the committer's fsync, so the
                    # appended entries the ack covers are durable here —
                    # stamp the fsync point and the ack hand-off, and
                    # file the leg locally so this host's dump renders
                    # the follower side of the flow
                    now = time.time()
                    if not ctx.t_fsync:
                        ctx.t_fsync = now
                    if not ctx.t_ack:
                        ctx.t_ack = now
                        tr = self.tracer
                        if tr is not None:
                            tr.add_repl_leg(ctx)
                self.nh.send_message(m)
        if ud.ready_to_reads:
            self.pending_reads.add_ready(ud.ready_to_reads)
            # devsm groups release at the device watermark too (floor is
            # 0 everywhere else — the max is the identity then)
            self.pending_reads.applied(
                max(self.sm.get_last_applied(), self.devsm_release_floor)
            )
        handed = self._apply_snapshot_and_update(ud)
        self._save_snapshot_required()
        return handed

    def _apply_snapshot_and_update(self, ud: Update) -> bool:
        if not is_empty_snapshot(ud.snapshot):
            ss = ud.snapshot
            plog.info(
                "%s installing snapshot index %d", self.describe(), ss.index
            )
            # the logreader window already moved at the top of
            # process_raft_update (before the entry append)
            self.to_apply.enqueue(
                Task(
                    cluster_id=self.cluster_id,
                    node_id=self.node_id,
                    recover=True,
                    ss=ss,
                    index=ss.index,
                )
            )
            self.nh.engine.set_apply_ready(self.cluster_id)
        handed = self.apply_committed(ud)
        if ud.more_committed_entries:
            self.nh.engine.set_step_ready(self.cluster_id)
        return handed

    def apply_committed(self, ud: Update) -> bool:
        """Hand ``ud``'s committed entries to the apply queue and wake an
        apply worker; True where any went.  Only entries above the highest
        index already handed over go, so the engine may call this before the
        update's persist (reference ``execengine.go`` ``processSteps``:
        ``applySnapshotAndUpdate(..., true)`` for an update whose
        ``fast_apply`` holds: what it commits an EARLIER update made
        durable) and ``process_raft_update`` again after it, and a persist
        that failed, whose update ``get_update`` produces again, applies
        nothing twice."""
        ents = ud.committed_entries
        if not ents or ents[-1].index <= self._applied_handed:
            return False
        if ents[0].index <= self._applied_handed:
            ents = ents[self._applied_handed + 1 - ents[0].index:]
        self._applied_handed = ents[-1].index
        self.to_apply.enqueue(
            Task(
                cluster_id=self.cluster_id,
                node_id=self.node_id,
                entries=ents,
            )
        )
        self.nh.engine.set_apply_ready(self.cluster_id)
        return True

    def persists_commit_alone(self, ud: Update) -> bool:
        """True where the hard state ``ud`` carries differs in nothing but
        ``commit`` from the last one this replica's raft gave out (term and
        vote, which must be durable before anything that follows from them
        leaves, are those an earlier update persisted).  The step worker
        asks right after ``step_node``, while nothing of the group is in
        flight."""
        peer = self.peer
        if peer is None:
            return False
        prev = peer.prev_state
        return ud.state.term == prev.term and ud.state.vote == prev.vote

    def _save_snapshot_required(self) -> None:
        """Auto snapshot every ``snapshot_entries`` applied (reference
        ``node.go:605`` ``saveSnapshotRequired``)."""
        if not self.snapshot_due():
            return
        # held until the queued PERIODIC save completes (_save_snapshot
        # releases it), so duplicate save tasks never pile up
        obs = self.replica_obs
        if not self._snapshotting.acquire(blocking=False):
            if obs is not None:
                # one more snapshot_entries applied behind a save still
                # queued or running: that snapshot is skipped
                applied = self.sm.get_last_applied()
                if applied >= self._ss_refuse_from:
                    self._ss_refuse_from = (
                        applied + self.config.snapshot_entries
                    )
                    obs.save_refused()
            return
        if obs is not None:
            self._ss_refuse_from = (
                self.sm.get_last_applied() + self.config.snapshot_entries
            )
        self.to_apply.enqueue(
            Task(
                cluster_id=self.cluster_id,
                node_id=self.node_id,
                save=True,
                ss_request=SSRequest(type=SSReqType.PERIODIC),
                queued_at=self._obs_now(),
            )
        )
        self.nh.engine.set_apply_ready(self.cluster_id)

    def _obs_now(self) -> float:
        """``perf_counter`` while the replica instruments are attached
        (a snapshot task's ``queued_at``), else 0.0."""
        return time.perf_counter() if self.replica_obs is not None else 0.0

    def commit_raft_update(self, ud: Update) -> None:
        with self.raft_mu:
            if self.peer is not None:
                self.peer.commit(ud)
            self._update_out = False

    # ---- apply path (reference processApplies / handleTask) ----

    def handle_apply_tasks(self) -> None:
        # serialized: the engine's apply workers already serialize per
        # group among themselves, but the fast lane's apply pump calls
        # this inline too — an unsynchronized drain would interleave
        # get_all() batches and apply entries out of order
        with self._apply_serial:
            self._handle_apply_tasks_locked()

    def _handle_apply_tasks_locked(self) -> None:
        tasks = self.to_apply.get_all()
        for t in tasks:
            if self._stopped.is_set():
                return
            if t.save:
                # snapshot saves run on the dedicated pool (reference
                # execengine.go:240-635) so a slow user save_snapshot never
                # blocks the other groups sharing this apply worker; the
                # regular-SM save/update lock in rsm.StateMachine keeps the
                # image consistent against concurrent applies
                self.nh.engine.submit_snapshot(
                    lambda t=t: self._save_snapshot(t)
                )
            elif t.stream:
                self.nh.engine.submit_snapshot(
                    lambda t=t: self._stream_snapshot(t)
                )
            elif t.recover:
                self._recover_from_snapshot(t)
            else:
                self.sm.handle([t])
                applied = self.sm.get_last_applied()
                with self.raft_mu:
                    if self.peer is not None:
                        self.peer.notify_raft_last_applied(applied)
                self.sm.set_batched_last_applied(applied)
                self.pending_reads.applied(applied)
                if self._natsm_attached and self.fastlane is not None:
                    # lift the native-SM attach barrier: the native plane
                    # applies only past what Python has applied
                    self.fastlane.nat.note_applied(self.cluster_id, applied)
                self.nh.engine.set_step_ready(self.cluster_id)

    def _try_capture_save(self, req: SSRequest):
        """Snapshot an ENROLLED native-SM group from a consistent image
        captured by the native core (``natr_capture_sm``) — the no-eject
        periodic-snapshot path.  Returns ``(ss, env)`` or ``None`` to fall
        back to the scalar ``sm.save`` flow (which requires the group to
        be off the fast lane).  Exported requests stay scalar: the export
        flow's env/finalize handling expects the standard savable."""
        fl = self.fastlane
        if (
            fl is None
            or not self.fast_lane
            or not self._natsm_attached
            or req.exported
            or self.sm.on_disk
        ):
            return None
        # membership must be captured atomically with the capture index:
        # snapshot it BEFORE the native capture, then verify the
        # config-change id did not move while the capture ran (a racing
        # fast_eject + config-change apply in that window would otherwise
        # label the image with membership newer than its index).  The
        # pre-capture view is consistent with the captured index exactly
        # when the ccid is unchanged — config changes only apply on the
        # Python plane, which the enrolled lane holds off.
        pre_members = self.sm.get_membership()
        cap = fl.nat.capture_sm(self.cluster_id)
        if cap is None or (
            self.sm.get_membership().config_change_id
            != pre_members.config_change_id
        ):
            # cannot capture (no save fn on the attached SM / attach
            # barrier still in flight / mid-eject), or membership moved
            # under the capture: restore the pre-capture behavior —
            # leave the lane FIRST, because a scalar sm.save() while
            # native applies keep mutating the shared state would label
            # the image with a stale index (double-apply after recovery)
            if self.fast_lane:
                self._count_eject("snapshot-due")
                self.fast_eject()
            return None
        index, term, kv_image, sess_image = cap
        # entries through the captured index are durable (native applies
        # only run past the local fsync watermark) but the Python-side
        # LogReader window froze at enrollment; extend it (monotonic,
        # atomic vs a racing fast_eject) so create_snapshot/compaction
        # accept the new snapshot index
        self.logreader.extend_to(index)
        return self.sm.save_from_capture(
            req, index, term, kv_image, sess_image, membership=pre_members
        )

    def _snapshot_scope(self, t: Task, kind: str):
        """The ``snapshot_save`` span and annotation around one snapshot
        task (``ReplicaObs.save``), or None while the instruments are
        off."""
        obs = self.replica_obs
        if obs is None:
            return None
        return obs.save(
            kind=kind, cluster_id=self.cluster_id, node_id=self.node_id,
            queued_at=t.queued_at,
            entries_since=(
                self.sm.get_last_applied() - self.sm.get_snapshot_index()
            ),
            snapshot_entries=self.config.snapshot_entries,
        )

    def _save_snapshot(self, t: Task) -> None:
        scope = self._snapshot_scope(
            t,
            "periodic" if t.ss_request.type == SSReqType.PERIODIC
            else "requested",
        )
        with (scope if scope is not None else _OFF):
            self._save_snapshot_task(t, scope)

    def _save_snapshot_task(self, t: Task, scope) -> None:
        req = t.ss_request
        # only user-initiated requests may resolve the pending-snapshot slot;
        # PERIODIC failures must not complete an unrelated user request
        user_req = req.type in (SSReqType.USER_REQUESTED, SSReqType.EXPORTED)
        try:
            try:
                cap = self._try_capture_save(req)
                ss, env = (
                    cap if cap is not None else self.sm.save(req, scope)
                )
                if scope is not None:
                    # a regular state machine's save holds the group's
                    # applies out while the image is captured
                    # (rsm.StateMachine._update_mu: update_lock_ms)
                    scope.lap("sm_save")
            except SnapshotIgnored:
                if user_req:
                    self.pending_snapshot.notify(
                        RequestResult(code=RequestResultCode.REJECTED)
                    )
                return
            except Exception as e:
                plog.error("%s snapshot save failed: %s", self.describe(), e)
                if user_req:
                    self.pending_snapshot.notify(
                        RequestResult(code=RequestResultCode.ABORTED)
                    )
                return
            if req.exported:
                # promote tmp → final inside the user's export dir; keep the
                # flag file — ImportSnapshot reads it (tools/import.go:130)
                try:
                    env.finalize_snapshot()
                except Exception as e:
                    plog.error("%s export finalize failed: %s", self.describe(), e)
                    env.remove_tmp_dir()
                    self.pending_snapshot.notify(
                        RequestResult(code=RequestResultCode.ABORTED)
                    )
                    return
                self.pending_snapshot.notify(
                    RequestResult(
                        code=RequestResultCode.COMPLETED, snapshot_index=ss.index
                    )
                )
                return
            try:
                # the rename, the root's fsync, then ONE LogDB batch: the
                # record and the deletes of the records beyond the three
                # newest
                stale = self.snapshotter.commit(ss, env)
                if scope is not None:
                    scope.logdb_commit()
                    scope.lap("commit")
                self._publish_event(SystemEventType.SNAPSHOT_CREATED, index=ss.index)
            except FileExistsError:
                env.remove_tmp_dir()
                if user_req:
                    self.pending_snapshot.notify(
                        RequestResult(code=RequestResultCode.REJECTED)
                    )
                return
            # the orders a crash and a lagging follower need, as the
            # reference keeps them: the reader learns of the snapshot only
            # once its record is durable, and BEFORE its marker moves (a
            # marker ahead of the reader's snapshot leaves a follower behind
            # it neither entries nor an image to be sent); the reader
            # compacts before the LogDB drops the entries, in a second
            # batch, and its refusal drops none; a stale record went before
            # its directory goes
            try:
                self.logreader.create_snapshot(ss)
            except Exception as e:
                plog.warning("%s create_snapshot: %s", self.describe(), e)
                # nothing was compacted, but the stale records are gone
                self.snapshotter.remove_dirs(stale)
                if user_req:
                    self.pending_snapshot.notify(
                        RequestResult(code=RequestResultCode.ABORTED)
                    )
                return
            with (scope.saved(ss, env) if scope is not None else _OFF):
                self._compact_log(ss, req, scope)
                self.snapshotter.remove_dirs(stale)
            self._publish_event(SystemEventType.SNAPSHOT_COMPACTED, index=ss.index)
            if req.type == SSReqType.USER_REQUESTED:
                self.pending_snapshot.notify(
                    RequestResult(
                        code=RequestResultCode.COMPLETED, snapshot_index=ss.index
                    )
                )
        finally:
            if req.type == SSReqType.PERIODIC:
                self._snapshotting.release()

    # ---- on-disk SM snapshot streaming (reference node.go:718-738) ----

    def push_stream_snapshot_request(self, to: int) -> None:
        """Queue a stream-to-follower task (reference
        ``pushStreamSnapshotRequest``)."""
        self.to_apply.enqueue(
            Task(
                cluster_id=self.cluster_id,
                node_id=self.node_id,
                stream=True,
                stream_to=to,
                ss_request=SSRequest(type=SSReqType.STREAMING),
                queued_at=self._obs_now(),
            )
        )
        self.nh.engine.set_apply_ready(self.cluster_id)

    def _stream_snapshot(self, t: Task) -> None:
        scope = self._snapshot_scope(t, "stream")
        with (scope if scope is not None else _OFF):
            self._stream_snapshot_task(t)

    def _stream_snapshot_task(self, t: Task) -> None:
        to = t.stream_to
        sink = self.nh.transport.get_stream_sink(self.cluster_id, to)
        if sink is None:
            plog.warning(
                "%s no stream sink for %d (unreachable/at capacity)",
                self.describe(), to,
            )
            # report failure so the remote leaves Snapshot state eventually
            self.nh._snapshot_status(self.cluster_id, to, True)
            return
        try:
            self.sm.stream(sink, to, self.nh.nhconfig.get_deployment_id())
        except Exception as e:  # noqa: BLE001
            plog.error("%s streaming to %d failed: %s", self.describe(), to, e)
            sink.stop()

    def _compact_log(self, ss: Snapshot, req: SSRequest, scope) -> None:
        """Reference ``node.go:689-716``: keep ``compaction_overhead``
        entries behind the snapshot."""
        overhead = (
            req.compaction_overhead
            if req.override_compaction_overhead
            else self.config.compaction_overhead
        )
        if ss.index <= overhead:
            return
        compact_to = ss.index - overhead
        try:
            self.logreader.compact(compact_to)
        except Exception:
            return
        self.logdb.remove_entries_to(self.cluster_id, self.node_id, compact_to)
        if scope is not None:
            scope.logdb_commit()
        with self._compacted_to_mu:
            self._compacted_to = compact_to
        obs = self.replica_obs
        if obs is not None:
            obs.compaction()
        self._publish_event(SystemEventType.LOG_COMPACTED, index=compact_to)

    def _recover_from_snapshot(self, t: Task) -> None:
        if t.initial:
            # restart path: newest local snapshot, if any
            ss = self.snapshotter.get_most_recent_snapshot()
            if ss is not None and not ss.is_empty():
                t = Task(
                    cluster_id=self.cluster_id,
                    node_id=self.node_id,
                    recover=True,
                    ss=ss,
                )
                self.sm.recover(t)
                self._publish_event(
                    SystemEventType.SNAPSHOT_RECOVERED, index=ss.index
                )
            if self.sm.on_disk:
                self.sm.open()
            # reference node.go:1382-1410 setInitialStatus: raft must learn
            # the recovered applied index or has_config_change_to_apply()
            # (committed > applied) suppresses elections forever on a node
            # whose log tail is empty (e.g. after ImportSnapshot repair)
            applied = self.sm.get_last_applied()
            if applied:
                with self.raft_mu:
                    if self.peer is not None:
                        self.peer.notify_raft_last_applied(applied)
                self.sm.set_batched_last_applied(applied)
                self.pending_reads.applied(applied)
            self._initialized.set()
            self._publish_event(SystemEventType.NODE_READY)
            self.nh.engine.set_step_ready(self.cluster_id)
            return
        try:
            self.sm.recover(t)
        except Exception as e:
            plog.error("%s recover failed: %s", self.describe(), e)
            raise
        if t.ss is not None:
            self._publish_event(
                SystemEventType.SNAPSHOT_RECOVERED, index=t.ss.index
            )
        applied = self.sm.get_last_applied()
        with self.raft_mu:
            if self.peer is not None:
                self.peer.notify_raft_last_applied(applied)
        self.sm.set_batched_last_applied(applied)
        self.nh.engine.set_step_ready(self.cluster_id)

    # ---- rsm.INodeProxy callbacks ----

    def node_ready(self) -> None:
        self.nh.engine.set_step_ready(self.cluster_id)

    def apply_update(
        self,
        entry: Entry,
        result: Result,
        rejected: bool,
        ignored: bool,
        notify_read: bool,
    ) -> None:
        if not ignored and entry.key:
            self.pending_proposals.applied(
                entry.key, entry.client_id, entry.series_id, result, rejected
            )

    def apply_config_change(
        self, cc: ConfigChange, key: int, rejected: bool
    ) -> None:
        with self.raft_mu:
            if self.peer is None:
                return
            if rejected:
                self.peer.reject_config_change()
            else:
                self.peer.apply_config_change(cc)
                self._on_config_change_applied(cc)
                self._publish_event(
                    SystemEventType.MEMBERSHIP_CHANGED, from_=cc.node_id
                )
        rs = self.pending_config_change.pending()
        if rs is not None and rs.key == key and key != 0:
            code = (
                RequestResultCode.REJECTED
                if rejected
                else RequestResultCode.COMPLETED
            )
            self.pending_config_change.notify(RequestResult(code=code))

    def _on_config_change_applied(self, cc: ConfigChange) -> None:
        if cc.type in (
            ConfigChangeType.ADD_NODE,
            ConfigChangeType.ADD_OBSERVER,
            ConfigChangeType.ADD_WITNESS,
        ):
            self.nh.node_registry.add(self.cluster_id, cc.node_id, cc.address)
        elif cc.type == ConfigChangeType.REMOVE_NODE:
            self.nh.node_registry.remove(self.cluster_id, cc.node_id)
            if cc.node_id == self.node_id:
                self._delete_required = True

    def restore_remotes(self, ss: Snapshot) -> None:
        with self.raft_mu:
            if self.peer is not None:
                self.peer.restore_remotes(ss)
        for nid, addr in ss.membership.addresses.items():
            if nid != self.node_id:
                self.nh.node_registry.add(self.cluster_id, nid, addr)

    def should_stop(self) -> bool:
        return self._stopped.is_set()

    # ---- status / shutdown ----

    def get_membership(self) -> Membership:
        return self.sm.get_membership()

    def get_leader_id(self):
        with self.raft_mu:
            if self.peer is None:
                return 0, False
            lid = self.peer.raft.leader_id
            return lid, lid != 0

    def is_leader(self) -> bool:
        with self.raft_mu:
            return self.peer is not None and self.peer.raft.is_leader()

    def lease_status(self) -> Optional[dict]:
        """Leader-lease snapshot (ISSUE 10): ``None`` when the group runs
        without ``Config.read_lease``; else the lease's plain-int stats
        plus whether it is currently valid and its remaining ticks —
        read under raftMu so the view is consistent."""
        with self.raft_mu:
            if self.peer is None:
                return None
            r = self.peer.raft
            lease = r.lease
            if lease is None:
                return None
            d = lease.stats()
            remaining = 0
            if r.is_leader():
                remaining = lease.remaining(
                    r.tick_count, r.quorum(), r.voting_members(), r.node_id
                )
            d["held"] = remaining > 0
            d["remaining_ticks"] = max(remaining, 0)
            return d

    def health_snapshot(self, lock_timeout: float = 0.05) -> dict:
        """One health-sample row for this group (obs/health.py, ISSUE
        13): raft plane (state/term/leader/commit/applied), request
        pressure, reachability (check-quorum leaders), device commit
        watermark, lease and devsm status.  Low-rate caller contract:
        ``raft_mu`` is acquired with ``lock_timeout`` (``<= 0`` =
        non-blocking) — a contended group reports ``busy: True`` with
        only the lock-free fields rather than stalling the tick worker
        behind a long step.  The SAMPLER owns the whole-pass budget:
        it shrinks ``lock_timeout`` as its deadline approaches, so a
        host full of contended groups degrades to busy rows instead of
        n_groups × timeout of tick-worker stall."""
        self._health_track = True
        d = {
            "node_id": self.node_id,
            "pending_proposals": self.pending_proposals.has_pending(),
            "pending_reads": self.pending_reads.has_pending(),
            "applied": self.sm.get_last_applied(),
            "dev_commit": self._dev_commit_seen,
            "fast_lane": self.fast_lane,
        }
        plane = self.devsm_plane
        if plane is not None:
            dv = plane.health_snapshot(self.cluster_id)
            if dv is not None:
                dv["release_floor"] = self.devsm_release_floor
                d["devsm"] = dv
        if lock_timeout > 0:
            acquired = self.raft_mu.acquire(timeout=lock_timeout)
        else:
            acquired = self.raft_mu.acquire(blocking=False)
        if not acquired:
            d["busy"] = True
            return d
        try:
            peer = self.peer
            if peer is None:
                d["busy"] = True
                return d
            r = peer.raft
            d["state"] = r.state.name
            d["term"] = r.term
            d["leader_id"] = r.leader_id
            d["committed"] = r.log.committed
            voters = r.voting_members()
            d["voters"] = len(voters)
            d["quorum"] = r.quorum()
            if r.is_leader() and r.check_quorum:
                # reachability from the check-quorum activity flags: set
                # on every response, cleared once per election window —
                # only meaningful where that refresh loop runs (a
                # non-check-quorum leader's flags latch True forever)
                d["reachable"] = sum(
                    1
                    for nid, rp in voters.items()
                    if nid == r.node_id or rp.is_active()
                )
                # the ids behind the count: quorum_at_risk actuation
                # (obs/recovery.py) evicts exactly these
                d["unreachable_ids"] = [
                    nid
                    for nid, rp in voters.items()
                    if nid != r.node_id and not rp.is_active()
                ]
            lease = r.lease
            if lease is not None:
                ls = lease.stats()
                remaining = 0
                if r.is_leader():
                    remaining = lease.remaining(
                        r.tick_count, r.quorum(), voters, r.node_id
                    )
                ls["held"] = remaining > 0
                ls["remaining_ticks"] = max(remaining, 0)
                d["lease"] = ls
        finally:
            self.raft_mu.release()
        return d

    def request_compaction(self) -> threading.Event:
        """User-requested LogDB compaction up to the last auto-compacted
        watermark (reference ``node.go:912-927`` requestCompaction —
        swap-to-zero, so back-to-back requests don't recompact).  Raises
        RejectedError when nothing has been compacted since the last
        request."""
        with self._compacted_to_mu:
            compact_to, self._compacted_to = self._compacted_to, 0
        if compact_to == 0:
            from .requests import RejectedError

            raise RejectedError("nothing to compact")
        # the compaction worker publishes LOGDB_COMPACTED on completion
        # (logdb.on_compaction, wired by NodeHost)
        return self.logdb.compact_entries_to(
            self.cluster_id, self.node_id, compact_to
        )

    def describe(self) -> str:
        return f"node {self.cluster_id}:{self.node_id}"

    def requested_stop(self) -> bool:
        return self._stopped.is_set()

    def stop(self) -> None:
        if self.fast_lane:
            # clean shutdown: flush the native WAL tail and reclaim the
            # scalar state (a crash without this is still raft-safe — only
            # unreplicated, unacked proposals are lost)
            try:
                self.fast_eject()
            except Exception:
                plog.exception("%s fast-lane eject on stop", self.describe())
        self._stopped.set()
        self.sm.stopc.stop()
        self.entry_q.close()
        self.mq.close()
        self.pending_proposals.close()
        self.pending_reads.close()
        self.pending_config_change.close()
        self.pending_snapshot.close()
        self.pending_leader_transfer.close()
        self.sm.offloaded()
