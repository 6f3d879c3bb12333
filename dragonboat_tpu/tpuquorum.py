"""TPU quorum plugin: routes the live runtime's hot path through the
batched device engine.

This is the plugin boundary BASELINE.json's north star calls
``plugin/tpuquorum`` (selected via ``ExpertConfig.quorum_engine``): with it
enabled, the per-group scalar work the reference does inside
``processSteps`` — ReplicateResp ack tallying, matchIndex quorum reduction
(``raft.go:888-909`` ``tryCommit``) and candidate vote tallying
(``raft.go:1062-1080``) — is staged as compact event batches and computed
for ALL groups in one fused device dispatch per coordinator round
(:mod:`dragonboat_tpu.ops`).  With it disabled, nothing below runs and the
scalar path is untouched.

Division of labor (SURVEY.md §7 design pivot):
- dense 99% paths on device: ack ingest (scatter-max), per-group
  kth-largest commit reduction, vote tally vs quorum
- rare paths stay scalar on host and re-sync their row: leadership
  transitions, membership change, snapshot restore, index rebase
- commit/election *effects* are applied back under each node's raftMu
  with the scalar guards intact (``log.try_commit(q, term)`` re-checks the
  term rule), so a stale device result is rejected, never applied

Determinism: the device commit index is the same ``kth_largest(match)``
the scalar sort computes, and the term guard is re-applied scalar-side —
commit outputs are bit-identical to the pure-scalar path (differential
tests in ``tests/test_tpuquorum.py`` + ``tests/test_ops_quorum.py``).
"""
from __future__ import annotations

import collections
import os
import threading
import time
from typing import Dict, Optional, TYPE_CHECKING

from . import obs as _obs
from .logger import get_logger
from .obs.instruments import HB_SINGLE_CAUSES, ReadCtx
from .obs.recorder import OFF as _OFF, annotate as _annotate
from .wire import Message, MessageType, pack_hb_rows, unpack_hb_rows

if TYPE_CHECKING:
    from .node import Node

plog = get_logger("tpuquorum")

#: released/refused ReadIndex contexts remembered per group, so that a late
#: heartbeat echo can be put down to its cause (bounded: oldest forgotten)
_READ_GONE_KEEP = 512
#: sampled ReadIndex contexts followed at once (``_read_traces``); one more
#: closes the oldest as ``dropped``, so a context that no release and no
#: transition ever names cannot stay
_READ_TRACES_KEEP = 4096


class TpuQuorumCoordinator:
    """Owns the device engine; one round = one fused dispatch.

    All staging methods are called from raft under the owning node's
    raftMu; the coordinator serializes engine access with its own lock.
    The round thread applies commit/election results back through
    ``Node.offload_commit`` / ``Node.offload_election``.
    """

    def __init__(
        self,
        capacity: int = 1024,
        n_peers: int = 8,
        interval_s: float = 0.002,
        drive_ticks: bool = True,
        mesh_devices: int = 0,
        drive_reads: bool = True,
        warm_fused: bool = False,
        compilation_cache_dir: Optional[str] = None,
        telem: bool = False,
    ):
        from .ops.engine import (
            WARM_K_BUCKETS,
            BatchedQuorumEngine,
            enable_persistent_compilation_cache,
            k_bucket,
        )

        # mesh-sharded dispatch plane (ExpertConfig.engine_mesh_devices,
        # ops/mesh.py): no data ever flows BETWEEN groups, so N mesh
        # devices run N independent single-device per-shard engines —
        # each shard owns a contiguous group partition with its OWN
        # concurrent dispatch stream and per-shard dispatch lock.  This
        # replaced the GSPMD-partitioned single engine whose every
        # dispatch was an all-device rendezvous serialized process-wide
        # by the old _MULTIDEV_MU class lock (zero dispatch concurrency
        # from mesh hardware); the GSPMD path remains available by
        # constructing BatchedQuorumEngine(sharding=...) directly.
        mesh_n = 0  # shard count (0 = unsharded)
        mesh_devs = None
        if mesh_devices > 1:
            import jax

            devs = jax.devices()
            if len(devs) < mesh_devices:
                raise ValueError(
                    f"engine_mesh_devices={mesh_devices} but jax.devices() "
                    f"holds {len(devs)}: {devs}"
                )
            mesh_n = mesh_devices
            capacity = ((capacity + mesh_n - 1) // mesh_n) * mesh_n
            mesh_devs = devs[:mesh_n]
            plog.info(
                "quorum engine mesh-sharded over %d devices "
                "(%d rows, %d per shard)", mesh_n, capacity,
                capacity // mesh_n,
            )
        self.mesh_devices = mesh_n
        # persistent XLA compilation cache (ISSUE 7): enabled BEFORE any
        # program compiles so even the single-round warm misses persist.
        # JAX_COMPILATION_CACHE_DIR decides where it is set; otherwise the
        # configured directory, else the fixed in-checkout default (see
        # enable_persistent_compilation_cache).
        self.compilation_cache_dir = None
        try:
            self.compilation_cache_dir = enable_persistent_compilation_cache(
                compilation_cache_dir or ""
            )
        except OSError as e:
            plog.warning("compilation cache unavailable: %r", e)
        if mesh_n > 1:
            from .ops.mesh import MeshQuorumEngine

            self.eng = MeshQuorumEngine(
                capacity, n_peers, event_cap=max(4 * capacity, 4096),
                devices=mesh_devs, device_ticks=drive_ticks,
            )
        else:
            self.eng = BatchedQuorumEngine(
                capacity, n_peers, event_cap=max(4 * capacity, 4096),
                device_ticks=drive_ticks,
            )
        self.capacity = capacity
        # adaptive K-round batching (ISSUE 7 tentpole): once the warmup
        # pass has compiled the padded fused program set, the round
        # thread replays tick backlogs as ONE fused dispatch of up to
        # fused_k_max rounds; until then (and whenever a round carries
        # votes) it stays on the single-round path
        self._k_bucket = k_bucket
        # the deficit cap IS the largest warmed program: a bigger cap
        # would silently drop the ticks past the pad clamp
        self.fused_k_max = max(WARM_K_BUCKETS)
        self.fused_dispatches = 0
        # auto-warm only ticking engines: the fused live path is
        # tick-deficit replay, meaningless without drive_ticks.  Mesh
        # coordinators warm too — each shard's program set is
        # single-device (no collectives, no rendezvous), walked
        # sequentially off the round thread by the facade's niced
        # background warmer (ops/mesh.py warmup_fused).
        self._warm_requested = warm_fused and drive_ticks
        # device-tick mode: the per-tick firing decisions (election due,
        # heartbeat due, check-quorum window) come from the device tick
        # kernel; registered nodes set raft.device_ticks accordingly
        self.drive_ticks = drive_ticks
        # device read plane (ISSUE 3): ReadIndex heartbeat-echo quorum
        # counting batches into the same single-round dispatch; the
        # scalar ReadIndex stays the pending bookkeeping and the releaser
        self.drive_reads = drive_reads
        # per-group FIFO of device-staged read ctxs: cid -> list of
        # (slot, low, high, term) in staging order.  Confirmation of a
        # slot releases its ctx through the scalar prefix release, which
        # also frees every EARLIER ctx — their engine slots are cancelled
        # here.  Guarded by _mu (round thread + drain).
        self._read_pending: Dict[int, list] = {}
        # batched device-plane lease tracking (ISSUE 10, lease.LeaseTable):
        # created by the first registered read_lease group; the drain loop
        # folds the heartbeat-ack ops it is ALREADY walking into a
        # per-round tally — lease-coverage introspection across thousands
        # of groups with no extra host pass and no raftMu.  Advisory only:
        # the serving authority is each group's scalar LeaderLease.
        self.lease_table = None
        # observability: ctxs confirmed BY THE DEVICE plane vs echoes that
        # fell back to the scalar tally — the read-plane tests assert the
        # device actually served the load.  Plain integers, always on
        # (ISSUE 26): echoes the device tallied (``read_acks``), echoes
        # tallied scalar-side by cause — ``slot_overflow`` (the ctx never
        # got a device slot), ``after_confirm`` (the ctx was already
        # confirmed or prefix-released when the echo came; with three
        # replicas the second echo of every ctx is this), ``purged`` (a
        # transition dropped the group's FIFO, or the ctx aged out of
        # ``_read_gone``) — and ctxs given / refused a slot.
        # ``read_fallbacks`` is the causes' sum.
        self.read_confirms = 0
        self.read_acks = 0
        self.read_fallback_causes = {
            "slot_overflow": 0, "after_confirm": 0, "purged": 0,
        }
        self.reads_staged = 0
        self.reads_refused = 0
        # the four counters above as of the last recorded coord_round span
        self._reads_spanned = (0, 0, 0, dict(self.read_fallback_causes))
        # what the drains handed on, counted only while _obs is attached:
        # follower acknowledgements, and the ReadIndex contexts a leader
        # staged by origin — the host's own clients' against those a
        # follower forwarded; then the three as of the last recorded span
        self.acks_drained = 0
        self.reads_local = 0
        self.reads_remote = 0
        self._drained_spanned = (0, 0, 0)
        # cid -> {(low, high): cause} of ctxs no longer (or never)
        # device-tracked, newest last; guarded by _mu like _read_pending
        self._read_gone: Dict[int, dict] = {}
        # the sampled ReadIndex contexts this host's leaders hold (ISSUE
        # 39): (cid, low, high) -> ReadCtx, from the step worker that
        # accepted one carrying a requester's trace to the step worker
        # that answered it, which writes its ``read_ctx`` span.  EMPTY
        # while nothing is sampled (tracer off: always), and every site on
        # a read's way tests its truthiness and nothing else.  Single
        # dict operations from both sides, no lock.
        self._read_traces: Dict[tuple, ReadCtx] = {}
        # of those, the ones the drain in progress gave a slot (they get
        # the round's span seq once it opens) and the ones the step
        # confirmed (stamped at the fan-out); round thread only
        self._rt_staged: list = []
        self._rt_confirmed: list = []
        # rounds that dispatched while _obs is attached (a ReadCtx counts
        # the rounds from its acceptance to its confirmation)
        self._rounds_recorded = 0
        # device state machine plane (devsm, ISSUE 11; DevKVPlane):
        # created by the FIRST DeviceKVStateMachine registration
        # (NodeHost.start_cluster with Config.device_kv).  None keeps the
        # round loop bit-identical — every hook below gates on it.
        self.devsm = None
        # cost-driven placement cadence (mesh only): the round thread
        # runs at most one bounded rebalance pass per interval
        self._rebalance_interval = 1.0
        self._next_rebalance = time.monotonic() + self._rebalance_interval
        # monotonically increasing tick sequence written ONLY by the tick
        # thread; the round compares against the last value it consumed, so
        # a tick arriving mid-round is never lost (no lock needed: single
        # writer, single reader)
        self._tick_seq = 0
        self._tick_seen = 0
        # the tick deficit's account (plain integers, always on): host
        # ticks a round ran late (its deficit beyond the one tick that
        # was due), ticks it could not replay (beyond the warmed K: this
        # host's device clocks ran that much slow), and the election-due
        # flags such a round held back — see _round_inner
        self.ticks_replayed = 0
        self.ticks_dropped = 0
        self.elections_held = 0
        # the shortest election timeout (ticks) of any row ever synced
        self._min_election_timeout = 1 << 30
        # batched heartbeat plane: the host's link to its peers
        # (``attach_host_link``, set by NodeHost; None leaves every
        # heartbeat on the per-group message) and what went by the block
        # against what took the per-group message, by cause
        self._hb_link = None
        self.hb_block_rows = 0
        # of those, a busy group's rows, served in reduced form without
        # the group's lock (Node._hb_block_raft)
        self.hb_lite_rows = 0
        self.hb_single_causes = dict.fromkeys(HB_SINGLE_CAUSES, 0)
        # the counters above as of the last recorded coord_round span
        self._plane_spanned = (0, 0, dict(self.hb_single_causes))
        self._nodes: Dict[int, "Node"] = {}
        self._mu = threading.RLock()
        # staging is decoupled from the engine lock: raft step workers only
        # append and NEVER wait on an in-flight device dispatch — a
        # blocked step worker delays heartbeats and provokes spurious
        # elections (the same reason the reference sends Replicate before
        # fsync, execengine.go:954-961).  A deque and no lock at all: an
        # append is atomic under the interpreter lock, a group's ops are
        # staged under its raftMu (so in order), and the one consumer pops
        # what it found.  Under a micro-lock every staged op of every
        # step worker of the host queued for it, and on a saturated
        # interpreter a contended lock costs each waiter a switch
        # interval: one host's workers crawled while its peers' ran.
        self._staged: collections.deque = collections.deque()
        # perf_counter when the oldest op / tick the next round will see
        # was staged (``wait_ms`` of its coord_round span); stamped only
        # while _obs is attached, taken by the drain
        self._first_at: Optional[float] = None
        self._round_first_at: Optional[float] = None
        # per-round leader-contact dedup: one election-clock reset per
        # group per round is sufficient and idempotent; without this a
        # follower ingesting tens of thousands of Replicates per second
        # would stage one event slot per message
        self._contacted: set = set()
        # a group's sleep (``Config.quiesce`` on the device tick plane):
        # the same dedup for a quiesce group's activity marks (one idle
        # clock reset a group a round), and the plane's account: replicas
        # of this host asleep now, own entries, wakes (plain integers,
        # written under the replicas' raftMu)
        self._acted: set = set()
        self.rows_quiesced = 0
        self.quiesce_enters = 0
        self.quiesce_wakes = 0
        self._quiesce_spanned = (0, 0)
        self._pending = threading.Event()
        self._stopped = threading.Event()
        self._interval = interval_s
        # compartmentalized host plane (hostplane.py, wired by NodeHost
        # when ExpertConfig.host_compartments is on): the round fan-out
        # below then flags offload effects with wake=False and coalesces
        # the engine step wakeups to ONE per touched group per round —
        # the coordinator feeds the same batched-wakeup tier the ingress
        # batcher uses.  None keeps the per-effect wakeups (bit-identical
        # pre-compartment behavior).
        self.hostplane = None
        # device-plane observability (ISSUE 5): OFF by default, gated on
        # `is not None` everywhere (the engine's overhead contract); the
        # module latch covers tests/bench, NodeHostConfig.enable_metrics
        # covers the live stack (nodehost.py wiring)
        self._obs = None
        # cross-plane request tracer (obs/trace.py, ISSUE 9; set by
        # NodeHost): the round fan-out stamps "device_round" on the
        # in-flight traces of every group whose commit/read-confirm this
        # round released, linking the engine's dispatch span seq.  None
        # keeps the round loop bit-identical.
        self.tracer = None
        # replication attribution (obs/replattr.py, ISSUE 14; set by
        # NodeHost with the tracer): device-plane commits link the
        # staged-round ack block's dispatch span into their attribution
        # records, so a closed record names the round that released it.
        # None keeps the round loop bit-identical.
        self.replattr = None
        # device capacity & profiling plane (obs/devprof.py, ISSUE 15;
        # attached by NodeHost when device_profile > 0).  None keeps the
        # engine's _devprof latch down and the dispatch path bit-identical.
        self.devprof = None
        # device telemetry fold (ISSUE 20, kernels.telem_fold; NodeHost
        # wires NodeHostConfig.health_aggregate here): flipped BEFORE
        # warmup starts so the warmed fused programs already include the
        # fold — a late enable_telem still works but pays one recompile
        # per variant on next use (the late-devsm precedent).
        if telem:
            self.eng.enable_telem()
        if _obs.enabled():
            self.enable_obs()
        if self._warm_requested:
            self.start_warmup()
        self._thread = threading.Thread(
            target=self._round_main, name="tpuquorum", daemon=True
        )
        self._thread.start()

    def start_warmup(self, force: bool = False):
        """Kick off the engine's background AOT warm-compile (idempotent;
        see ``BatchedQuorumEngine.warmup_fused``).  NodeHost calls this
        AFTER wiring observability so the warmup spans/metrics land in
        the host's registry; until the readiness latch flips, every
        round uses the already-compiled single-round programs — a
        proposal never waits on XLA.

        Mesh-sharded coordinators warm too: the facade's background
        walker compiles each shard's SINGLE-DEVICE program set
        sequentially (no collectives, so the historical multi-device
        first-compile rendezvous wedge cannot recur), and the
        ``fused_ready`` readiness latch flips only once every shard
        finished — until then fused-eligible rounds record
        ``fuse_skip="mesh_warmup"``.

        No-op (returns None) on a tickless coordinator unless ``force``:
        the fused live path is tick-deficit replay."""
        if not force and not self.drive_ticks:
            return None
        return self.eng.warmup_fused()

    @property
    def warmup_stats(self) -> dict:
        """The engine's warm-compile record (programs, wall seconds,
        persistent-cache hits/misses, error)."""
        return self.eng.warmup_stats

    def enable_obs(self, recorder=None, registry=None, stall_ms=None,
                   host=None):
        """Attach round-loop + engine instruments: coordinator spans and
        ``dragonboat_coord_*`` families here, ``dragonboat_device_*`` on
        the engine, node offload counters on registered nodes — all into
        one registry so ``write_health_metrics`` exposes the whole device
        plane.  ``stall_ms`` overrides the recorder's stall threshold
        (the round-gate watchdog's trip point).  A repeat call with no
        recorder/registry is a no-op; explicit arguments REBIND (the
        engine's ``enable_obs`` note: a latch-attached coordinator must
        not swallow NodeHost's later registry wiring).  ``host`` (the
        NodeHost's raft address) tags every span of this coordinator and
        its engine, so co-hosted NodeHosts can share one recorder."""
        if self._obs is None or recorder is not None or registry is not None:
            from .obs.instruments import CoordObs

            if host is None and self._obs is not None:
                host = self._obs.host
            eng_obs = self.eng.enable_obs(recorder, registry, host=host)
            self._obs = CoordObs(
                eng_obs.recorder, registry=registry, host=host
            )
            with self._mu:
                for node in self._nodes.values():
                    node.obs_registry = self._obs.registry
        if stall_ms is not None:
            self._obs.recorder.stall_ms = float(stall_ms)
        return self._obs

    @property
    def flight_recorder(self):
        """The attached flight recorder (None while obs is off)."""
        return self._obs.recorder if self._obs is not None else None

    def enable_devprof(self, devprof):
        """Attach the device capacity & profiling plane (ISSUE 15,
        obs/devprof.py; NodeHost wires it when
        ``NodeHostConfig.device_profile`` > 0): binds the DevProf to the
        engine (flipping its ``_devprof`` latch — sampled device-time
        estimation, padding-waste accounting, the HBM ledger) and hands
        it this coordinator so its snapshots can reach the devsm plane's
        shadow residency."""
        devprof.coord = self
        devprof.bind_engine(self.eng)
        self.devprof = devprof
        return devprof

    def enable_telem(self, topk: Optional[int] = None) -> None:
        """Flip the engine's device telemetry fold (ISSUE 20,
        ``kernels.telem_fold``): every subsequent fused/dense/sparse
        dispatch egresses a fixed-size health aggregate (commit-lag
        histogram, per-state counts, stalled count, slot occupancy,
        on-device top-K worst groups).  One-way, like ``enable_devprof``;
        prefer the ``telem=True`` constructor kwarg so the warmed program
        set already includes the fold."""
        self.eng.enable_telem(topk)

    @property
    def telem_enabled(self) -> bool:
        return self.eng.telem_enabled

    def telem_snapshot(self) -> Optional[dict]:
        """Latest harvested device telemetry aggregate (None until the
        first telem-on dispatch lands; mesh coordinators merge per-shard
        folds host-side).  Passive: the dict refreshes only when rounds
        dispatch, and carries ``seq``/``mono`` so the health sampler can
        tell a fresh fold from a stale one on an idle engine."""
        return self.eng.telem_snapshot()

    def registered_cids(self) -> set:
        """Cluster ids currently registered on the device engine (the
        aggregate health sampler's coverage set: these groups are
        watched by the telemetry fold, everything else keeps the
        per-group raft_mu walk).  Snapshot under the coordinator lock —
        callers cache it keyed on the membership signature."""
        with self._mu:
            return set(self._nodes)

    @property
    def read_fallbacks(self) -> int:
        """Heartbeat read echoes tallied scalar-side (every cause)."""
        return sum(self.read_fallback_causes.values())

    def _read_gone_note(self, cid: int, key, cause: str) -> None:
        """Remember why ctx ``key`` of ``cid`` is not device-tracked
        (under _mu)."""
        gone = self._read_gone.get(cid)
        if gone is None:
            gone = self._read_gone[cid] = {}
        gone[key] = cause
        if len(gone) > _READ_GONE_KEEP:
            del gone[next(iter(gone))]

    def health_snapshot(self) -> dict:
        """Round-loop health for the cluster health sampler (ISSUE 13):
        staged-op backlog, registered groups, warmup readiness and the
        read-plane tallies — all lock-free or micro-locked reads, never
        the engine lock (a sampler must not queue behind a dispatch)."""
        staged = len(self._staged)
        d = {
            "groups": len(self._nodes),
            "staged": staged,
            "tick_deficit": self._tick_seq - self._tick_seen,
            "fused_ready": bool(self.eng.fused_ready),
            "fused_dispatches": self.fused_dispatches,
            "read_confirms": self.read_confirms,
            "read_fallbacks": self.read_fallbacks,
            "read_acks": self.read_acks,
            "read_fallback_causes": dict(self.read_fallback_causes),
            "reads_staged": self.reads_staged,
            "reads_refused": self.reads_refused,
        }
        lt = self.lease_table
        if lt is not None:
            d["lease_groups_held"] = lt.held_count(self._tick_seen)
        if self.mesh_devices > 1:
            # per-shard placement/cost view (mesh dispatch plane): group
            # counts, dispatch-cost EMA and per-shard warm readiness,
            # plus the lifetime migration count — the shard_imbalance
            # health detector keys off these
            d["shards"] = self.eng.shard_stats()
            d["migrations"] = self.eng.migrations
        return d

    # ------------------------------------------------------------------
    # node lifecycle
    # ------------------------------------------------------------------

    @property
    def quiesce_on_device(self) -> bool:
        """Whether a ``Config.quiesce`` group's idle clock and sleep are
        the tick kernel's here: a ticking coordinator on one device (a
        mesh facade's shards have no idle columns wired; there such a
        group keeps scalar ticks and the host-side manager)."""
        return self.drive_ticks and self.mesh_devices <= 1

    def register(self, node: "Node") -> None:
        """Add the node's group and sync its current raft state into the
        row.  Called after Peer.launch with the raft lock held."""
        with self._mu:
            if getattr(node, "dev_quiesce", False):
                # the first quiesce group flips the engine's latch (and
                # starts a warm-up under way over: has_quiesce programs)
                self.eng.enable_quiesce()
            self._nodes[node.cluster_id] = node
            self._sync_row_locked(node)
            if self.drive_reads:
                node.peer.raft.device_reads = True
            if self._obs is not None:
                node.obs_registry = self._obs.registry

    def unregister(self, cluster_id: int) -> None:
        if self.devsm is not None:
            self.devsm.unregister(cluster_id)
        with self._mu:
            node = self._nodes.pop(cluster_id, None)
            if node is not None and getattr(node, "_asleep", False):
                self.rows_quiesced -= 1  # (a stopped replica sleeps no more)
            self._read_pending.pop(cluster_id, None)
            self._read_gone.pop(cluster_id, None)
            if self._read_traces:
                self._read_traces_drop(cluster_id)
            if self.lease_table is not None:
                self.lease_table.remove(cluster_id)
            if cluster_id in self.eng.groups:
                self.eng.remove_group(cluster_id)

    def devsm_plane(self):
        """The device state machine plane, created on first use
        (``NodeHost.start_cluster`` registration path)."""
        if self.devsm is None:
            from .devsm.plane import DevKVPlane

            self.devsm = DevKVPlane(self)
            # the ENGINE egress hook is the single delivery channel for
            # KV read captures: it fires on every harvest that carried
            # one — including rare-path internal harvests (row syncs,
            # transitions) whose results the round loop never sees and
            # which would otherwise strand parked readers until timeout
            self.eng.kv_egress_hook = self.devsm.deliver
        return self.devsm

    def devsm_force_release(self, cluster_id: int) -> bool:
        """Actuation surface for the recovery plane (obs/recovery.py,
        ISSUE 17): force-release the group's device binding so a
        bind/unbind loop stops burning uploads — reads fall back to the
        gated host shadow and the bind re-arms only on the next
        leadership transition.  Returns True when the group was tracked
        (something to release)."""
        plane = self.devsm
        if plane is None or not plane.tracks(cluster_id):
            return False
        plane.on_unbind(cluster_id)
        return True

    def _sync_row_locked(self, node: "Node") -> None:
        """(Re)build the group's row from scalar raft state — the rare-path
        resync used at registration and after membership changes."""
        r = node.peer.raft
        cid = r.cluster_id
        self._read_pending.pop(cid, None)
        if r.lease is not None:
            # (re)configure the advisory lease row from scalar state —
            # quorum/duration track membership changes through the same
            # resync path the engine row rides
            if self.lease_table is None:
                from .lease import LeaseTable

                self.lease_table = LeaseTable()
            self.lease_table.configure(
                cid, r.quorum(), r.lease.duration, r.node_id,
                voters=list(r.remotes) + list(r.witnesses),
            )
        if cid in self.eng.groups:
            self.eng.remove_group(cid)
        voters = sorted(set(r.remotes))
        witnesses = tuple(sorted(r.witnesses))
        observers = tuple(sorted(r.observers))
        if r.node_id not in r.remotes and r.node_id not in r.witnesses and (
            r.node_id not in r.observers
        ):
            # a joining node knows no membership yet (it learns from the
            # log); register a self-only row until membership_changed
            # resyncs it
            voters = sorted(set(voters) | {r.node_id})
        if r.election_timeout < self._min_election_timeout:
            self._min_election_timeout = r.election_timeout
        self.eng.add_group(
            cid,
            node_ids=voters,
            self_id=r.node_id,
            election_timeout=r.election_timeout,
            heartbeat_timeout=r.heartbeat_timeout,
            # per-replica seeded randomized timeout (scalar raft's own),
            # so co-hosted replicas don't fire elections in lockstep
            rand_timeout=r.randomized_election_timeout,
            check_quorum=r.check_quorum,
            witnesses=witnesses,
            observers=observers,
            **(
                # (``Soft.quiesce_threshold_factor`` x election ticks,
                # host-seeded like ``rand_timeout``; a rebuilt row is
                # awake, as its replica is: what rebuilt it was activity)
                {"quiesce_threshold": node.quiesce_mgr.threshold}
                if getattr(node, "dev_quiesce", False) else {}
            ),
        )
        if r.hier is not None:
            # hier geometry (ISSUE 18) is membership-like: the near mask
            # and sub-quorum cardinality follow the voter set and this
            # replica's static domain, not the row's role, so the
            # registration/resync rebuild is the only push site — the
            # staged leader/candidate/follower transitions leave it
            # untouched exactly like the membership columns.  The fused
            # rule only ever widens q on leader rows (kernels._finish_step
            # has_hier twin of Raft._hier_try_commit).
            from .raft.hier import sub_quorum_size

            near = r.hier.near_voters(set(voters) | set(witnesses))
            self.eng.set_hier(
                cid, near, sub_quorum_size(len(near)) if near else 0
            )
        if r.is_leader():
            self.eng.set_leader(
                cid,
                term=r.term,
                term_start=self._term_start(r),
                last_index=r.log.last_index(),
            )
            # replay known match state so commit picks up where scalar was
            for nid, rp in list(r.remotes.items()) + list(r.witnesses.items()):
                if rp.match > 0:
                    self.eng.ack(cid, nid, rp.match)
            if self.devsm is not None and self.devsm.tracks(cid):
                # a resync on a standing leader re-arms the devsm bind at
                # the current log tail (the drain's resync op unbound it)
                self.devsm.on_leader(cid, r.log.last_index())
        elif r.is_candidate():
            self.eng.set_candidate(cid, term=r.term)
            for nid, granted in r.votes.items():
                self.eng.vote(cid, nid, granted)
        else:
            self.eng.set_follower(cid, term=r.term)

    @staticmethod
    def _term_start(r) -> int:
        """First index of the leader's current term — the floor below which
        counting-based commit is forbidden (raft paper p8).  O(1): the
        leader records the index of its promotion noop
        (``raft.term_start_index``); the scan fallback covers only rows
        synced from state predating the attribute (never in practice)."""
        if r.term_start_index > 0:
            return r.term_start_index
        idx = r.log.last_index()
        first = r.log.first_index()
        while idx >= first:
            try:
                if r.log.term(idx) != r.term:
                    return idx + 1
            except Exception:
                return idx + 1
            idx -= 1
        return idx + 1

    # ------------------------------------------------------------------
    # staging hooks (called from raft under the node's raftMu)
    # ------------------------------------------------------------------

    def _wake_round(self) -> None:
        """Tell the round thread there is work.  ``Event.set`` takes the
        event's lock whether or not it is set already; called once a
        staged op by every step worker of the host, that lock becomes a
        convoy on a saturated interpreter (each contended acquire costs
        its caller a switch interval) and one host's workers stop while
        its peers' run.  The flag is read without the lock: whoever finds
        it set staged before the round thread cleared it, and the drain
        that follows the clear takes the op."""
        if not self._pending.is_set():
            self._pending.set()

    def _stage(self, op) -> None:
        if self._obs is not None and self._first_at is None:
            self._first_at = time.perf_counter()
        self._staged.append(op)
        self._wake_round()

    def ack(self, cluster_id: int, node_id: int, index: int) -> None:
        self._stage(("ack", cluster_id, node_id, index))

    def vote(self, cluster_id: int, node_id: int, granted: bool) -> None:
        self._stage(("vote", cluster_id, node_id, granted))

    def heartbeat_resp(self, cluster_id: int, node_id: int) -> None:
        self._stage(("hbresp", cluster_id, node_id))

    def leader_contact(self, cluster_id: int) -> None:
        # (a racing duplicate is one more idempotent clock reset)
        if cluster_id in self._contacted:
            return
        self._contacted.add(cluster_id)
        self._stage(("contact", cluster_id))

    def set_randomized_timeout(self, cluster_id: int, timeout: int) -> None:
        self._stage(("randto", cluster_id, timeout))

    # a group's sleep (called by a quiesce group's node under its raftMu)

    def quiesce_activity(self, cluster_id: int) -> None:
        """Activity on an AWAKE replica: its row's idle clock restarts
        (once a round is enough, and a racing duplicate is one more
        idempotent reset)."""
        if cluster_id in self._acted:
            return
        self._acted.add(cluster_id)
        self._stage(("qwake", cluster_id))

    def quiesce_woke(self, cluster_id: int) -> None:
        """A message or a request woke a sleeping replica."""
        self.rows_quiesced -= 1
        self.quiesce_wakes += 1
        with (_annotate("quiesce_wake") if self._obs is not None else _OFF):
            self._stage(("qwake", cluster_id))

    def quiesce_slept(self, cluster_id: int, own: bool) -> None:
        """A replica went to sleep: its row's own idle clock crossed
        (``own``: the row sleeps already) or a peer's QUIESCE said so (the
        row is put to sleep by the next round)."""
        self.rows_quiesced += 1
        if own:
            self.quiesce_enters += 1
        else:
            self._stage(("qsleep", cluster_id))

    # ------------------------------------------------------------------
    # batched heartbeat plane
    #
    # The device ticks every row in lockstep and hands back all of a
    # host's heartbeat-due flags in one egress.  Spent one group at a
    # time they cost the process, per group and tick, a step-worker turn
    # on the leader, two messages out, a turn on each follower, two
    # responses back and two more turns on the leader — at 1,024 groups
    # and a 50 ms tick some 160,000 message events a second against one
    # interpreter.  Spent by the block they cost a HOST, per tick, one
    # message to each peer host and one back, whatever the group count:
    #
    #   leader's round thread   _fan_out -> _heartbeat_block: for every
    #       due row whose scalar state takes no per-group message
    #       (Node.hb_block_rows), one (cluster, to, from, term, commit)
    #       row into the HEARTBEAT_BLOCK of the peer's host;
    #   follower's host         on_heartbeat_block: every row whose group
    #       agrees (same term, that leader, a follower) resets its
    #       election clock scalar-side and, as ONE staged block, on the
    #       device rows, takes the commit index, and answers with a row
    #       of the HEARTBEAT_RESP_BLOCK going back;
    #   leader's host           on_heartbeat_block: every response row of
    #       the term it was sent in marks the remote active and stages
    #       its device activity bit, again as one block.
    #
    # A row whose group does not agree at any leg is handed to the group
    # as the per-group HEARTBEAT / HEARTBEAT_RESP it stands for, and the
    # scalar handlers see exactly today's message (tests/test_hb_block.py
    # holds the two paths equal).  Block or single is chosen by what the
    # round sees: one due row takes the per-group path, more take the
    # block (the scalars-for-one, one-pass-for-many rule of stage_read).
    # ------------------------------------------------------------------

    def attach_host_link(self, resolve, send_to_host) -> None:
        """NodeHost's wiring: ``resolve(cluster_id, node_id) -> address``
        and ``send_to_host(address, Message) -> bool``."""
        self._hb_link = (resolve, send_to_host)

    def _hb_single(self, cause: str, n: int = 1) -> None:
        self.hb_single_causes[cause] += n

    def _heartbeat_block(self, cids, demote: set) -> list:
        """Leader leg (round thread, outside _mu): send the block rows of
        the due groups; returns the groups that take the per-group
        ``offload_tick_heartbeat`` instead.  A group of the block whose
        check-quorum window closed this tick (``demote``) has had its
        scalar CHECK_QUORUM run with the heartbeat and is taken out of
        the set."""
        resolve, send = self._hb_link
        nodes = self._nodes
        singles = []
        by_host: Dict[str, list] = {}
        for cid in cids:
            node = nodes.get(cid)
            if node is None:
                continue
            term, rows, demoted = node.hb_block_rows(cid in demote)
            if term.__class__ is str:
                singles.append(cid)
                # ``rows`` heartbeats and as many responses go per group
                self._hb_single(term, 2 * max(rows, 0))
                continue
            if demoted:
                demote.discard(cid)
            me = node.node_id
            for to, commit in rows:
                addr = resolve(cid, to)
                if addr is None:
                    continue
                block = by_host.get(addr)
                if block is None:
                    block = by_host[addr] = []
                block.append((cid, to, me, term, commit))
        for addr, rows in by_host.items():
            send(addr, Message(
                type=MessageType.HEARTBEAT_BLOCK, entries=pack_hb_rows(rows)
            ))
        return singles

    def on_heartbeat_block(self, m, src: str) -> None:
        """A block message arrived (the transport's delivery thread):
        handle every row whose group agrees, hand the rest to their groups
        as per-group messages."""
        rows = unpack_hb_rows(m)
        if not rows:
            return
        nodes = self._nodes
        ok_cids = []
        ok_peers = []
        if m.type == MessageType.HEARTBEAT_BLOCK:
            resp = []
            for cid, to, from_, term, commit in rows:
                node = nodes.get(cid)
                if node is None or node.node_id != to:
                    continue
                cause = node.hb_block_contact(from_, term, commit)
                if cause is None or cause == "lite":
                    ok_cids.append(cid)
                    resp.append((cid, from_, to, term, 0))
                    if cause is not None:
                        self.hb_lite_rows += 1
                else:
                    # the heartbeat and its response go per group
                    self._hb_single(cause, 2)
                    node.handle_message_batch(Message(
                        type=MessageType.HEARTBEAT, cluster_id=cid, to=to,
                        from_=from_, term=term, commit=commit,
                    ))
            if ok_cids:
                self.hb_block_rows += len(ok_cids)
                self._stage_block(("contact_block", ok_cids))
            if resp and self._hb_link is not None and src:
                self._hb_link[1](src, Message(
                    type=MessageType.HEARTBEAT_RESP_BLOCK,
                    entries=pack_hb_rows(resp),
                ))
            return
        for cid, to, from_, term, _commit in rows:
            node = nodes.get(cid)
            if node is None or node.node_id != to:
                continue
            cause = node.hb_block_resp(from_, term)
            if cause is None or cause == "lite":
                ok_cids.append(cid)
                ok_peers.append(from_)
                if cause is not None:
                    self.hb_lite_rows += 1
            else:
                self._hb_single(cause)
                node.handle_message_batch(Message(
                    type=MessageType.HEARTBEAT_RESP, cluster_id=cid, to=to,
                    from_=from_, term=term,
                ))
        if ok_cids:
            self.hb_block_rows += len(ok_cids)
            self._stage_block(("hbresp_block", ok_cids, ok_peers))

    def _stage_block(self, op) -> None:
        if op[0] == "contact_block":
            # the per-round contact dedup covers these groups too
            self._contacted.update(op[1])
        self._stage(op)

    def _drain_block(self, op, recover: list) -> None:
        """Apply a staged contact / heartbeat-response block (under _mu):
        the rows are resolved here, where a resync cannot move them."""
        groups = self.eng.groups
        contact = op[0] == "contact_block"
        if self.mesh_devices > 1:
            # a mesh engine's rows are shard-local: op by op
            for i, cid in enumerate(op[1]):
                if cid in groups:
                    try:
                        if contact:
                            self.eng.leader_contact(cid)
                        else:
                            self.eng.heartbeat_resp(cid, op[2][i])
                    except (ValueError, KeyError):
                        recover.append(cid)
            return
        rows = []
        slots = []
        for i, cid in enumerate(op[1]):
            gi = groups.get(cid)
            if gi is None:
                continue
            if contact:  # a contact is an ack at rel 0 on the row's own slot
                slot = gi.self_slot
            else:
                slot = gi.slots.get(op[2][i])
                if slot is None:  # unknown peer: rebuild the row (rare)
                    recover.append(cid)
                    continue
            slots.append(slot)
            rows.append(gi.row)
        if rows:
            self.eng.heartbeat_resp_block(rows, slots)

    def read_stage(
        self, cluster_id: int, committed: int, low: int, high: int, term: int,
        remote: bool = False, trace=None,
    ) -> None:
        """A leader accepted a ReadIndex ctx (``handle_leader_read_index``
        under raftMu): stage it into the group's pending-read slot,
        captured at scalar raft's own committed watermark.  ``remote``
        says a follower forwarded it (counted by origin, obs on);
        ``trace`` is the requester's wire context where the batch holds a
        sampled request (``Message.trace``, None otherwise): the ctx is
        then followed to its release (instant ``a``)."""
        if trace is not None and self._obs is not None:
            self._read_trace_open(
                cluster_id, low, high, term, remote, trace
            )
        self._stage(
            ("rstage", cluster_id, committed, low, high, term, remote)
        )

    def read_ack_hint(
        self, cluster_id: int, node_id: int, low: int, high: int
    ) -> None:
        """A heartbeat response echoed a ReadIndex hint: joins the ctx's
        pending-read slot; the device row-sum decides the quorum."""
        rt = self._read_traces
        if rt:
            # instants ``e1`` / ``eq``: the first echo, and the echo of
            # the follower that completes the quorum, as staged
            rc = rt.get((cluster_id, low, high))
            if rc is not None and rc.c is None and node_id not in rc.peers:
                now = time.perf_counter()
                rc.peers.add(node_id)
                if rc.e1 is None:
                    rc.e1 = now
                if rc.eq is None and len(rc.peers) >= rc.need:
                    rc.eq = now
                    rc.eq_peer = node_id
        self._stage(("rack", cluster_id, node_id, low, high))

    # ------------------------------------------------------------------
    # a sampled ReadIndex context, followed (ISSUE 39; obs on, and only
    # for a ctx whose READ_INDEX carried a requester's trace)
    # ------------------------------------------------------------------

    def read_leased(
        self, cluster_id: int, low: int, high: int, term: int, remote: bool,
        trace, accepted: float, remaining_ticks: int,
    ) -> None:
        """A leader answered a SAMPLED read under its lease in the step
        that took it (``Raft.try_lease_read``, under raftMu; ISSUE 41):
        nothing was staged and no round will see it, so its one
        ``read_ctx`` span is written here, ``path = lease``, from the
        accept to the answer, with what ``lease.check`` returned."""
        obs = self._obs
        if obs is None:
            return
        rc = ReadCtx(cluster_id, low, high, term, remote, trace, 0,
                     self._rounds_recorded)
        rc.a = accepted
        rc.r = time.perf_counter()
        rc.path = "lease"
        rc.remaining_ticks = remaining_ticks
        obs.read_ctx(rc)

    def _read_trace_open(self, cluster_id: int, low: int, high: int,
                         term: int, remote: bool, trace) -> None:
        """Instant ``a`` (step worker, under the group's raftMu)."""
        rt = self._read_traces
        if len(rt) >= _READ_TRACES_KEEP:
            old = rt.pop(next(iter(rt)), None)
            if old is not None:
                self._read_trace_close(old, "dropped")
        node = self._nodes.get(cluster_id)
        need = 1
        lease_fallback = False
        if node is not None and node.peer is not None:
            r = node.peer.raft
            need = max(1, r.quorum() - 1)
            # a lease group's context is staged only where its leader
            # found the lease not valid (``try_lease_read``)
            lease_fallback = r.lease is not None
        rc = rt[(cluster_id, low, high)] = ReadCtx(
            cluster_id, low, high, term, remote, trace, need,
            self._rounds_recorded,
        )
        rc.lease_fallback = lease_fallback

    def _read_trace_close(self, rc: ReadCtx, path: Optional[str] = None
                          ) -> None:
        if path is not None:
            rc.path = path
        obs = self._obs
        if obs is not None:
            obs.read_ctx(rc)

    def _read_traces_drop(self, cluster_id: int,
                          below_term: Optional[int] = None) -> None:
        """A transition took the group's pending reads: close the sampled
        ctxs it held (those accepted in a term under ``below_term``, all
        of them with None) with ``path = dropped``."""
        rt = self._read_traces
        for key, rc in list(rt.items()):
            if key[0] == cluster_id and (
                below_term is None or rc.term < below_term
            ):
                if rt.pop(key, None) is not None:
                    self._read_trace_close(rc, "dropped")

    def read_released(self, cluster_id: int, ris, scalar: bool = False
                      ) -> None:
        """Instant ``r``: ``apply_read_releases`` answered the requesters
        of ``ris`` (a READ_INDEX_RESP sent, or ``ready_to_read`` filed);
        the sampled ones' spans are written (called only while
        ``_read_traces`` holds something).  ``scalar``: the step worker's
        own ``read_index.confirm`` released them in this same turn, which
        is then their instant ``c`` too (the wait for that turn is the
        scalar path's ``confirm_ms``)."""
        rt = self._read_traces
        now = time.perf_counter()
        for s in ris:
            rc = rt.pop((cluster_id, s.ctx.low, s.ctx.high), None)
            if rc is not None:
                if scalar and rc.c is None:
                    rc.c = now
                    rc.rounds = self._rounds_recorded - rc.round0
                rc.r = now
                self._read_trace_close(rc)

    def stage_sm_ops(self, cluster_id: int, ops) -> None:
        """A devsm leader appended application entries
        (``raft.append_entries`` under raftMu): hand their ``(index,
        payload)`` pairs to the device state machine plane — the apply
        fold consumes them the round their commit lands."""
        self._stage(("kvops", cluster_id, ops))

    def set_leader(
        self, cluster_id: int, term: int, term_start: int, last_index: int
    ) -> None:
        self._stage(("leader", cluster_id, term, term_start, last_index))

    def set_candidate(self, cluster_id: int, term: int) -> None:
        if self._obs is not None:
            self._obs.campaign(cluster_id)
        self._stage(("candidate", cluster_id, term))

    def set_follower(self, cluster_id: int, term: int) -> None:
        self._stage(("follower", cluster_id, term))

    def membership_changed(self, cluster_id: int) -> None:
        self._stage(("resync", cluster_id))

    def request_tick(self) -> None:
        """One RTT elapsed: the next round runs the device tick kernel
        (called from the NodeHost tick worker, once per tick for ALL
        groups — the device ticks rows in lockstep)."""
        self._tick_seq += 1
        if self._obs is not None and self._first_at is None:
            # single writer besides the staging lock's holders; a lost
            # stamp costs one round's wait_ms, nothing else
            self._first_at = time.perf_counter()
        self._wake_round()

    def _drain_locked(self) -> list:
        """Apply staged ops to the engine in staging order (so a
        transition's queued-event purge still covers exactly the events
        staged before it).  Returns the cids needing a row recovery —
        recovery takes node.raft_mu, and the lock order everywhere else is
        raft_mu -> coord._mu (register's contract), so acquiring raft_mu
        HERE (under _mu) deadlocks against fast_eject -> register (seen
        live in the tpu+fastlane chaos run); the caller recovers after
        releasing _mu."""
        # the dedup set first: a contact that finds the new, empty set is
        # staged (again, at worst), never skipped for an op already taken
        self._contacted = set()
        self._acted = set()
        # cid -> wake (True) / sleep: a quiesce row's last mark of this
        # drain, handed to the engine once the ops are through
        marks: Dict[int, bool] = {}
        self._round_first_at, self._first_at = self._first_at, None
        staged = self._staged
        ops = [staged.popleft() for _ in range(len(staged))]
        recover = []
        lt = self.lease_table
        lease_acks: Dict[int, set] = {}
        obs_on = self._obs is not None
        rt = self._read_traces
        # bulk-pull every row a transition below will mutate: one device
        # gather per field for the whole set, instead of ~20 single-row
        # reads inside each set_* call (the dominant cost of election
        # bursts at 4k+ groups)
        sync_rows = []
        for op in ops:
            if op[0] in ("leader", "candidate", "follower", "randto"):
                gi = self.eng.groups.get(op[1])
                if gi is not None:
                    sync_rows.append(gi.row)
        if sync_rows:
            self.eng.sync_rows(sync_rows)
        for op in ops:
            kind, cid = op[0], op[1]
            if kind in ("contact_block", "hbresp_block"):
                self._drain_block(op, recover)
                if lt is not None and kind == "hbresp_block":
                    # the advisory lease tally, as the "hbresp" op below
                    for c, peer in zip(op[1], op[2]):
                        if lt.tracks(c):
                            lease_acks.setdefault(c, set()).add(peer)
                continue
            if cid not in self.eng.groups:
                continue
            try:
                if kind == "ack":
                    self.eng.ack(cid, op[2], op[3])
                    if obs_on:
                        # a leader's own append is staged as an ack too,
                        # and is no follower's acknowledgement
                        gi = self.eng.groups[cid]
                        if gi.slots.get(op[2]) != gi.self_slot:
                            self.acks_drained += 1
                elif kind == "vote":
                    self.eng.vote(cid, op[2], op[3])
                elif kind == "hbresp":
                    self.eng.heartbeat_resp(cid, op[2])
                    if lt is not None and lt.tracks(cid):
                        # lease tally rides the op walk already in flight
                        lease_acks.setdefault(cid, set()).add(op[2])
                elif kind == "contact":
                    self.eng.leader_contact(cid)
                elif kind == "randto":
                    self.eng.set_randomized_timeout(cid, op[2])
                elif kind == "rstage":
                    if obs_on:  # by origin, given a device slot or refused
                        if op[6]:
                            self.reads_remote += 1
                        else:
                            self.reads_local += 1
                    try:
                        slot = self.eng.stage_read(cid, count=1, index=op[2])
                    except RuntimeError:
                        # every pending-read slot holds an unconfirmed
                        # batch: leave this ctx to the scalar fallback
                        # (its echoes arrive as unknown-ctx racks below)
                        self.reads_refused += 1
                        self._read_gone_note(
                            cid, (op[3], op[4]), "slot_overflow"
                        )
                        slot = None
                    else:
                        self.reads_staged += 1
                        self._read_pending.setdefault(cid, []).append(
                            (slot, op[3], op[4], op[5])
                        )
                    if rt:  # instant ``s``: given a slot, or refused
                        rc = rt.get((cid, op[3], op[4]))
                        if rc is not None and rc.s is None:
                            rc.s = time.perf_counter()
                            if slot is None:
                                rc.path = "scalar:slot_overflow"
                            else:
                                rc.path = "device"
                                self._rt_staged.append(rc)
                elif kind == "rack":
                    node_id, low, high = op[2], op[3], op[4]
                    slot = None
                    for sl, lo, hi, _t in self._read_pending.get(cid, ()):
                        if lo == low and hi == high:
                            slot = sl
                            break
                    if slot is not None:
                        self.read_acks += 1
                        self.eng.read_ack(cid, node_id, slot)
                    else:
                        # ctx not device-tracked: scalar tally under
                        # raftMu — confirm() on an unknown ctx is a
                        # no-op.  Counted by cause: refused a slot,
                        # already confirmed / prefix-released, or (not
                        # remembered either way) dropped by a transition
                        self.read_fallback_causes[
                            self._read_gone.get(cid, {}).get(
                                (low, high), "purged"
                            )
                        ] += 1
                        node = self._nodes.get(cid)
                        if node is not None:
                            node.offload_read_echo(node_id, low, high)
                    if rt:  # instant ``d``: the quorum's echo, drained
                        rc = rt.get((cid, low, high))
                        if (
                            rc is not None and rc.d is None
                            and rc.c is None and rc.eq_peer == node_id
                        ):
                            rc.d = time.perf_counter()
                elif kind == "kvops":
                    if self.devsm is not None:
                        self.devsm.handle_ops(cid, op[2])
                elif kind == "leader":
                    self._read_pending.pop(cid, None)
                    if rt:  # the ctxs of the new term are staged behind
                        self._read_traces_drop(cid, below_term=op[2])
                    if lt is not None:
                        lt.drop(cid)
                    self.eng.set_leader(
                        cid, term=op[2], term_start=op[3], last_index=op[4]
                    )
                    if self.devsm is not None:
                        self.devsm.on_leader(cid, op[4])
                elif kind == "candidate":
                    self._read_pending.pop(cid, None)
                    if rt:
                        self._read_traces_drop(cid, below_term=op[2] + 1)
                    if lt is not None:
                        lt.drop(cid)
                    self.eng.set_candidate(cid, term=op[2])
                    if self.devsm is not None:
                        self.devsm.on_unbind(cid)
                elif kind == "follower":
                    self._read_pending.pop(cid, None)
                    if rt:
                        self._read_traces_drop(cid, below_term=op[2] + 1)
                    if lt is not None:
                        lt.drop(cid)
                    self.eng.set_follower(cid, term=op[2])
                    if self.devsm is not None:
                        self.devsm.on_unbind(cid)
                elif kind == "qwake" or kind == "qsleep":
                    marks[cid] = kind == "qwake"
                else:  # resync
                    self._read_pending.pop(cid, None)
                    if rt:
                        # the scalar ReadIndex keeps these ctxs: their
                        # echoes are tallied by the step worker now
                        for key, rc in list(rt.items()):
                            if key[0] == cid and rc.path == "device":
                                rc.path = "scalar:purged"
                    if lt is not None:
                        lt.drop(cid)
                    if self.devsm is not None:
                        self.devsm.on_unbind(cid)
                    recover.append(cid)
            except (ValueError, KeyError):
                # unknown peer slot / index past the rebase window: rebuild
                # the row from scalar state (rare)
                recover.append(cid)
        if lt is not None and lease_acks:
            lt.note_round(lease_acks, self._tick_seen)
        for cid, wake in marks.items():
            if cid in self.eng.groups and cid not in recover:
                self.eng.quiesce_mark(cid, wake)
        return recover

    def _recover_row(self, cluster_id: int) -> None:
        """Rebuild a row from scalar state.  Lock order: raft_mu FIRST,
        then _mu (matching register/fast_eject) — never call under _mu."""
        node = self._nodes.get(cluster_id)
        if node is None:
            return
        with node.raft_mu:
            if node.peer is None:
                return
            with self._mu:
                if cluster_id not in self.eng.groups:
                    return
                try:
                    self.eng.rebase(cluster_id)
                except Exception:
                    pass
                self._sync_row_locked(node)

    # ------------------------------------------------------------------
    # the round
    # ------------------------------------------------------------------

    def _round_main(self) -> None:
        # Deprioritize this thread (Linux per-thread niceness, default +5,
        # DBTPU_ENGINE_NICE overrides, 0 disables).  The round thread is
        # a batch amortizer — a delayed round just batches more events —
        # but its dispatches (and the jax runtime work they trigger)
        # compete with the raft/transport threads for cycles on a
        # core-starved box: the e2e A/B's bimodal throughput (a ~6.6k
        # w/s mode whenever the scheduler favored this thread; PERF.md
        # round-5 §3) hit 3 of 8 un-niced runs and 0 of 6 niced ones
        # (validated at both +10 and this +5 default; mean up ~22%).
        # On an idle machine niceness changes nothing — a niced thread
        # with a free core still runs immediately.
        import os as _os

        try:
            nice = int(_os.environ.get("DBTPU_ENGINE_NICE", "5"))
        except ValueError:
            plog.warning("malformed DBTPU_ENGINE_NICE; using default 5")
            nice = 5
        if nice:
            try:
                _os.setpriority(
                    _os.PRIO_PROCESS, threading.get_native_id(), nice
                )
            except (OSError, AttributeError) as e:
                # the perf fix silently not applying must be attributable
                # (the bimodal slow mode would return with no clue)
                plog.warning("engine round-thread nice failed: %r", e)
        while not self._stopped.is_set():
            if self._obs is not None:
                # named, so a device idle gap reads "round thread had
                # nothing to do" instead of no_event_traced
                with _annotate("round_idle"):
                    fired = self._pending.wait(timeout=self._interval)
            else:
                fired = self._pending.wait(timeout=self._interval)
            if self._stopped.is_set():
                return
            if fired:
                self._pending.clear()
            try:
                self._round()
            except Exception:
                plog.exception("tpu quorum round failed")

    def _round(self) -> None:
        recover: list = []
        try:
            if self._obs is not None:
                # every turn of the round thread is a dbtpu:round in a
                # capture; one that dispatches nothing (the quiet poll
                # every interval_s, a drain of leader contacts) holds no
                # dbtpu:fanout and has no coord_round span: the ring is
                # the count of dispatched rounds
                with _annotate("round"):
                    self._round_inner(recover)
            else:
                self._round_inner(recover)
        finally:
            if recover:
                # rare-path row rebuilds, OUTSIDE _mu (lock order: raft_mu
                # then _mu); the recovered rows step next round
                for cid in dict.fromkeys(recover):
                    self._recover_row(cid)
                self._pending.set()

    def _round_inner(self, recover: list) -> None:
        obs = self._obs
        t0 = time.perf_counter() if obs is not None else 0.0
        span = None
        n_ops = 0
        k_rounds = 1
        fused = False
        fuse_skip = None
        with self._mu:
            seq = self._tick_seq
            # catch up missed ticks (a slow round — first-use compile,
            # contended host — can span several host ticks; the scalar
            # path replays every LOCAL_TICK the same way).  Fused-ready
            # rounds replay up to fused_k_max ticks in ONE dispatch;
            # before warmup completes the cap stays at 4 so the per-step
            # fallback can't turn a stall into a dispatch storm.
            fused_ok = self.drive_ticks and self.eng.fused_ready
            cap = self.fused_k_max if fused_ok else 4
            missed = seq - self._tick_seen if self.drive_ticks else 0
            deficit = min(missed, cap)
            do_tick = deficit > 0
            self._tick_seen = seq
            if obs is not None:
                n_ops = len(self._staged)  # racy read, gauge-grade
            with (obs.phase("drain") if obs is not None else _OFF):
                recover.extend(self._drain_locked())
                if self.devsm is not None:
                    # advance pending devsm binds (host apply catching
                    # the promotion watermark completes them)
                    self.devsm.poll()
            has_acks = bool(
                self.eng._acks or self.eng._ack_blocks or self.eng._votes
            )
            # staged read ctxs / heartbeat echoes must dispatch even
            # on an otherwise-quiet round: with drive_ticks off (or
            # a quiet group) nothing else would ever flush them and
            # the pending ReadIndex would hang until client timeout
            has_reads = self.eng._reads_pending()
            # ... and so must staged devsm entry ops / KV read captures
            # (a parked lookup is waiting on exactly one dispatch)
            has_kv = self.eng._kv_pending()
            # dirty-only rounds (row registrations, transition
            # replays with no queued events) need no dispatch when
            # ticks drive regular rounds anyway: the upload
            # piggybacks on the next event/tick round.  Bulk
            # registration of thousands of groups otherwise
            # interleaves a dispatch between every few registers.
            dirty_gate = bool(self.eng._dirty and not self.drive_ticks)
            if not (do_tick or has_acks or has_reads or has_kv or dirty_gate):
                return
            if obs is not None:
                # the round dispatches: open its span BEFORE the first
                # dispatch, so the engine's spans name it as their parent
                first = self._round_first_at
                span = obs.round_open(
                    t0=t0,
                    gate="+".join(
                        name
                        for name, hit in (
                            ("tick", do_tick), ("acks", has_acks),
                            ("reads", has_reads), ("kv", has_kv),
                            ("dirty", dirty_gate),
                        )
                        if hit
                    ),
                    wait_ms=(
                        max(0.0, t0 - first) * 1e3
                        if first is not None else 0.0
                    ),
                )
                self.eng.set_span_parent(span["seq"])
                self._rounds_recorded += 1
                if self._rt_staged:
                    for rc in self._rt_staged:
                        rc.stage_round = span["seq"]
                    self._rt_staged = []
            # Adaptive K-round batching (ISSUE 7 tentpole).  The fused
            # K-round program (step_rounds, the ladder's workhorse) was
            # once measured here and reverted because each first-use XLA
            # compile of a fused variant cost 0.5-4s and stalled
            # proposals behind it; the warmup pass killed the stall
            # instead of the feature — AOT warm-compile of the padded
            # (K,G,P) program set at enable time, persisted across
            # restarts by the XLA compilation cache.  Policy:
            #   - quiet rounds (deficit <= 1) keep the single-round
            #     program — identical dispatch, identical latency;
            #   - a tick backlog replays as ONE fused dispatch: the
            #     staged events ride round 0 and the remaining deficit
            #     ticks run as event-free padding rounds (tick_rounds),
            #     padded to the nearest warm K bucket so the whole
            #     adaptive range reuses len(buckets) compiled programs;
            #   - rounds carrying VOTES fall back to the single-round
            #     path (elections want the fastest round, not a batched
            #     one — and the fused vote variant is deliberately not
            #     warmed);
            #   - until warmup completes, the per-step replay below
            #     keeps using the already-compiled single-round
            #     programs, so a proposal NEVER waits on XLA
            #     (fuse_skip span field: "warmup"/"votes").
            # Semantics are unchanged either way: epoch filters resolve
            # at dispatch exactly like the single-round path (no round
            # is sealed mid-drain), and a deficit-K block is precisely
            # the old step + (K-1) tick replays in one program
            # (differential: tests/test_live_fused.py).
            has_votes = bool(self.eng._votes)
            # the coordinator itself never stages in-program recycles
            # (membership changes resync through the host rare path),
            # but a hybrid caller driving stage_recycle/begin_round on
            # this engine could leave churn in the backlog — and the
            # warmed program set deliberately excludes the has_churn
            # variant, so fusing it would reintroduce the first-use
            # compile stall this PR exists to kill
            has_churn = bool(self.eng._churn or self.eng._round_blocks)
            # a kv-carrying block needs the has_kv fused variants warmed
            # (warmup_devsm, kicked at plane registration) — until then
            # kv rounds take the already-compiling dense single-round
            # path instead of stalling a fused dispatch behind XLA.
            # BUFFERED device ents force the fold too (the engine runs
            # has_kv on every dispatch while any op awaits its commit —
            # see _kv_ents_buffered), so they gate fusing the same way
            kv_unwarmed = (
                has_kv or self.eng._kv_ents_buffered()
            ) and not self.eng.kv_fused_ready
            read_confirms: list = []
            if deficit > 1:
                if not fused_ok:
                    # distinguish a mesh coordinator's per-shard program
                    # sets still warming from the single-device case —
                    # the readiness latch is all-shards-ready
                    fuse_skip = (
                        "mesh_warmup" if self.mesh_devices > 1 else "warmup"
                    )
                elif has_votes:
                    fuse_skip = "votes"
                elif has_churn:
                    fuse_skip = "churn"
                elif kv_unwarmed:
                    fuse_skip = "devsm"
            if (
                fused_ok and deficit > 1 and not has_votes
                and not has_churn and not kv_unwarmed
            ):
                fused = True
                k_rounds = deficit
                # guarantee >= 1 round even on a pure tick-catch-up
                # round with nothing staged
                self.eng.begin_round()
                res = self.eng.step_rounds(
                    do_tick=True,
                    pad_rounds_to=self._k_bucket(deficit),
                    tick_rounds=deficit,
                )
                if res is None:
                    # a mesh engine dispatches nothing while no shard
                    # owns a group (a tick backlog before registration /
                    # after teardown)
                    from .ops.engine import StepResult

                    res = StepResult()
                else:
                    self.fused_dispatches += 1
                self._collect_read_confirms(res, read_confirms)
            else:
                # per-step replay keeps the historical 4-tick cap even
                # when the fused gate (votes, warmup) bounced a deeper
                # backlog here: one skipped fuse must not become a
                # 16-dispatch storm (excess ticks are swallowed, exactly
                # as the old cap swallowed them)
                deficit = min(deficit, 4)
                res = self.eng.step(do_tick=do_tick)
                self._collect_read_confirms(res, read_confirms)
                for _ in range(deficit - 1):  # replay remaining ticks
                    extra = self.eng.step(do_tick=True)
                    res.commit.update(extra.commit)
                    self._collect_read_confirms(extra, read_confirms)
                    for field in (
                        "won", "lost", "elect", "heartbeat", "demote",
                        "quiesce",
                    ):
                        merged = set(getattr(res, field))
                        merged.update(getattr(extra, field))
                        setattr(res, field, list(merged))
            n_rows = len(self.eng.groups)
        # The tick deficit's rule.  A round runs every host tick since the
        # last one, up to the largest warmed K (4 until the fused programs
        # are warm, and whenever votes or churn keep a backlog off them);
        # ticks beyond that are DROPPED: this host's device clocks run that
        # much slow, which delays its own followers' elections and widens
        # its check-quorum windows, and deposes nobody.  Both are counted.
        # What a stalled host must not do is read its own stall as its
        # leaders' silence: the contacts that arrived meanwhile were all
        # applied before the replayed ticks, and its scalar clocks catch
        # up by the whole stall at their next step.  A host that was away
        # for an election timeout or more therefore HOLDS its elections:
        # the round's election-due flags are not fanned out and every row
        # of the host gets its election clock reset (a staged contact
        # block, a no-op on leader rows) — one more timeout, by which a
        # leader that really is gone is found.
        dropped = missed - deficit
        held = 0
        if deficit > 1:
            self.ticks_replayed += deficit - 1
        if dropped > 0:
            self.ticks_dropped += dropped
        if missed >= self._min_election_timeout:
            held = len(res.elect)
            self.elections_held += held
            res.elect = []
            self._stage_block(("contact_block", list(self._nodes)))
        with (obs.phase("fanout") if obs is not None else _OFF):
            self._fan_out(
                res, read_confirms, do_tick,
                span["seq"] if span is not None else None,
            )
        # the state blocks the step replaced die here, once the round's
        # commits are offloaded: each death hands the interpreter away
        self.eng.drop_retired()
        if obs is not None:
            if self.lease_table is not None:
                # advisory lease-coverage gauge (dragonboat_lease_groups_
                # held), refreshed from the drain-fed table — device-plane
                # lease introspection with zero raftMu traffic
                self.lease_table.publish(obs.registry, self._tick_seen)
            # the recorder's stall check on wall_ms IS the round-gate
            # watchdog: a round outlasting stall_ms (wedged dispatch,
            # first-compile storm) auto-dumps the ring
            # with this span as the trigger
            # the read-plane counters since the last RECORDED round: a
            # round that only drained (a scalar-side echo, no dispatch)
            # has no span, so its counts ride the next one that has
            causes = self.read_fallback_causes
            reads0 = self._reads_spanned
            self._reads_spanned = (
                self.read_acks, self.reads_staged, self.reads_refused,
                dict(causes),
            )
            obs.round(
                span,
                ops=n_ops,
                deficit=deficit,
                commits=len(res.commit),
                staged_depth=len(self._staged),
                k_rounds=k_rounds,
                fused=fused,
                fuse_skip=fuse_skip,
                read_acks=self.read_acks - reads0[0],
                reads_staged=self.reads_staged - reads0[1],
                reads_refused=self.reads_refused - reads0[2],
                read_fallbacks={
                    c: causes[c] - n for c, n in reads0[3].items()
                },
                plane=self._plane_account(
                    res, do_tick, deficit, dropped, held, n_rows
                ),
                fan_in=self._fan_in_account(),
            )
        # cost-driven placement (mesh dispatch plane): a time-gated
        # rebalance pass on dispatched rounds only — quiet coordinators
        # have no load to balance.  Runs under _mu like every other
        # engine access; the pass is bounded (one migration) and bails
        # unless the shard cost EMAs actually skew.
        if self.mesh_devices > 1:
            now = time.monotonic()
            if now >= self._next_rebalance:
                self._next_rebalance = now + self._rebalance_interval
                try:
                    with self._mu:
                        self.eng.maybe_rebalance()
                except Exception:
                    plog.exception("mesh rebalance failed")

    def _fan_out(self, res, read_confirms: list, do_tick: bool,
                 round_seq: Optional[int]) -> None:
        """Everything of a round after the engine returned, OUTSIDE _mu:
        trace stamps, read-confirm and commit offloads, the tick flags,
        election outcomes (the ``fanout_ms`` of the round's span;
        ``round_seq`` is that span's seq, None while obs is off)."""
        # (devsm KV read captures were already delivered by the engine's
        # kv_egress_hook inside each harvest — see devsm_plane())
        # confirmed-read releases, OUTSIDE _mu like the commit callbacks:
        # the node re-checks leader/term under raftMu and releases through
        # the scalar ReadIndex prefix pop (indices identical to the pure
        # scalar path — tests/test_read_confirm.py).  With the host plane
        # attached, effects are flagged with wake=False and the step
        # wakeups coalesce to one per touched group at the end of the
        # round (hostplane.wake_nodes) — a commit+tick+read round for one
        # group costs one CV notify instead of three.
        tracer = self.tracer
        if tracer is not None and (res.commit or read_confirms):
            # stamp the device round BEFORE the offload fan-out (the
            # apply stamp must sort after this one), linking the span
            # seq of the dispatch that served this round.  The common
            # round has no read confirms — iterate res.commit's keys
            # directly instead of building a merged set (this block is
            # on the round thread, the tpu path's bottleneck)
            seq = self.eng.last_span_seq
            if read_confirms:
                cids = set(res.commit)
                cids.update(c for c, _l, _h, _t in read_confirms)
            else:
                cids = res.commit
            tracer.mark_clusters(
                cids, seq if seq >= 0 else None, round_seq
            )
        replattr = self.replattr
        if replattr is not None and res.commit:
            # device-plane commit attribution (ISSUE 14): link THIS
            # round's dispatch span into the groups' open commit records
            # before the offload fan-out closes them under raftMu — the
            # closed record then cites the same span the request trace
            # links via mark_clusters above
            seq = self.eng.last_span_seq
            if seq >= 0:
                for cid in res.commit:
                    replattr.note_device_round(cid, seq)
        hp = self.hostplane
        touched: dict = {}
        # wake_kw stays EMPTY without the host plane so duck-typed test
        # nodes that predate the wake kwarg keep working unchanged
        wake_kw: dict = {} if hp is None else {"wake": False}
        if self._rt_confirmed:
            # instant ``c``: the step confirmed these sampled ctxs (or a
            # later ctx of their group, whose release takes them along)
            now = time.perf_counter()
            for rc in self._rt_confirmed:
                if rc.c is None:
                    rc.c = now
                    rc.confirm_round = round_seq
                    rc.rounds = self._rounds_recorded - rc.round0
            self._rt_confirmed = []
        for cid, low, high, term in read_confirms:
            node = self._nodes.get(cid)
            if node is not None:
                node.offload_read_confirm(low, high, term, **wake_kw)
                if hp is not None:
                    touched[cid] = node
        for cid, q in res.commit.items():
            node = self._nodes.get(cid)
            if node is not None:
                node.offload_commit(q, **wake_kw)
                if hp is not None:
                    touched[cid] = node
        # device tick flags: election due / heartbeat due / check-quorum
        # demote — applied through the scalar handlers under raftMu with
        # all guards intact (stale flags are rejected there)
        if do_tick:
            for cid in res.elect:
                node = self._nodes.get(cid)
                if node is not None:
                    node.offload_tick_elect(**wake_kw)
                    if hp is not None:
                        touched[cid] = node
            due, demote = res.heartbeat, res.demote
            if len(due) > 1 and self._hb_link is not None:
                # more than one row due: by the block; what comes back
                # are the groups whose state takes the per-group message
                demote = set(demote)
                due = self._heartbeat_block(due, demote)
            for cid in due:
                node = self._nodes.get(cid)
                if node is not None:
                    node.offload_tick_heartbeat(**wake_kw)
                    if hp is not None:
                        touched[cid] = node
            for cid in demote:
                node = self._nodes.get(cid)
                if node is not None:
                    node.offload_tick_demote(**wake_kw)
                    if hp is not None:
                        touched[cid] = node
            for cid in res.quiesce:
                # the row went to sleep: the replica's one turn of the
                # sleep (it tells its peers)
                node = self._nodes.get(cid)
                if node is not None:
                    node.offload_quiesce_enter(**wake_kw)
                    if hp is not None:
                        touched[cid] = node
        if hp is not None and touched:
            hp.wake_nodes(touched.values())
        # tag election outcomes with the term the row held when the round
        # ran: during long dispatches (first jit compile, busy host) the
        # scalar side may have restarted the campaign at a higher term, and
        # a stale won-flag must never promote a later-term candidate that
        # lacks a quorum at that term
        won_terms = {}
        lost_terms = {}
        if res.won or res.lost:
            with self._mu:
                groups = self.eng.groups
                decided = [
                    (cid, groups[cid].row, cid_list is res.won)
                    for cid_list in (res.won, res.lost)
                    for cid in cid_list if cid in groups
                ]
                if len(decided) > 1 and self.mesh_devices <= 1:
                    # a campaign wave decides hundreds of elections in
                    # one round: ONE gather for their terms, not a device
                    # read a group under _mu (seconds of a round, in
                    # which no heartbeat of this host left)
                    terms = self.eng.read_rows(
                        "term", [row for _c, row, _w in decided]
                    ).tolist()
                else:
                    terms = [
                        int(self.eng._read("term", row))
                        for _c, row, _w in decided
                    ]
                for (cid, _row, won), term in zip(decided, terms):
                    (won_terms if won else lost_terms)[cid] = int(term)
        for cid, term in won_terms.items():
            node = self._nodes.get(cid)
            if node is not None:
                node.offload_election(True, term)
        for cid, term in lost_terms.items():
            node = self._nodes.get(cid)
            if node is not None:
                node.offload_election(False, term)

    def _plane_account(self, res, do_tick: bool, deficit: int, dropped: int,
                       held: int, n_rows: int) -> dict:
        """The tick and heartbeat plane's fields of a round's span (obs
        on).  Block messages arrive between rounds, so the block / single
        counts are those since the last recorded round."""
        blocks0, busy0, single0 = self._plane_spanned
        causes = self.hb_single_causes
        self._plane_spanned = (
            self.hb_block_rows, self.hb_lite_rows, dict(causes)
        )
        quiesce = {}
        if self.eng.quiesce_enabled:
            enters0, wakes0 = self._quiesce_spanned
            self._quiesce_spanned = (self.quiesce_enters, self.quiesce_wakes)
            quiesce = {
                "rows_quiesced": self.rows_quiesced,
                "quiesce_enters": self.quiesce_enters - enters0,
                "quiesce_wakes": self.quiesce_wakes - wakes0,
            }
        return {
            **quiesce,
            "ticks_replayed": max(deficit - 1, 0),
            "ticks_dropped": dropped,
            "elect_held": held,
            "hb_flags": len(res.heartbeat) if do_tick else 0,
            "elect_flags": len(res.elect) if do_tick else 0,
            "demote_flags": len(res.demote) if do_tick else 0,
            "hb_block_rows": self.hb_block_rows - blocks0,
            "hb_lite_rows": self.hb_lite_rows - busy0,
            "hb_single": {c: causes[c] - n for c, n in single0.items()},
            "rows": n_rows,
        }

    def _fan_in_account(self) -> dict:
        """What the drains since the last recorded round handed on (obs
        on; a round that only drained has no span, so its counts ride
        the next one that has)."""
        acks0, local0, remote0 = self._drained_spanned
        self._drained_spanned = (
            self.acks_drained, self.reads_local, self.reads_remote
        )
        return {
            "acks_drained": self.acks_drained - acks0,
            "reads_local": self.reads_local - local0,
            "reads_remote": self.reads_remote - remote0,
        }

    def _collect_read_confirms(self, res, out: list) -> None:
        """Map confirmed-read egress slots back to their ctxs (under _mu).

        A confirmed slot releases its ctx AND — through the scalar prefix
        release — every ctx staged before it; the earlier ctxs' engine
        slots are cancelled here so they don't leak until a transition
        purge.  Ctxs no longer tracked (a transition purged the group's
        FIFO after the dispatch was staged) drop silently: the node-side
        term guard would reject them anyway."""
        if res.read_cids is None or not len(res.read_cids):
            return
        for cid, slot, _index, _count in res.reads:
            fifo = self._read_pending.get(cid)
            if not fifo:
                continue
            pos = next(
                (i for i, e in enumerate(fifo) if e[0] == slot), None
            )
            if pos is None:
                continue
            _slot, low, high, term = fifo[pos]
            if self._read_traces:
                for e in fifo[: pos + 1]:
                    rc = self._read_traces.get((cid, e[1], e[2]))
                    if rc is not None:
                        self._rt_confirmed.append(rc)
            for e in fifo[:pos]:  # prefix-released scalar-side
                self._read_gone_note(cid, (e[1], e[2]), "after_confirm")
                try:
                    self.eng.cancel_read(cid, e[0])
                except (ValueError, KeyError):
                    pass
            self._read_gone_note(cid, (low, high), "after_confirm")
            del fifo[: pos + 1]
            self.read_confirms += 1
            out.append((cid, low, high, term))

    def flush(self) -> None:
        """Run one round synchronously (tests)."""
        self._round()

    def stop(self) -> None:
        self._stopped.set()
        self.eng.cancel_warmup()
        self._pending.set()
        self._thread.join(timeout=5)
        stop_streams = getattr(self.eng, "stop", None)
        if stop_streams is not None:  # mesh facade: join shard streams
            stop_streams()
