"""NodeHost: the public facade hosting many raft groups in one process.

Reference: ``nodehost.go`` — lifecycle (``NewNodeHost``, ``StartCluster`` ×3
SM kinds, ``StopCluster``), request APIs (sync/async propose, linearizable
read, membership changes, snapshots, leader transfer), the cluster registry
with its change counter, tick fan-out and incoming-message routing.
"""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

from . import vfs
from .client import Session
from .events import (
    RaftEventListener,
    SysEventListener,
    SystemEvent,
    SystemEventType,
)
from .config import Config, NodeHostConfig
from .engine import Engine
from .logdb import LogReader, open_logdb
from .logger import get_logger
from .node import _FAST_WIRE_TYPES, Node
from .raft.peer import PeerAddress
from .requests import (
    ClusterAlreadyExistError,
    ClusterNotFoundError,
    RejectedError,
    RequestResult,
    RequestState,
    TimeoutError_,
)
from .settings import Soft
from .rsm import (
    SSReqType,
    SSRequest,
    StateMachine,
    from_concurrent_sm,
    from_on_disk_sm,
    from_regular_sm,
)
from .snapshotter import Snapshotter
from .statemachine import Result
from .transport import Registry, Transport, create_transport
from .wire import (
    Bootstrap,
    ConfigChange,
    ConfigChangeType,
    Membership,
    Message,
    MessageBatch,
    MessageType,
    StateMachineType,
)

plog = get_logger("nodehost")

_HB_BLOCK_TYPES = (
    MessageType.HEARTBEAT_BLOCK, MessageType.HEARTBEAT_RESP_BLOCK,
)


@dataclass
class ClusterInfo:
    """Snapshot of one managed Raft cluster node (reference
    ``nodehost.go:163`` ``ClusterInfo``)."""

    cluster_id: int = 0
    node_id: int = 0
    nodes: Dict[int, str] = field(default_factory=dict)
    observers: Dict[int, str] = field(default_factory=dict)
    witnesses: Dict[int, str] = field(default_factory=dict)
    config_change_index: int = 0
    state_machine_type: StateMachineType = StateMachineType.REGULAR
    is_leader: bool = False
    is_observer: bool = False
    is_witness: bool = False
    pending: bool = False  # nothing applied yet — details unavailable


@dataclass
class NodeHostInfo:
    """Host-wide inventory (reference ``nodehost.go:193``
    ``NodeHostInfo``): the managed clusters plus every (cluster, node)
    with raft state in the LogDB."""

    raft_address: str = ""
    cluster_info_list: list = field(default_factory=list)
    log_info: list = field(default_factory=list)  # [(cluster_id, node_id)]


class NodeHost:
    """Reference ``nodehost.go:246`` ``NodeHost``."""

    def __init__(self, nhconfig: NodeHostConfig):
        nhconfig.validate()
        nhconfig.prepare()
        self.nhconfig = nhconfig
        self._mu = threading.Lock()
        self._clusters: Dict[int, Node] = {}
        self._csi = 0  # cluster-set change counter (reference clusterMu.csi)
        self._stopped = threading.Event()
        # global tick counter (lazy tick delivery): nodes with native/
        # device-owned raft clocks read this at step time instead of
        # receiving one LOCAL_TICK message per RTT each
        self.tick_count = 0
        # filesystem the snapshot paths go through (ExpertConfig.fs lets
        # tests run diskless via vfs.MemFS or inject faults via vfs.ErrorFS,
        # which is auto-detected like the reference nodehost.go:321-327)
        self._fs = nhconfig.expert.fs or nhconfig.fs or vfs.DEFAULT
        self._capture_panics = vfs.is_error_fs(self._fs)
        # event/metrics plumbing (reference event.go; delivery thread
        # nodehost.go:1748-1769)
        self.sys_events = SysEventListener(nhconfig.system_event_listener)
        self.raft_events = RaftEventListener(
            nhconfig.raft_event_listener, enabled=nhconfig.enable_metrics
        )
        # shared leader-lease instruments (ISSUE 10), created lazily by
        # the first lease-enabled group when enable_metrics is on
        self._lease_obs = None
        # shared hierarchical-commit instruments (ISSUE 18), created
        # lazily by the first hier-enabled group when enable_metrics is on
        self._hier_obs = None
        # storage
        in_memory = nhconfig.node_host_dir == ":memory:"
        # directory management: deployment-id layout + flock + compat flag
        # file (reference internal/server/context.go:73-378).  A second
        # NodeHost on the same dir fails fast; a changed hard setting
        # refuses to open instead of corrupting data.
        self.server_ctx = None
        if not in_memory:
            from .server.context import ServerContext

            self.server_ctx = ServerContext(nhconfig)
            did = nhconfig.get_deployment_id()
            data_dir, _ = self.server_ctx.create_nodehost_dir(did)
            self.server_ctx.lock_nodehost_dir()
            self.server_ctx.check_nodehost_dir(
                did, nhconfig.raft_address, "nativekv"
            )
        # shard-count priority: expert override > logdb config > the
        # step-worker count; a directory that exists keeps the count it
        # has (open_logdb).  Shards aligned with the step workers
        # reproduce the reference's DoubleFixedPartitioner geometry
        # (server/partition.go:59): one worker round → one shard → one
        # fsynced write batch
        if nhconfig.logdb_factory is not None:
            self.logdb = nhconfig.logdb_factory(nhconfig)
        else:
            self.logdb = open_logdb(
                "" if in_memory else os.path.join(data_dir, "logdb"),
                **nhconfig.open_logdb_args(),
            )
        # delayed snapshot-status feedback (reference feedback.go:23-129):
        # transport-reported send status is parked and released to raft
        # later; the follower's SNAPSHOT_RECEIVED ack accelerates it.
        # Created before the transport so an early inbound message can't
        # race the attribute into existence.
        from .feedback import SnapshotFeedback

        self.snapshot_feedback = SnapshotFeedback(
            self._push_snapshot_status,
            push_delay_ms=Soft.snapshot_status_push_delay_ms,
        )
        # transport.  The listener accepts connections the moment it binds,
        # and a restarted host's peers reconnect INSTANTLY under load — the
        # router must drop inbound batches until construction completes
        # (raft resends cover the gap; round-4 soak: dispatching into a
        # half-built NodeHost killed receiver threads with AttributeError)
        self._router_ready = False
        self._router_gated_drops = 0
        # quorum_engine="auto" may need a probe dispatch (first use
        # compiles, seconds).  Run it BEFORE the listener binds whenever
        # the fast lane cannot be on — inside the gated window it would
        # silently black-hole inbound traffic for the whole probe
        expert = nhconfig.expert
        self._probe_ok = None
        if expert.quorum_engine == "auto" and not expert.fast_lane:
            self._probe_ok = self._dispatch_within_budget()
        self.node_registry = Registry()
        self.transport: Transport = create_transport(
            nhconfig,
            self.node_registry,
            self._message_router,
            self._snapshot_status,
            unreachable_handler=self._unreachable,
            snapshot_dir_fn=self.snapshot_dir,
            sys_events=self.sys_events,
            snapshot_received_handler=self._snapshot_received,
            # the dragonboat_transport_* families land in THIS host's
            # registry (ISSUE 14 satellite) so the /metrics endpoint and
            # write_health_metrics actually expose them
            metrics_registry=self.raft_events.registry,
        )
        self.logdb.on_compaction = lambda cid, nid: self.sys_events.publish(
            SystemEvent(
                type=SystemEventType.LOGDB_COMPACTED, cluster_id=cid, node_id=nid
            )
        )
        # native replication fast lane (ExpertConfig.fast_lane): enrolled
        # groups' steady-state replication runs in C++ (fastlane.py).
        # Built BEFORE the engine choice: "auto" depends on it.
        self.fastlane = None
        if expert.fast_lane:
            from .fastlane import FastLaneManager

            mgr = FastLaneManager(self)
            if mgr.enabled:
                self.fastlane = mgr
                # netsplit injection coverage for the paths that do NOT
                # ride the native streams (snapshot jobs, chunks,
                # Python-socket sends) — see fastlane.set_partition
                self.transport.partition_filter = mgr.is_partitioned
        # TPU quorum plugin (the north star's plugin/tpuquorum boundary):
        # "tpu" routes hot-path tallying through the batched device engine;
        # "scalar" leaves the pure-host path untouched; "auto" picks by
        # deployment shape + measured dispatch budget (r4 A/B at rung 3:
        # with the fast lane at ~1.0 enrollment duty the device engine's
        # per-tick dispatches are pure CPU competition — 6.3k vs 8.8k w/s —
        # so auto uses the device only when the lane is NOT carrying
        # steady state, and only when a dispatch fits the latency budget)
        self.quorum_coordinator = None
        engine_choice = expert.quorum_engine
        if engine_choice == "auto":
            if self.fastlane is not None:
                engine_choice = "scalar"
            elif self._probe_ok is not None:
                engine_choice = "tpu" if self._probe_ok else "scalar"
            else:
                # fast lane requested but could not enable, and no
                # pre-listener probe ran: probing NOW would black-hole
                # inbound traffic behind the router gate for up to the
                # probe timeout — default to scalar instead (the log
                # makes the unusual configuration visible)
                plog.warning(
                    "quorum_engine=auto: fast lane unavailable and no "
                    "pre-listener probe; defaulting to scalar"
                )
                engine_choice = "scalar"
            plog.info(
                "quorum_engine=auto resolved to %s (fast_lane=%s)",
                engine_choice, self.fastlane is not None,
            )
        self.quorum_engine_resolved = engine_choice
        # aggregate health sampling (ISSUE 20): resolved BEFORE the
        # coordinator so the engine's telemetry-fold latch flips ahead of
        # warmup — the warmed fused program set then already includes the
        # fold instead of paying a recompile on first use.
        health_aggregate = nhconfig.health_aggregate or (
            os.environ.get("DBTPU_HEALTH_AGGREGATE", "")
            in ("1", "true", "on")
        )
        # request tracing (obs/trace.py, ISSUE 9) is resolved BEFORE the
        # coordinator too: a host whose tracer is on also attaches the
        # coordinator's and engine's instruments (ISSUE 26: one switch),
        # so the tracer's device_round stamp links a flight-recorder span
        # and the spans exist in every traced run.
        trace_n = nhconfig.trace_sample_every
        if not trace_n:
            try:
                trace_n = int(os.environ.get("DBTPU_TRACE_SAMPLE", "0") or 0)
            except ValueError:
                # degrade like DBTPU_TRACE_STALL_MS: a malformed env var
                # must not fail every NodeHost construction
                plog.warning("malformed DBTPU_TRACE_SAMPLE; tracing off")
                trace_n = 0
        if engine_choice == "tpu":
            from .tpuquorum import TpuQuorumCoordinator

            self.quorum_coordinator = TpuQuorumCoordinator(
                capacity=expert.engine_block_groups
                or Soft.quorum_engine_block_groups,
                mesh_devices=expert.engine_mesh_devices,
                compilation_cache_dir=(
                    nhconfig.compilation_cache_dir or None
                ),
                telem=health_aggregate,
            )
            if self.fastlane is None:
                # batched heartbeat plane: the coordinator sends one
                # message a peer host a tick through this host's
                # transport (with the fast lane on, every raft message of
                # a remote rides its one ordered native stream instead)
                self.quorum_coordinator.attach_host_link(
                    self.node_registry.resolve, self.transport.send_to_host
                )
            if nhconfig.enable_metrics or trace_n > 0:
                # device-plane observability rides the same flag as the
                # raft event metrics, and the tracer's: the flight
                # recorder plus the engine/coordinator instrument
                # families land in this host's registry, so
                # write_health_metrics exposes device-plane health next
                # to the node/transport counters
                self.quorum_coordinator.enable_obs(
                    registry=self.raft_events.registry,
                    host=nhconfig.raft_address,
                )
            if expert.engine_warm_fused:
                # AOT warm-compile of the fused program set, AFTER the
                # obs wiring above so the warmup spans/metrics land in
                # this host's registry.  Background + niced: the round
                # thread keeps using the already-compiled single-round
                # programs until the readiness latch flips, so proposals
                # issued during warmup never block on compilation.
                self.quorum_coordinator.start_warmup()
        # compartmentalized host plane (ISSUE 8): proposal ingress
        # batcher + cross-shard group-commit WAL + decoupled apply/egress
        # executors.  Built BEFORE the engine (the committers persist
        # through its flusher, apply readiness routes to its pool); OFF by
        # default — nothing below is constructed and the scalar host path
        # stays bit-identical.
        self.hostplane = None
        # multi-process host tier (hostproc/, ISSUE 12): worker
        # processes behind shared-memory staging rings for the ingress
        # encode, the WAL redo-journal fsync cycle and the spawnable-SM
        # apply tier.  host_workers > 0 implies the compartmentalized
        # plane (the workers are its stages' execution resources); a
        # failed spawn degrades to the in-process plane with a log line
        # — never a failed NodeHost.
        self.hostproc = None
        if expert.host_workers > 0:
            from .hostproc.control import HostProcPlane

            try:
                self.hostproc = HostProcPlane(
                    workers=expert.host_workers,
                    encode_lanes=expert.host_ingress_shards or 2,
                )
            except Exception:
                plog.exception(
                    "hostproc spawn failed; in-process host plane"
                )
                self.hostproc = None
        if expert.host_compartments or self.hostproc is not None:
            from .hostplane import HostPlane

            self.hostplane = HostPlane(
                self.logdb,
                self._clusters.get,  # GIL-atomic dict get; None while
                # starting/stopped — the pool just skips the wakeup
                ingress_shards=expert.host_ingress_shards,
                ingress_ring=expert.host_ingress_ring,
                wal_window_ms=expert.host_wal_window_ms,
                apply_workers=expert.host_apply_workers,
                egress_workers=expert.host_egress_workers,
                # ErrorFS fault injection must reach the journaled
                # mode's actual durability point — but ONLY the
                # fault-injection vfs is threaded through: the journal
                # otherwise stays on the raw OS path next to the shard
                # stores (which never ride the snapshot vfs), keeping
                # write and REPLAY (open_logdb, raw OS) on one medium
                fs=self._fs if vfs.is_error_fs(self._fs) else None,
                hostproc=self.hostproc,
                wal_journal_mode=expert.host_wal_journal,
            )
            if nhconfig.enable_metrics:
                self.hostplane.enable_obs(
                    registry=self.raft_events.registry
                )
                if self.hostproc is not None:
                    self.hostproc.enable_obs(
                        registry=self.raft_events.registry
                    )
            if self.quorum_coordinator is not None:
                # the device-plane coordinator feeds the same tier: its
                # round fan-out coalesces step wakeups through the plane
                self.quorum_coordinator.hostplane = self.hostplane
        # cross-plane request tracing (obs/trace.py, ISSUE 9): allocate a
        # sampled 1-in-N trace context at propose/read time and stamp it
        # through ingress → raft step → WAL → device round → apply →
        # egress.  OFF by default (trace_sample_every=0 and no env):
        # nothing below is constructed and every request path keeps its
        # bit-identical trace=None latch.
        self.tracer = None
        self.replattr = None
        if trace_n > 0:
            from .obs.trace import Tracer

            self.tracer = Tracer(
                sample_every=trace_n,
                registry=self.raft_events.registry,
                recorder=(
                    self.quorum_coordinator.flight_recorder
                    if self.quorum_coordinator is not None else None
                ),
            )
            self.tracer.host = nhconfig.raft_address
            # replication attribution (obs/replattr.py, ISSUE 14): the
            # cross-host half of the tracer — sampled proposals carry a
            # ReplTrace over the wire and each commit's quorum close is
            # decomposed per peer.  Lives and dies with the tracer; peer
            # rows label by latency class when an injector is installed
            # (transport.latency, read dynamically — monkey.set_latency
            # may arrive after construction).
            from .obs.replattr import ReplAttr

            self.replattr = ReplAttr(
                host=nhconfig.raft_address,
                registry=self.raft_events.registry,
                recorder=(
                    self.quorum_coordinator.flight_recorder
                    if self.quorum_coordinator is not None else None
                ),
            )
            self.replattr.resolver = self.node_registry.resolve

            def _peer_class(addr: str):
                inj = self.transport.latency
                if inj is not None:
                    # per-pair asymmetric overrides reclassify the link
                    # (ISSUE 18 bugfix — a near peer behind an injected
                    # slow link must not label "near" in closer/laggard
                    # rows); peer_class falls back to the static domain
                    peer_class = getattr(inj, "peer_class", None)
                    if peer_class is not None:
                        return peer_class(nhconfig.raft_address, addr)
                    domain_of = getattr(inj, "domain_of", None)
                    if domain_of is not None:
                        return domain_of(addr)
                return None

            self.replattr.class_of = _peer_class
            self.tracer.replattr = self.replattr
            if self.quorum_coordinator is not None:
                self.quorum_coordinator.tracer = self.tracer
                self.quorum_coordinator.replattr = self.replattr
                # a sampled request that found its group asleep leaves a
                # ``quiesce_wake`` span at its end (ISSUE 44)
                cobs = self.quorum_coordinator._obs
                if cobs is not None:
                    self.tracer.wake_sink = cobs.quiesce_wake
        # cluster health plane (obs/health.py, ISSUE 13): low-rate
        # per-group/host health sampling + anomaly detectors + the live
        # scrape endpoint.  OFF by default (health_sample_ms=0 and no
        # env): nothing below is constructed — no sampler, no listener,
        # no dragonboat_health_* families — and the request paths keep
        # their bit-identical latches.
        self.health = None
        self.metrics_server = None
        health_ms = nhconfig.health_sample_ms
        if not health_ms:
            try:
                health_ms = int(
                    os.environ.get("DBTPU_HEALTH_SAMPLE_MS", "0") or 0
                )
            except ValueError:
                plog.warning("malformed DBTPU_HEALTH_SAMPLE_MS; health off")
                health_ms = 0
        if health_aggregate and self.quorum_coordinator is None:
            # the fold lives in the device quorum kernels; on a scalar
            # host the knob is inert (visible, not fatal — the devprof
            # inert-knob precedent)
            plog.warning(
                "health_aggregate set but no tpu quorum engine; "
                "aggregate sampling off"
            )
            health_aggregate = False
        if health_aggregate and health_ms <= 0:
            plog.warning(
                "health_aggregate set but the health plane is off "
                "(health_sample_ms=0); aggregate sampling off"
            )
        if health_ms > 0:
            from .obs.health import HealthSampler

            self.health = HealthSampler(
                self,
                sample_ms=health_ms,
                registry=self.raft_events.registry,
                recorder=self.flight_recorder,
                aggregate=health_aggregate,
            )
        # closed-loop recovery plane (obs/recovery.py, ISSUE 17): the
        # health detectors actuate guard-railed remediations.  OFF by
        # default (auto_recover=False and no env): nothing constructed,
        # no subscriber registered on the sampler (its ``_subs`` latch
        # stays None — asserted structurally in tests/test_recovery.py).
        self.recovery = None
        auto_recover = nhconfig.auto_recover or (
            os.environ.get("DBTPU_AUTO_RECOVER", "") in ("1", "true", "on")
        )
        if auto_recover:
            if self.health is None:
                # actuation without detection is meaningless; degrade
                # loudly (the devprof inert-knob precedent)
                plog.warning(
                    "auto_recover set but the health plane is off "
                    "(health_sample_ms=0); recovery off"
                )
            else:
                from .obs.recovery import RecoveryController

                dry = nhconfig.auto_recover_dry_run or (
                    os.environ.get("DBTPU_RECOVER_DRY_RUN", "")
                    in ("1", "true", "on")
                )
                self.recovery = RecoveryController(
                    self,
                    self.health,
                    dry_run=dry,
                    registry=self.raft_events.registry,
                    **dict(nhconfig.auto_recover_knobs),
                )
        # device capacity & profiling plane (obs/devprof.py, ISSUE 15):
        # HBM ledger + capacity model, warm-set program registry,
        # sampled device-time estimator and on-demand jax.profiler
        # capture windows over the batched quorum engine.  OFF by
        # default (device_profile=0 and no env): nothing constructed,
        # the engine keeps its bit-identical _devprof=None latch.
        self.devprof = None
        devprof_n = nhconfig.device_profile
        if not devprof_n:
            try:
                devprof_n = int(
                    os.environ.get("DBTPU_DEVICE_PROFILE", "0") or 0
                )
            except ValueError:
                plog.warning("malformed DBTPU_DEVICE_PROFILE; devprof off")
                devprof_n = 0
        if devprof_n > 0:
            if self.quorum_coordinator is None:
                # the plane profiles the DEVICE engine; on a scalar host
                # the knob is inert (visible, not fatal — the health
                # plane's degrade precedent)
                plog.warning(
                    "device_profile set but no tpu quorum engine; "
                    "devprof off"
                )
            else:
                from .obs.devprof import DevProf

                base = nhconfig.node_host_dir
                self.devprof = DevProf(
                    registry=self.raft_events.registry,
                    recorder=self.flight_recorder,
                    sample_every=devprof_n,
                    artifact_dir=(
                        base if base and base != ":memory:" else None
                    ),
                )
                self.quorum_coordinator.enable_devprof(self.devprof)
        metrics_addr = nhconfig.metrics_addr or os.environ.get(
            "DBTPU_METRICS_ADDR", ""
        )
        if metrics_addr:
            from .obs.health import MetricsServer

            try:
                self.metrics_server = MetricsServer(self, metrics_addr)
            except (OSError, ValueError) as e:
                # a taken port (OSError) or a malformed addr (ValueError
                # — possibly from the ENV fallback, which no config
                # validation covers) must not fail the whole NodeHost:
                # the raft planes are fine, only the scrape surface is
                # not (the DBTPU_HEALTH_SAMPLE_MS degrade precedent)
                plog.warning(
                    "metrics endpoint unavailable on %s: %r",
                    metrics_addr, e,
                )
        # engine
        workers = nhconfig.step_workers()
        self.engine = Engine(
            self._get_nodes,
            self.logdb,
            step_workers=workers,
            apply_workers=workers,
            get_csi=self._get_csi,
            hostplane=self.hostplane,
        )
        if self.tracer is not None:
            self.engine.tracer = self.tracer
        # replica-plane instruments (obs/instruments.py ReplicaObs, ISSUE
        # 37): snapshot saves, compactions, InstallSnapshot, the snapshot
        # pool and check-quorum windows, on the tracer's and
        # enable_metrics' one switch.  With both off nothing is built and
        # every site keeps its ``is None`` latch.
        self.replica_obs = None
        if nhconfig.enable_metrics or self.tracer is not None:
            from .obs.instruments import ReplicaObs

            self.replica_obs = ReplicaObs(
                recorder=self.flight_recorder,
                registry=self.raft_events.registry,
                host=nhconfig.raft_address,
                workers=self.engine.snapshot_workers,
            )
            self.engine.replica_obs = self.replica_obs
        # opt-in SIGUSR2 live-debug dump (ISSUE 9 satellite): the
        # handler sets the flag; the tick worker performs the dump
        self._dump_sig_old = None
        self._dump_requested = False
        if nhconfig.dump_signal:
            self._install_dump_signal()
        # ticks
        self._tick_thread = threading.Thread(
            target=self._tick_worker_main, name="tick-worker", daemon=True
        )
        self._tick_thread.start()
        self._router_ready = True

    @staticmethod
    def _dispatch_within_budget(budget_ms: float = 5.0) -> bool:
        """Probe one tiny batched-engine dispatch round trip, in process
        (a child could not have the chip once this process has touched
        jax).  A per-tick engine targeting <5ms commit p99 needs a
        dispatch well inside that; a local device costs ~0.2ms.  Only runs
        for quorum_engine="auto" without the fast lane.  A probe that
        ERRORS raises — only a probe that is SLOW chooses scalar."""
        from .ops.engine import BatchedQuorumEngine

        eng = BatchedQuorumEngine(8, 3, event_cap=16)
        eng.add_group(1, node_ids=[1, 2, 3], self_id=1)
        eng.set_leader(1, term=1, term_start=1, last_index=1)
        eng.step(do_tick=True)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            eng.step(do_tick=True)
            ts.append(time.perf_counter() - t0)
        p50_ms = sorted(ts)[1] * 1e3
        plog.info("auto-engine dispatch probe: p50 %.2fms", p50_ms)
        return p50_ms <= budget_ms

    # ---- dirs ----

    def snapshot_dir(self, cluster_id: int, node_id: int) -> str:
        if self.server_ctx is None:
            base = os.path.join(
                "/tmp", "dragonboat-tpu-mem",
                self.raft_address().replace(":", "_"),
            )
            return os.path.join(
                base, "snapshot", f"{cluster_id:020d}-{node_id:020d}"
            )
        return self.server_ctx.get_snapshot_dir(
            self.nhconfig.get_deployment_id(), cluster_id, node_id
        )

    def raft_address(self) -> str:
        return self.nhconfig.raft_address

    # ---- health metrics / observability ----

    @property
    def metrics_registry(self):
        """The registry this host's metrics publish into (raft events,
        transport, system events, and — when ``enable_metrics`` wired the
        device plane — the ``dragonboat_device_*``/``dragonboat_coord_*``
        families)."""
        return self.raft_events.registry

    def write_health_metrics(self, out) -> None:
        """Prometheus text exposition of this host's registry (reference
        ``WriteHealthMetrics``, ``nodehost.go``)."""
        self.raft_events.registry.write_health_metrics(out)

    @property
    def flight_recorder(self):
        """The device-plane flight recorder (None unless a quorum
        coordinator is running with observability enabled)."""
        qc = self.quorum_coordinator
        return qc.flight_recorder if qc is not None else None

    def dump_trace(self, path: Optional[str] = None,
                   limit: Optional[int] = None) -> dict:
        """Export the sampled request traces as Chrome-trace / Perfetto
        JSON (one proposal = one flow across host threads and device
        rounds; linked flight-recorder spans render on a
        ``device-plane`` track).  Requires tracing
        (``NodeHostConfig.trace_sample_every`` / ``DBTPU_TRACE_SAMPLE``).
        Returns the trace dict; also writes it to ``path`` when given —
        load the file at https://ui.perfetto.dev or about://tracing."""
        if self.tracer is None:
            raise RuntimeError(
                "tracing is off — set NodeHostConfig.trace_sample_every"
            )
        d = self.tracer.export_chrome(limit=limit)
        if path:
            with open(path, "w") as f:
                json.dump(d, f)
        return d

    def profile_device(
        self, ms: float = 1000.0, path: Optional[str] = None
    ) -> str:
        """Open an on-demand ``jax.profiler`` capture window for ``ms``
        milliseconds (obs/devprof.py, ISSUE 15) and return the artifact
        directory — written beside the ``dump_trace``/``debug_dump``
        artifacts so ``tools/trace_merge.py`` sessions and device
        profiles are collected from one place (load the result at
        https://ui.perfetto.dev).  Requires the device profiling plane
        (``NodeHostConfig.device_profile`` / ``DBTPU_DEVICE_PROFILE``);
        one window at a time — the profiler is process-global."""
        if self.devprof is None:
            raise RuntimeError(
                "device profiling is off — set "
                "NodeHostConfig.device_profile"
            )
        return self.devprof.capture(ms, path=path)

    def health_report(self) -> dict:
        """Aggregated cluster-health verdict (obs/health.py, ISSUE 13):
        open detector events, per-detector open/close counts, and the
        recovery-time attribution percentiles (failover / worker-respawn
        / devsm-rebind) derived from open→close durations.  ``status``
        is ``"ok"`` unless any detector is open — the ``/healthz``
        endpoint serves exactly this dict (503 while degraded).  With
        the health plane off the report is a plain ok stub."""
        if self.health is None:
            return {"status": "ok", "health_plane": "off"}
        return self.health.report()

    def recovery_report(self) -> dict:
        """Closed-loop recovery actuation report (obs/recovery.py,
        ISSUE 17): executed/dry-run actions per detector, skip reasons,
        flap-suppressed keys and the guardrail knobs.  A plain off stub
        while ``auto_recover`` is off."""
        if self.recovery is None:
            return {"enabled": False, "recovery_plane": "off"}
        return self.recovery.report()

    def debug_dump(self, path: Optional[str] = None) -> str:
        """Write the flight-recorder ring plus any in-flight/completed
        sampled traces (and the health sample ring when the health
        plane is on) to a timestamped JSON file (the SIGUSR2 handler's
        body; callable directly).  Returns the path written."""
        d = {
            "time": time.time(),
            "raft_address": self.raft_address(),
            "recorder": (
                self.flight_recorder.to_json()
                if self.flight_recorder is not None else None
            ),
            "traces": (
                self.tracer.to_json() if self.tracer is not None else None
            ),
            "replattr": (
                self.replattr.summary()
                if self.replattr is not None else None
            ),
            "health": (
                self.health.to_json(limit=64)
                if self.health is not None else None
            ),
            "devprof": (
                self.devprof.to_json()
                if self.devprof is not None else None
            ),
        }
        if path is None:
            base = self.nhconfig.node_host_dir
            if not base or base == ":memory:":
                import tempfile

                base = tempfile.gettempdir()
            path = os.path.join(
                base,
                time.strftime("dbtpu-dump-%Y%m%d-%H%M%S.json"),
            )
        with open(path, "w") as f:
            json.dump(d, f, indent=1, default=str)
        plog.warning("debug dump written to %s", path)
        return path

    def _install_dump_signal(self) -> None:
        """Opt-in SIGUSR2 → :meth:`debug_dump` (live soak/chaos debugging
        without attaching a debugger).  The handler only SETS A FLAG —
        the dump runs on the tick worker: signal handlers execute on the
        main thread mid-frame, and dumping inline would re-acquire
        non-reentrant tracer/recorder locks the interrupted frame may
        already hold (self-deadlock).  Signal handlers only install from
        the main thread; elsewhere the opt-in degrades to a warning."""
        import signal as _signal

        def _handler(signum, frame):
            self._dump_requested = True

        try:
            self._dump_sig_old = _signal.signal(_signal.SIGUSR2, _handler)
        except (ValueError, OSError, AttributeError) as e:
            plog.warning("SIGUSR2 dump handler unavailable: %r", e)

    # ---- cluster registry ----

    def _get_nodes(self) -> Tuple[int, Dict[int, Node]]:
        with self._mu:
            # None entries are in-flight start_cluster reservations
            return self._csi, {
                k: v for k, v in self._clusters.items() if v is not None
            }

    def _get_csi(self) -> int:
        # GIL-atomic int read; lets engine workers skip the locked
        # dict copy in _get_nodes when the cluster set hasn't changed
        return self._csi

    def get_node(self, cluster_id: int) -> Node:
        # lock-free read (GIL-atomic dict get): this sits on the propose
        # hot path, once per client request
        n = self._clusters.get(cluster_id)
        if n is None:
            raise ClusterNotFoundError(f"cluster {cluster_id} not found")
        return n

    def has_cluster(self, cluster_id: int) -> bool:
        with self._mu:
            return cluster_id in self._clusters

    # ---- lifecycle (reference StartCluster nodehost.go:440-520,1509) ----

    def start_cluster(
        self,
        initial_members: Dict[int, str],
        join: bool,
        create_sm: Callable,
        config: Config,
    ) -> None:
        self._start_cluster(
            initial_members, join, create_sm, config, StateMachineType.REGULAR
        )

    def start_concurrent_cluster(
        self, initial_members, join, create_sm, config: Config
    ) -> None:
        self._start_cluster(
            initial_members, join, create_sm, config, StateMachineType.CONCURRENT
        )

    def start_on_disk_cluster(
        self, initial_members, join, create_sm, config: Config
    ) -> None:
        self._start_cluster(
            initial_members, join, create_sm, config, StateMachineType.ON_DISK
        )

    def _start_cluster(
        self,
        initial_members: Dict[int, str],
        join: bool,
        create_sm: Callable,
        config: Config,
        smtype: StateMachineType,
    ) -> None:
        config.validate()
        cluster_id, node_id = config.cluster_id, config.node_id
        if join and initial_members:
            raise ValueError("addresses given for a joining node")
        if not join and not initial_members:
            # the reference only rejects this for NEW nodes
            # (nodehost.go:1509 startCluster): a restarting node passes
            # empty members + join=False and resumes from its bootstrap
            # record
            if self.logdb.get_bootstrap_info(cluster_id, node_id) is None:
                raise ValueError("addresses not given for an initial member")
        with self._mu:
            if cluster_id in self._clusters:
                raise ClusterAlreadyExistError(str(cluster_id))
            # reserve the id under the lock so a concurrent start of the
            # same cluster fails instead of silently double-starting
            self._clusters[cluster_id] = None
        try:
            self._build_and_start_node(
                initial_members, join, create_sm, config, smtype
            )
        except BaseException:
            self._unreserve_cluster(cluster_id)
            raise

    def _build_and_start_node(
        self,
        initial_members: Dict[int, str],
        join: bool,
        create_sm: Callable,
        config: Config,
        smtype: StateMachineType,
    ) -> None:
        cluster_id, node_id = config.cluster_id, config.node_id
        # bootstrap record (reference bootstrapCluster nodehost.go:1479)
        bs = self.logdb.get_bootstrap_info(cluster_id, node_id)
        new_node = bs is None
        if bs is None:
            bs = Bootstrap(
                addresses=dict(initial_members), join=join, type=int(smtype)
            )
            self.logdb.save_bootstrap_info(cluster_id, node_id, bs)
        elif bs.type not in (int(StateMachineType.UNKNOWN), int(smtype)):
            raise ValueError("SM type changed across restarts")
        members = bs.addresses if not bs.join else initial_members
        # register peer addresses
        for nid, addr in (members or {}).items():
            self.node_registry.add(cluster_id, nid, addr)
        self.node_registry.add(cluster_id, node_id, self.raft_address())
        # build the node
        logreader = LogReader.load(cluster_id, node_id, self.logdb)
        snapshotter = Snapshotter(
            self.snapshot_dir(cluster_id, node_id), cluster_id, node_id,
            self.logdb, fs=self._fs,
        )
        # hostproc apply tier (ISSUE 12): a REGULAR state machine whose
        # factory registered as process-spawnable runs inside an apply
        # worker behind a ProcStateMachine proxy — update/lookup/snapshot
        # become shared-memory round trips off this process's GIL.
        # Never wraps: witness replicas (no real SM work), device_kv
        # groups (the devsm plane IS their apply offload), or factories
        # that did not opt in.  The wrap decision is taken BEFORE
        # construction so the user machine is built exactly once, on
        # whichever side actually hosts it.  Worker crash ⇒ the proxy
        # rebuilds in-process from its snapshot+redo buffer,
        # exactly-once.
        proc_spec = None
        if (
            self.hostproc is not None
            and self.hostproc.offload_default
            and smtype == StateMachineType.REGULAR
            and not config.is_witness
            and not config.device_kv
        ):
            from .hostproc import spawnable_spec

            proc_spec = spawnable_spec(create_sm)
        if proc_spec is not None:
            from .hostproc.sm import ProcStateMachine

            usersm = ProcStateMachine(
                self.hostproc, proc_spec, cluster_id, node_id, create_sm
            )
        else:
            usersm = create_sm(cluster_id, node_id)
        if smtype == StateMachineType.REGULAR:
            managed = from_regular_sm(usersm)
        elif smtype == StateMachineType.CONCURRENT:
            managed = from_concurrent_sm(usersm)
        else:
            managed = from_on_disk_sm(usersm)
        node = Node(
            nh=self,
            config=config,
            logdb=self.logdb,
            logreader=logreader,
            snapshotter=snapshotter,
            sm=None,  # set below (circular)
            tick_millisecond=self.nhconfig.rtt_millisecond,
        )
        sm = StateMachine(
            managed,
            snapshotter,
            node,
            cluster_id,
            node_id,
            ordered_config_change=config.ordered_config_change,
            is_witness=config.is_witness,
            snapshot_compression=config.snapshot_compression,
        )
        node.sm = sm
        addresses = [
            PeerAddress(node_id=nid, address=a) for nid, a in (members or {}).items()
        ]
        node.peer_raft_events = self.raft_events
        node.quorum_coordinator = self.quorum_coordinator
        # device state machine registration (devsm, ISSUE 11), gated
        # default-OFF: both the config flag AND the SM's device_kv marker
        # must be present, and only the tpu engine has a coordinator to
        # serve it — anything else leaves the SM a plain host machine
        node.devsm_sm = (
            usersm
            if (
                config.device_kv
                and getattr(usersm, "device_kv", False)
                and self.quorum_coordinator is not None
                and smtype == StateMachineType.REGULAR
            )
            else None
        )
        node.fastlane = self.fastlane
        if config.read_lease and self.nhconfig.enable_metrics:
            # leader-lease instruments (ISSUE 10): one shared LeaseObs
            # per host — the dragonboat_lease_* families land in the same
            # registry write_health_metrics exposes.  Lazy: hosts with no
            # lease-enabled group never register the families.
            if self._lease_obs is None:
                from .lease import LeaseObs

                self._lease_obs = LeaseObs(self.raft_events.registry)
            node.lease_obs = self._lease_obs
        if config.hier_commit and self.nhconfig.enable_metrics:
            # hierarchical-commit instruments (ISSUE 18): one shared
            # HierObs per host, the LeaseObs pattern — lazy so hosts
            # with no hier-enabled group never register the families
            if self._hier_obs is None:
                from .raft.hier import HierObs

                self._hier_obs = HierObs(self.raft_events.registry)
            node.hier_obs = self._hier_obs
        if config.read_lease:
            # wall-clock lease guard (ISSUE 17; what a lease group has
            # since ISSUE 41): bound lease validity by monotonic wall
            # time so a starved tick loop cannot overextend it past the
            # majority's wall-time election
            node.lease_wall_s = self.nhconfig.rtt_millisecond / 1000.0
        if self.hostplane is not None:
            node.ingress = self.hostplane.ingress
            node.pending_proposals.set_egress(self.hostplane.egress)
            node.pending_reads.set_egress(self.hostplane.egress)
        if self.tracer is not None:
            node.tracer = self.tracer
            node.pending_reads._tracer = self.tracer
            node.replattr = self.replattr
        node.replica_obs = self.replica_obs
        node.start(addresses, initial=not join and new_node, new_node=new_node)
        with self._mu:
            self._clusters[cluster_id] = node
            self._csi += 1
        # signal only AFTER the store + csi bump: the workers reload their
        # node maps on csi change, so the wakeup now always finds the node
        # (the apply signal drives the queued initial-recovery task)
        self.engine.set_apply_ready(cluster_id)
        self.engine.set_step_ready(cluster_id)

    def _unreserve_cluster(self, cluster_id: int) -> None:
        with self._mu:
            if self._clusters.get(cluster_id) is None:
                self._clusters.pop(cluster_id, None)

    def stop_cluster(self, cluster_id: int) -> None:
        with self._mu:
            node = self._clusters.get(cluster_id)
            if node is None:
                # absent, or an in-flight start reservation — don't pop it
                raise ClusterNotFoundError(str(cluster_id))
            del self._clusters[cluster_id]
            self._csi += 1
        if self.quorum_coordinator is not None:
            self.quorum_coordinator.unregister(cluster_id)
        node.stop()
        self.sys_events.publish(
            SystemEvent(
                type=SystemEventType.NODE_UNLOADED,
                cluster_id=cluster_id,
                node_id=node.node_id,
            )
        )

    def stop_node(self, cluster_id: int, node_id: int) -> None:
        self.stop_cluster(cluster_id)

    def stop(self) -> None:
        if self._stopped.is_set():
            return
        self._stopped.set()
        self.sys_events.publish(
            SystemEvent(type=SystemEventType.NODE_HOST_SHUTTING_DOWN)
        )
        if self.metrics_server is not None:
            # first: a scrape arriving mid-teardown must not race the
            # planes it reads
            self.metrics_server.stop()
            self.metrics_server = None
        if self.recovery is not None:
            # before the nodes: an in-flight remediation (config change,
            # transfer) must drain while its group still exists
            self.recovery.stop()
        with self._mu:
            nodes = list(self._clusters.values())
            self._clusters.clear()
            self._csi += 1
        for n in nodes:
            if n is not None:
                n.stop()
        if self.fastlane is not None:
            self.fastlane.stop()
        self.engine.stop()
        if self.hostplane is not None:
            # after engine.stop(): the committers (joined there) are the
            # flusher's riders — stopping the flusher first would strand
            # an in-flight flush
            self.hostplane.stop()
        if self.hostproc is not None:
            # after hostplane.stop(): every worker-tier caller (batcher
            # encode, WAL sink, SM proxies) is quiesced, so the workers'
            # drain-and-stop sees an empty backlog
            self.hostproc.stop()
        if self.devprof is not None:
            # before the coordinator: an open jax.profiler window must
            # close while the engine it observes still exists
            self.devprof.stop()
            self.devprof = None
        if self.quorum_coordinator is not None:
            self.quorum_coordinator.stop()
        self.transport.stop()
        self.logdb.close()
        if self.server_ctx is not None:
            self.server_ctx.stop()
        if self.tracer is not None:
            self.tracer.close()
        if self.replica_obs is not None:
            self.replica_obs.close()
        if self._dump_sig_old is not None:
            import signal as _signal

            try:
                _signal.signal(_signal.SIGUSR2, self._dump_sig_old)
            except (ValueError, OSError):
                pass
            self._dump_sig_old = None
        self.sys_events.stop()

    # ---- proposals / reads (reference SyncPropose :523, SyncRead :548) ----

    def get_noop_session(self, cluster_id: int) -> Session:
        return Session.noop_session(cluster_id)

    def propose(
        self, session: Session, cmd: bytes, timeout: float
    ) -> RequestState:
        node = self.get_node(session.cluster_id)
        return node.propose(session, cmd, timeout)

    def propose_batch(
        self, session: Session, cmds, timeout: float
    ) -> list:
        """Burst-propose: one completion future per command (see
        ``Node.propose_batch``)."""
        node = self.get_node(session.cluster_id)
        return node.propose_batch(session, cmds, timeout)

    def sync_propose(
        self, session: Session, cmd: bytes, timeout: float = 5.0
    ) -> Result:
        r = self._sync_retry(
            lambda t: self.propose(session, cmd, t), timeout
        )
        _raise_on_failure(r)
        if not session.is_noop_session():
            session.proposal_completed()
        return r.result

    def read_index(self, cluster_id: int, timeout: float) -> RequestState:
        return self.get_node(cluster_id).read(timeout)

    def sync_read(self, cluster_id: int, query, timeout: float = 5.0):
        r = self._sync_retry(
            lambda t: self.read_index(cluster_id, t), timeout,
            retry_timeout=True,
        )
        _raise_on_failure(r)
        return self.get_node(cluster_id).sm.lookup(query)

    def _sync_retry(
        self, submit, timeout: float, retry_timeout: bool = False
    ) -> RequestResult:
        """Retry dropped requests until the deadline (reference
        ``nodehost.go`` execute-on-temporary-error pattern in Sync* APIs).

        ``retry_timeout=True`` additionally splits the budget into short
        attempts and retries attempts that time out — safe only for
        idempotent requests (reads): a request forwarded to a dead leader
        is silently lost and would otherwise burn the whole budget.
        """
        deadline = time.monotonic() + timeout
        attempt_cap = (
            max(20 * self.nhconfig.rtt_millisecond / 1000.0, 0.25)
            if retry_timeout
            else timeout
        )
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return RequestResult()  # TIMEOUT
            attempt = min(remaining, attempt_cap)
            rs = submit(attempt)
            r = rs.wait(attempt)
            if r.dropped or (retry_timeout and r.timeout):
                time.sleep(self.nhconfig.rtt_millisecond / 1000.0)
                continue
            return r

    def request_compaction(self, cluster_id: int, node_id: int):
        """User-requested LogDB compaction (reference ``nodehost.go:980``
        ``RequestCompaction``).  Returns a ``threading.Event`` set when
        the compaction completes.  For a cluster already removed from
        this host (e.g. after ``remove_data``) the whole log range is
        compacted; for a live node, compaction runs up to the last
        auto-compacted watermark (RejectedError when there is none)."""
        with self._mu:
            node = self._clusters.get(cluster_id)
            starting = node is None and cluster_id in self._clusters
        if starting:
            # in-flight start_cluster reservation (the None placeholder):
            # NOT removed data — refuse rather than full-range compact a
            # cluster that is coming up
            raise ClusterNotFoundError(f"cluster {cluster_id} is starting")
        if node is None:
            # removed via remove_data: compact everything it left behind
            return self.logdb.compact_entries_to(
                cluster_id, node_id, (1 << 64) - 1
            )
        if node.node_id != node_id:
            raise ClusterNotFoundError(f"{cluster_id}:{node_id}")
        done = node.request_compaction()
        self.engine.set_step_ready(cluster_id)
        return done

    def has_node_info(self, cluster_id: int, node_id: int) -> bool:
        """True when this host holds bootstrap state for the replica
        (reference ``nodehost.go:1319`` ``HasNodeInfo``)."""
        return self.logdb.get_bootstrap_info(cluster_id, node_id) is not None

    def get_node_host_info(self, skip_log_info: bool = False) -> "NodeHostInfo":
        """Details of this host and every Raft cluster it manages
        (reference ``nodehost.go:1333`` ``GetNodeHostInfo``)."""
        infos = []
        with self._mu:
            # skip in-flight start_cluster reservations (None placeholders)
            nodes = [n for n in self._clusters.values() if n is not None]
        for n in nodes:
            try:
                m = n.sm.get_membership()
                pending = not m.addresses and not m.observers and not m.witnesses
                infos.append(ClusterInfo(
                    cluster_id=n.cluster_id,
                    node_id=n.node_id,
                    nodes=dict(m.addresses),
                    observers=dict(m.observers),
                    witnesses=dict(m.witnesses),
                    config_change_index=m.config_change_id,
                    state_machine_type=n.sm.sm_type,
                    is_leader=n.is_leader(),
                    is_observer=n.config.is_observer,
                    is_witness=n.config.is_witness,
                    pending=pending,
                ))
            except Exception:  # a node racing stop: report it as pending
                infos.append(ClusterInfo(
                    cluster_id=n.cluster_id, node_id=n.node_id, pending=True
                ))
        log_info = [] if skip_log_info else self.logdb.list_node_info()
        return NodeHostInfo(
            raft_address=self.raft_address(),
            cluster_info_list=infos,
            log_info=[(ni.cluster_id, ni.node_id) for ni in log_info],
        )

    def stale_read(self, cluster_id: int, query):
        return self.get_node(cluster_id).stale_read(query)

    # ---- sessions (reference SyncGetSession/SyncCloseSession) ----

    def sync_get_session(self, cluster_id: int, timeout: float = 5.0) -> Session:
        s = Session.new_session(cluster_id)
        s.prepare_for_register()
        node = self.get_node(cluster_id)
        rs = node.propose_session(s, timeout)
        r = rs.wait(timeout)
        _raise_on_failure(r)
        if r.result.value != s.client_id:
            raise RejectedError("session registration rejected")
        s.prepare_for_propose()
        return s

    def sync_close_session(self, s: Session, timeout: float = 5.0) -> None:
        s.prepare_for_unregister()
        node = self.get_node(s.cluster_id)
        rs = node.propose_session(s, timeout)
        r = rs.wait(timeout)
        _raise_on_failure(r)

    # ---- membership (reference RequestAddNode :1133 etc.) ----

    def request_add_node(
        self, cluster_id: int, node_id: int, address: str,
        config_change_index: int = 0, timeout: float = 5.0,
    ) -> RequestState:
        cc = ConfigChange(
            type=ConfigChangeType.ADD_NODE,
            node_id=node_id,
            address=address,
            config_change_id=config_change_index,
        )
        return self.get_node(cluster_id).request_config_change(cc, timeout)

    def request_delete_node(
        self, cluster_id: int, node_id: int,
        config_change_index: int = 0, timeout: float = 5.0,
    ) -> RequestState:
        cc = ConfigChange(
            type=ConfigChangeType.REMOVE_NODE,
            node_id=node_id,
            config_change_id=config_change_index,
        )
        return self.get_node(cluster_id).request_config_change(cc, timeout)

    def request_add_observer(
        self, cluster_id: int, node_id: int, address: str,
        config_change_index: int = 0, timeout: float = 5.0,
    ) -> RequestState:
        cc = ConfigChange(
            type=ConfigChangeType.ADD_OBSERVER,
            node_id=node_id,
            address=address,
            config_change_id=config_change_index,
        )
        return self.get_node(cluster_id).request_config_change(cc, timeout)

    def request_add_witness(
        self, cluster_id: int, node_id: int, address: str,
        config_change_index: int = 0, timeout: float = 5.0,
    ) -> RequestState:
        cc = ConfigChange(
            type=ConfigChangeType.ADD_WITNESS,
            node_id=node_id,
            address=address,
            config_change_id=config_change_index,
        )
        return self.get_node(cluster_id).request_config_change(cc, timeout)

    def sync_request_add_node(self, cluster_id, node_id, address,
                              config_change_index=0, timeout=5.0) -> None:
        r = self._sync_retry(
            lambda t: self.request_add_node(
                cluster_id, node_id, address, config_change_index, t
            ),
            timeout,
        )
        _raise_on_failure(r)

    def sync_request_delete_node(self, cluster_id, node_id,
                                 config_change_index=0, timeout=5.0) -> None:
        r = self._sync_retry(
            lambda t: self.request_delete_node(
                cluster_id, node_id, config_change_index, t
            ),
            timeout,
        )
        _raise_on_failure(r)

    def sync_request_add_observer(self, cluster_id, node_id, address,
                                  config_change_index=0, timeout=5.0) -> None:
        r = self._sync_retry(
            lambda t: self.request_add_observer(
                cluster_id, node_id, address, config_change_index, t
            ),
            timeout,
        )
        _raise_on_failure(r)

    def sync_request_add_witness(self, cluster_id, node_id, address,
                                 config_change_index=0, timeout=5.0) -> None:
        r = self._sync_retry(
            lambda t: self.request_add_witness(
                cluster_id, node_id, address, config_change_index, t
            ),
            timeout,
        )
        _raise_on_failure(r)

    def sync_get_cluster_membership(
        self, cluster_id: int, timeout: float = 5.0
    ) -> Membership:
        r = self._sync_retry(
            lambda t: self.read_index(cluster_id, t), timeout,
            retry_timeout=True,
        )
        _raise_on_failure(r)
        return self.get_node(cluster_id).get_membership()

    # ---- snapshots / leadership ----

    def request_snapshot(
        self, cluster_id: int, export_path: str = "",
        override_compaction_overhead: bool = False,
        compaction_overhead: int = 0, timeout: float = 5.0,
    ) -> RequestState:
        req = SSRequest(
            type=SSReqType.EXPORTED if export_path else SSReqType.USER_REQUESTED,
            path=export_path,
            override_compaction_overhead=override_compaction_overhead,
            compaction_overhead=compaction_overhead,
        )
        return self.get_node(cluster_id).request_snapshot(req, timeout)

    def sync_request_snapshot(self, cluster_id: int, timeout: float = 5.0) -> int:
        rs = self.request_snapshot(cluster_id, timeout=timeout)
        r = rs.wait(timeout)
        _raise_on_failure(r)
        return r.snapshot_index

    def request_leader_transfer(self, cluster_id: int, target: int) -> None:
        self.get_node(cluster_id).request_leader_transfer(target, 5.0)

    def get_leader_id(self, cluster_id: int) -> Tuple[int, bool]:
        return self.get_node(cluster_id).get_leader_id()

    def lease_status(self, cluster_id: int) -> Optional[dict]:
        """Leader-lease snapshot for one group (ISSUE 10): ``None`` when
        the group runs without ``Config.read_lease``; else held/remaining
        plus the local-vs-fallback read counters (``Node.lease_status``)."""
        return self.get_node(cluster_id).lease_status()

    def wal_status(self) -> Optional[dict]:
        """Group-commit WAL strategy snapshot (ISSUE 12 satellite, the
        ``lease_status`` pattern): ``None`` without the compartmentalized
        host plane; else the chosen journal strategy (mode / engaged /
        probe cost / pacing window), the journal's byte/fsync counters
        and whether durability currently runs through the hostproc WAL
        worker (``worker_sink``)."""
        if self.hostplane is None:
            return None
        return self.hostplane.wal.status()

    # ---- data management ----

    def remove_data(self, cluster_id: int, node_id: int) -> None:
        """Reference ``NodeHost.RemoveData``: only valid once the node is
        stopped."""
        with self._mu:
            if cluster_id in self._clusters:
                raise RuntimeError("cluster still running")
        self.logdb.remove_node_data(cluster_id, node_id)

    def get_node_user(self, cluster_id: int) -> Node:
        return self.get_node(cluster_id)

    # ---- message plumbing ----

    def send_message(self, m: Message) -> None:
        """Route an outbound raft message: local delivery when the target
        node lives on this host (reference ``nodehost.go:1792``)."""
        if m.to == 0:
            return
        target = self.node_registry.resolve(m.cluster_id, m.to)
        if target == self.raft_address():
            node = self._clusters.get(m.cluster_id)
            if node is not None and node.node_id == m.to:
                node.handle_message_batch(m)
            return
        # with the fast lane active, ALL raft messages for a remote ride
        # its single ordered native stream — mixing the Python transport's
        # sockets with the fast plane's reorders entries across
        # eject/re-enroll transitions and forces gap ejects
        if self.fastlane is not None and self.fastlane.send_message(m):
            return
        self.transport.send(m)

    def send_snapshot_message(self, m: Message) -> None:
        target = self.node_registry.resolve(m.cluster_id, m.to)
        if target == self.raft_address():
            node = self._clusters.get(m.cluster_id)
            if node is not None and node.node_id == m.to:
                node.handle_message_batch(m)
                return
        # on-disk SMs stream their live state through a per-transfer job
        # instead of chunking a snapshot file (reference nodehost.go:1796:
        # witness/in-memory -> file send; on-disk -> stream)
        sender = self._clusters.get(m.cluster_id)
        witness = m.snapshot is not None and m.snapshot.witness
        if sender is not None and sender.sm.on_disk and not witness:
            sender.push_stream_snapshot_request(m.to)
            return
        if not self.transport.send_snapshot(m):
            self._snapshot_status(m.cluster_id, m.to, True)

    def _message_router(self, batch: MessageBatch) -> None:
        """Reference ``messageHandler`` ``nodehost.go:2013``.

        Messages are queued first and step-readiness is signalled once per
        touched group — a batch regularly carries several messages for the
        same group and per-message wakeups are measurable overhead."""
        if not self._router_ready:
            # mid-construction: drop, the senders retry.  Visible, not
            # silent — a long gated window looks like a dead peer
            self._router_gated_drops += 1
            if self._router_gated_drops == 1:
                plog.warning(
                    "inbound batch dropped: NodeHost still constructing"
                )
            return
        touched = {}
        src = batch.source_address
        for m in batch.requests:
            ctx = m.trace
            if ctx is not None:
                # replication tracing (ISSUE 14): inbound stamp in THIS
                # host's clock.  First touch is the follower's
                # ``repl_recv``; the same context echoed back on the ack
                # lands here again on the leader as the ack-receive.
                if not ctx.t_recv:
                    ctx.t_recv = time.time()
                elif not ctx.t_ack_recv:
                    ctx.t_ack_recv = time.time()
            if m.type in _HB_BLOCK_TYPES:
                # batched heartbeat plane: host-addressed, every row of
                # it handled in one pass by the coordinator (no
                # coordinator, no plane: nothing on this host sent one)
                if self.quorum_coordinator is not None:
                    self.quorum_coordinator.on_heartbeat_block(m, src)
                continue
            if m.type == MessageType.SNAPSHOT_RECEIVED:
                # follower's ack for a sent snapshot: accelerates the
                # parked status release; never delivered to raft
                # (reference nodehost.go:2039-2044)
                self.snapshot_feedback.confirm(
                    m.cluster_id, m.from_, self._now_ms()
                )
                continue
            node = self._clusters.get(m.cluster_id)
            if node is None or node.node_id != m.to:
                continue
            if src:
                # learn the sender's address so replies route before
                # membership is applied locally (reference nodes.go)
                self.node_registry.add_remote(m.cluster_id, m.from_, src)
            # a non-fast message reaching Python for a fast-lane group means
            # the native core could not serve it: complete the eject handoff
            # FIRST so the scalar raft state is current when it handles the
            # message (fastlane.py eject protocol).  Fast-wire types are
            # NOT ejected for: they are frames that raced (re)enrollment
            # through the leftover pump — the enrolled step feeds them to
            # the native core in mq order (node._fast_lane_step), which was
            # the dominant round-3 eject storm (router:REPLICATE /
            # router:HEARTBEAT ~2-3k per rank, enrollment duty ~1/3)
            if node.fast_lane and m.type not in _FAST_WIRE_TYPES:
                if (
                    m.type is MessageType.REQUEST_VOTE_RESP
                    and m.term <= node.peer.raft.term
                ):
                    # straggler from the pre-enrollment election: an
                    # enrolled group is never a candidate, so scalar raft
                    # would no-op it — not worth an eject (term read is
                    # lock-free but safe: a racing campaign bumps the term,
                    # making a stale resp stale still)
                    if self.fastlane is not None:
                        self.fastlane.count_drop("router-stale-vote-resp")
                    continue
                if self.fastlane is not None:
                    self.fastlane.count_eject(f"router:{m.type.name}")
                # a REQUEST_VOTE reaching an enrolled follower means an
                # election is in progress (a netsplit peer campaigning).
                # Without the re-enroll backoff the group re-enrolls
                # within one step — before the scalar election clock ages
                # past the §6 vote-drop lease (frozen while enrolled, and
                # leader_id is still the stale pre-split leader) — so the
                # vote is dropped and every native liveness clock resets:
                # the candidate's own retries keep the group enrolled
                # forever (the partition_tcp no-leader stall)
                node.fast_eject(
                    reenroll_backoff=m.type is MessageType.REQUEST_VOTE
                )
            if node.enqueue_message(m):
                touched[m.cluster_id] = None
        engine = self.engine
        for cid in touched:
            engine.set_step_ready(cid)

    def _now_ms(self) -> int:
        return int(time.monotonic() * 1000)

    def _snapshot_status(self, cluster_id: int, node_id: int, failed: bool):
        """Transport finished a snapshot send: park the status with the
        feedback tracker instead of reporting to raft immediately
        (reference messageHandler.HandleSnapshotStatus nodehost.go:2063)."""
        self.snapshot_feedback.add_status(
            cluster_id, node_id, failed, self._now_ms()
        )

    def _push_snapshot_status(
        self, cluster_id: int, node_id: int, failed: bool
    ) -> bool:
        node = self._clusters.get(cluster_id)
        if node is None:
            return True  # group gone; nothing to deliver
        return node.handle_snapshot_status(node_id, failed)

    def _snapshot_received(self, cluster_id: int, node_id: int, from_: int) -> None:
        """A streamed/chunked snapshot finished arriving: ack the sender so
        its feedback tracker releases the status quickly (reference
        messageHandler.HandleSnapshot nodehost.go:2090)."""
        self.send_message(
            Message(
                type=MessageType.SNAPSHOT_RECEIVED,
                cluster_id=cluster_id,
                from_=node_id,
                to=from_,
            )
        )

    def _unreachable(self, cluster_id: int, node_id: int) -> None:
        node = self._clusters.get(cluster_id)
        if node is not None:
            node.handle_unreachable(node_id)

    # ---- ticks (reference tickWorkerMain nodehost.go:1725) ----

    def _tick_worker_main(self) -> None:
        interval = self.nhconfig.rtt_millisecond / 1000.0
        ticks = 0
        sweep = Soft.lazy_tick_sweep_ticks
        while not self._stopped.wait(interval):
            ticks += 1
            self.tick_count += 1
            now_tick = self.tick_count
            with self._mu:
                nodes = list(self._clusters.values())
            for n in nodes:
                if n is None:
                    continue
                if n.tick_lite():
                    # lazy delivery: the native core / device tick kernel
                    # owns this group's raft clock; wake it only when its
                    # pending-request GC could be overdue.  This is the
                    # O(groups)→O(active) tick-cost cut that lets one
                    # process hold tens of thousands of groups.  The
                    # reference's answer on the same axis, quiesce.go,
                    # rides it: a ``Config.quiesce`` group on the device
                    # engine is lite too, its idle clock a column of the
                    # tick kernel, and asleep it gets no message and no
                    # step-worker turn a tick.
                    if (
                        now_tick - n._seen_tick >= sweep
                        and n.has_pending_requests()
                    ):
                        self.engine.set_step_ready(n.cluster_id)
                else:
                    n.request_tick()
            if self.quorum_coordinator is not None:
                # one device tick round per RTT for ALL registered groups
                self.quorum_coordinator.request_tick()
            tracer = self.tracer
            if tracer is not None:
                # stage-level stall watchdog (ISSUE 9): a sampled request
                # stuck >stall_ms in one stage auto-dumps its partial
                # trace + the recorder ring.  Fast path (nothing sampled
                # in flight) is two dict truthiness checks per RTT.
                tracer.check_stalls()
            replattr = self.replattr
            if replattr is not None:
                # expire commit records that will never close (dropped
                # proposals, lost quorums).  Fast path (no open records)
                # is one dict truthiness check per RTT.
                replattr.sweep()
            health = self.health
            if health is not None:
                # cluster health plane (ISSUE 13): one low-rate sample
                # per health_sample_ms cadence, detectors included.
                # Fast path (cadence not elapsed) is one float compare
                # per RTT; sample failures are swallowed inside.
                health.maybe_sample()
            if self._dump_requested:
                # SIGUSR2 arrived: run the dump HERE, not in the signal
                # handler (non-reentrant locks; see _install_dump_signal)
                self._dump_requested = False
                try:
                    self.debug_dump()
                except Exception:
                    plog.exception("SIGUSR2 debug dump failed")
            self.snapshot_feedback.push_ready(self._now_ms())
            if ticks % max(1, int(1.0 / max(interval, 0.001))) == 0:
                self.transport.tick()


def _raise_on_failure(r: RequestResult) -> None:
    if r.completed:
        return
    if r.timeout:
        raise TimeoutError_("request timed out")
    if r.rejected:
        raise RejectedError("request rejected")
    if r.dropped:
        raise RejectedError("request dropped")
    if r.terminated:
        raise ClusterNotFoundError("cluster terminated")
    raise RejectedError(f"request failed: {r.code}")

