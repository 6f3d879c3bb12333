"""Engine/coordinator instruments: device-plane metric families.

``EngineObs`` and ``CoordObs`` hold a :class:`FlightRecorder` plus the
:class:`dragonboat_tpu.events.MetricsRegistry` the metrics publish into
(default: the process registry ``events.DEFAULT_REGISTRY``, the same one
``write_health_metrics`` exposes).  Every family is zero-registered at
construction, so the exposition shows the device plane the moment obs is
enabled — a scrape distinguishes "obs off" (families absent) from "obs
on, idle" (families at zero).

Families (device plane, published by ``EngineObs``):

- ``dragonboat_device_dispatch_total`` — device dispatches launched
- ``dragonboat_device_rounds_total`` — scanned rounds across dispatches
- ``dragonboat_device_dispatch_latency_ms`` — host stage+launch wall
  time histogram
- ``dragonboat_device_egress_latency_ms`` — blocking egress wall time
  histogram
- ``dragonboat_device_acks_staged_total`` / ``…votes_staged_total`` —
  events ingested
- ``dragonboat_device_recycles_total`` — in-program membership recycles
- ``dragonboat_device_reads_staged_total`` / ``…read_echoes_total`` /
  ``…reads_released_total`` — read-plane traffic
- ``dragonboat_device_upload_bytes_total`` — host→device event tensors
- ``dragonboat_device_egress_rows_total`` — rows whose commit advanced
- ``dragonboat_device_multidev_wait_ms_total`` — multi-device dispatch
  lock wait (zero on single-device / mesh-sharded engines)
- ``dragonboat_device_stalls_total`` — watchdog-flagged spans
- ``dragonboat_device_warmup_seconds`` / ``…warmup_programs_total`` —
  AOT warm-compile wall time and programs warmed (ISSUE 7)
- gauges: ``dragonboat_device_staged_rounds`` (egress/dispatch queue
  depth), ``dragonboat_device_read_slots_in_use``
- ``dragonboat_devsm_ops_staged_total`` / ``…applied_total`` /
  ``…reads_staged_total`` / ``…reads_served_total`` + gauge
  ``…slot_occupancy`` — device state machine traffic (ISSUE 11), spanned
  by the ``apply_kernel`` flight-recorder kind

Coordinator plane (``CoordObs``): ``dragonboat_coord_rounds_total``,
``…round_latency_ms`` (histogram), ``…ops_drained_total``,
``…tick_deficit_total``, ``…commits_offloaded_total``,
``…fused_dispatch_total`` /
``…fused_rounds_total`` (adaptive-K live batching); gauges
``…staged_depth``; ``…read_fallbacks_total{cause}`` — heartbeat read
echoes tallied scalar-side, by cause (``slot_overflow`` /
``after_confirm`` / ``purged``) — and ``…read_acks_total``, those the
device tallied.  Node offload application counts under
``dragonboat_node_offload_applied_total{kind=…}`` (node.py).

Replica plane (``ReplicaObs``, ISSUE 37; what a group's own settings turn
on: ``snapshot_entries``, ``compaction_overhead``, ``check_quorum``):
``dragonboat_snapshot_saves_total{kind}``, ``…saves_refused_total``,
``…save_latency_ms`` (histogram), ``…compactions_total``,
``…installs_total{direction}``, ``…pool_busy_seconds_total``, gauge
``…pool_queue_depth``; ``dragonboat_checkq_windows_total`` /
``…stepdowns_total``; one ``snapshot_save`` span a save.

Spans (ISSUE 26): every dispatch / round span is an interval
(``t0``/``t1`` on ``perf_counter``) with ``host`` and ``parent``, and
carries its phases as ``*_ms`` fields — ``EngineObs.phase`` /
``CoordObs.phase`` time one occurrence of a phase, annotate it for the
profiler (``recorder.ANNOTATIONS``) and add it to the span being built.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from ..events import DEFAULT_BUCKETS, DEFAULT_REGISTRY, MetricsRegistry
from .recorder import OFF, FlightRecorder, Phase, annotate

#: the phases of one engine step, by the span field they sum into
DISPATCH_PHASES = ("row_sync_ms", "stage_ms", "transfer_ms", "launch_ms",
                   "retire_ms")
EGRESS_PHASES = ("egress_wait_ms", "decode_ms")
#: why a heartbeat read echo was tallied scalar-side (tpuquorum.py)
READ_FALLBACK_CAUSES = ("slot_overflow", "after_confirm", "purged")
#: why a heartbeat or its response took the per-group message instead of
#: the block (tpuquorum.py, the batched heartbeat plane): the group was
#: mid-step (``busy``), not a plain device-ticked leader / follower
#: (``state``), at another term, following no leader yet, carrying a
#: ReadIndex context as its hint, mid snapshot, of a membership with
#: handlers of its own (observer, witness; a lease group too until ISSUE
#: 41), or a response from a remote that lags and must be probed
HB_SINGLE_CAUSES = (
    "busy", "state", "term", "unknown_leader", "read_ctx", "snapshot",
    "membership", "lagging",
)

#: log-spaced dispatch/egress/round latency buckets (ms): the live
#: coordinator's single-round dispatches sit near the bottom decade, a
#: first-use XLA compile or a wedged dispatch at the top.  ONE geometry,
#: shared with the registry default — histogram bucket sets are
#: first-declare-wins, so a second copy that drifted would be silently
#: ignored for already-declared families.
LATENCY_BUCKETS_MS = DEFAULT_BUCKETS

_DEV = "dragonboat_device_"
_COORD = "dragonboat_coord_"
_HOST = "dragonboat_host_"
_HPROC = "dragonboat_hostproc_"
_DEVSM = "dragonboat_devsm_"
_HEALTH = "dragonboat_health_"
_REPL = "dragonboat_repl_"
_DEVPROF = "dragonboat_devprof_"
_MESH = "dragonboat_mesh_"
_RECOV = "dragonboat_recovery_"
_TELEM = "dragonboat_telem_"
_SNAP = "dragonboat_snapshot_"
_CHECKQ = "dragonboat_checkq_"

#: recovery-duration buckets (seconds): a worker respawn lands near the
#: bottom, a failover around election timeouts, a wedged rebind loop or
#: an unhealed netsplit at the top
RECOVERY_BUCKETS_S = (
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)

#: ``# HELP`` text per family (ISSUE 9 satellite: the exposition was
#: ``# TYPE``-only).  Families not listed fall back to the registry's
#: deterministic placeholder.
_HELP = {
    _SNAP + "saves_total": "snapshot tasks a pool worker finished with "
    "an image committed (or streamed), by kind",
    _SNAP + "saves_refused_total": "periodic snapshots that fell due "
    "while the one before was still queued or running (skipped)",
    _SNAP + "save_latency_ms": "pool-worker wall time of one snapshot "
    "save: state machine save, commit, log and snapshot compaction",
    _SNAP + "compactions_total": "log compactions behind a snapshot",
    _SNAP + "installs_total": "InstallSnapshot messages sent to a "
    "follower behind the compacted log / received whole, by direction",
    _SNAP + "pool_busy_seconds_total": "seconds the snapshot pool's "
    "workers spent on tasks",
    _SNAP + "pool_queue_depth": "tasks waiting for a snapshot-pool "
    "worker when the last task finished",
    _CHECKQ + "windows_total": "check-quorum windows a leader closed",
    _CHECKQ + "stepdowns_total": "leaders that stepped down because a "
    "closed check-quorum window had not heard from a quorum",
    _HPROC + "workers_alive": "host-plane worker processes currently "
    "alive (spawned minus crashed/stopped)",
    _HPROC + "worker_restarts_total": "worker processes respawned after "
    "a crash/exit (bounded per worker; exhausted lanes stay in-process)",
    _HPROC + "ring_depth": "bytes staged across every shared-memory "
    "ring (request + response), sampled by the monitor",
    _HPROC + "ring_full_total": "ring pushes that stayed full past the "
    "busy window and raised SystemBusy, by role",
    _HPROC + "fallbacks_total": "stage executions that fell back "
    "in-process (worker gone/busy), by role",
    _HPROC + "calls_total": "completed worker round trips, by role",
    _HPROC + "worker_wall_ms": "worker-side execution wall time per "
    "round trip (the stage work done off the serving process), by role",
    _DEV + "dispatch_total": "device programs launched",
    _DEV + "rounds_total": "scanned rounds across device dispatches",
    _DEV + "acks_staged_total": "replicate acks ingested by dispatches",
    _DEV + "votes_staged_total": "votes ingested by dispatches",
    _DEV + "recycles_total": "in-program membership recycles",
    _DEV + "reads_staged_total": "ReadIndex batches staged on device",
    _DEV + "read_echoes_total": "heartbeat read-echoes staged on device",
    _DEV + "reads_released_total": "client reads released by confirmed slots",
    _DEV + "upload_bytes_total": "host-to-device event tensor bytes",
    _DEV + "egress_rows_total": "rows whose commit watermark advanced",
    _DEV + "multidev_wait_ms_total": "milliseconds waiting on the "
    "engine's multi-device dispatch lock (zero on single-device and "
    "mesh-sharded engines)",
    _DEV + "stalls_total": "stall-watchdog-flagged dispatch spans",
    _DEV + "warmup_seconds": "wall seconds spent AOT warm-compiling",
    _DEV + "warmup_programs_total": "device programs AOT warm-compiled",
    _DEV + "staged_rounds": "egress/dispatch round queue depth",
    _DEV + "read_slots_in_use": "pending-read engine slots occupied",
    _DEV + "dispatch_latency_ms": "host stage+launch wall time per dispatch",
    _DEV + "egress_latency_ms": "blocking device-to-host egress wall time",
    _COORD + "rounds_total": "coordinator rounds dispatched",
    _COORD + "round_latency_ms": "whole-round wall time",
    _COORD + "ops_drained_total": "staged ops drained into the engine",
    _COORD + "tick_deficit_total": "host ticks replayed by rounds",
    _COORD + "commits_offloaded_total": "group commits offloaded to nodes",
    _COORD + "fused_dispatch_total": "rounds served by one fused dispatch",
    _COORD + "fused_rounds_total": "rounds carried by fused dispatches",
    _COORD + "staged_depth": "ops staged for the next round",
    _COORD + "read_fallbacks_total": "heartbeat read echoes tallied "
    "scalar-side, by cause: slot_overflow (the context never got a "
    "device slot), after_confirm (its context was already confirmed or "
    "prefix-released), purged (a transition dropped the group's FIFO)",
    _COORD + "read_acks_total": "heartbeat read echoes the device tallied",
    _COORD + "ticks_replayed_total": "host ticks a round ran late (the "
    "deficit beyond its own tick)",
    _COORD + "ticks_dropped_total": "host ticks a round could not replay "
    "(beyond the warmed K): this host's device clocks ran that much slow",
    _COORD + "elections_held_total": "election-due flags not fanned out "
    "because their round dropped ticks (the host stalled, not the leader)",
    _COORD + "tick_flags_total": "device tick flags fanned out, by kind",
    _COORD + "hb_block_rows_total": "heartbeats and responses handled by "
    "the block",
    _COORD + "hb_lite_rows_total": "of the block's rows, those of a busy "
    "group (mid-step, or its update not committed yet) served without its "
    "lock",
    _COORD + "hb_single_total": "heartbeats and responses that took the "
    "per-group message, by cause",
    _COORD + "rows": "groups registered on the engine at the last round",
    _COORD + "rows_quiesced": "replicas of quiesce groups asleep on this "
    "host at the last round",
    _COORD + "quiesce_enters_total": "replicas whose own idle clock "
    "crossed the quiesce threshold and put them to sleep",
    _COORD + "quiesce_wakes_total": "sleeping replicas that a message or "
    "a request woke",
    "dragonboat_node_scalar_ticks_total": "LOCAL_TICK messages delivered "
    "to step workers (replicas whose raft clock the host ticks)",
    _COORD + "reads_total": "ReadIndex contexts a leader's coordinator "
    "staged, by origin: the host's own clients' (local) or forwarded by a "
    "follower (remote)",
    _HOST + "ingress_submitted_total": "commands accepted into ingress rings",
    _HOST + "ingress_drains_total": "ingress batcher drain cycles",
    _HOST + "ingress_drained_total": "commands drained by the batcher",
    _HOST + "ingress_ring_depth": "commands still ringed after a drain",
    _HOST + "wal_flushes_total": "group-commit WAL flush cycles",
    _HOST + "wal_riders_total": "committer submissions merged into cycles",
    _HOST + "wal_updates_total": "raft updates persisted by the WAL tier",
    _HOST + "wal_amortization": "committer submissions per fsync cycle",
    _HOST + "wal_flush_latency_ms": "merged save+fsync wall time",
    _HOST + "apply_batches_total": "decoupled apply executor wakeups",
    _HOST + "apply_groups_total": "groups covered by apply batches",
    _HOST + "egress_notified_total": "client completions delivered off-worker",
    # device state machine (devsm, ISSUE 11)
    _DEVSM + "ops_staged_total": "KV entry ops staged into device buffers",
    _DEVSM + "applied_total": "KV ops applied by the in-program fold",
    _DEVSM + "reads_staged_total": "KV reads staged for device capture",
    _DEVSM + "reads_served_total": "KV reads served from device state",
    _DEVSM + "slot_occupancy": "entry-buffer slots holding unapplied ops",
    # cluster health plane (obs/health.py, ISSUE 13)
    _HEALTH + "samples_total": "health samples taken by the tick-worker "
    "cadence",
    _HEALTH + "sample_ms": "wall milliseconds one health sample cost "
    "(the sampler-overhead evidence)",
    _HEALTH + "groups": "raft groups covered by the last health sample",
    _HEALTH + "events_total": "health detector OPEN events, by detector",
    _HEALTH + "open": "health events currently open, by detector",
    _HEALTH + "recovery_seconds": "open-to-close durations per detector "
    "(leader_flap = failover, worker_flap = worker respawn, "
    "devsm_rebind = device rebind — the recovery-time attribution)",
    # replication attribution (obs/replattr.py, ISSUE 14)
    _REPL + "ack_rtt_seconds": "sampled replication send-to-ack round "
    "trip per peer (leader clock), labeled by latency class",
    _REPL + "stage_seconds": "quorum-closing path's stage decomposition "
    "(wire_out / follower_append / follower_fsync / ack_send / "
    "wire_back), clock-offset corrected so stages sum to the RTT",
    _REPL + "quorum_close_seconds": "replicate fan-out to quorum close "
    "per sampled commit (the kth voter's ack, try_commit's own "
    "kth_largest rule)",
    _REPL + "quorum_closer_total": "sampled commits whose quorum this "
    "peer's ack closed, by peer and latency class",
    _REPL + "laggard_total": "sampled commits this peer had NOT acked "
    "when the quorum closed, by peer and latency class",
    _REPL + "commits_attributed_total": "sampled commits closed with a "
    "full attribution record",
    _REPL + "records_dropped_total": "attribution records dropped "
    "before closing (term change, transition reset, overflow, expiry)",
    _REPL + "clock_offset_ms": "latest NTP-style ack-pair clock-offset "
    "estimate per peer (follower minus leader milliseconds)",
    # device capacity & profiling plane (obs/devprof.py, ISSUE 15)
    _DEVPROF + "hbm_bytes": "device-resident bytes per state artifact "
    "(the HBM ledger), by plane and artifact",
    _DEVPROF + "hbm_plane_bytes": "device-resident bytes per plane "
    "(quorum / read / devsm / dispatch)",
    _DEVPROF + "bytes_per_group": "resident state bytes one group row "
    "costs (the capacity model's extrapolation base)",
    _DEVPROF + "capacity_groups": "predicted max groups per device from "
    "the capacity model (0 = no memory budget known for this backend)",
    _DEVPROF + "model_error_pct": "capacity-model prediction vs "
    "actually-allocated resident bytes, percent",
    _DEVPROF + "device_ms": "sampled post-launch block_until_ready "
    "delta per dispatch — the device-execution estimate the host "
    "dispatch wall does not separate",
    _DEVPROF + "duty_cycle": "estimated device busy fraction over the "
    "last sampling window (sampled device time x stride / wall, "
    "clamped to 1)",
    _DEVPROF + "dispatches_total": "dispatches seen by the profiling "
    "plane",
    _DEVPROF + "sampled_total": "dispatches whose device time was "
    "sampled (1-in-N block_until_ready)",
    _DEVPROF + "padded_rounds_total": "rounds shipped inside fused "
    "K-batched programs (padded program K)",
    _DEVPROF + "wasted_rounds_total": "provable no-op padding rounds "
    "(padded K minus live/ticked rounds) — measurable wasted device work",
    _DEVPROF + "padding_waste_ratio": "wasted over padded rounds across "
    "the plane's lifetime",
    _DEVPROF + "programs": "warm-set programs analyzed by the registry",
    _DEVPROF + "program_compile_ms": "AOT lower+compile wall per "
    "analyzed program (cache-hot compiles deserialize)",
    _DEVPROF + "program_flops": "XLA cost-analysis flops per warmed "
    "program, by variant",
    _DEVPROF + "program_bytes": "XLA cost-analysis bytes accessed per "
    "warmed program, by variant",
    _DEVPROF + "program_temp_bytes": "XLA peak temp allocation per "
    "warmed program, by variant",
    _DEVPROF + "captures_total": "on-demand jax.profiler capture "
    "windows started",
    _DEVPROF + "capture_active": "1 while a capture window is recording",
    # mesh dispatch plane (ops/mesh.py, ISSUE 16)
    _MESH + "shards": "per-shard engines behind the mesh dispatch plane",
    _MESH + "groups": "raft groups currently placed on the shard, by "
    "shard (the live group-to-shard assignment table)",
    _MESH + "migrations_total": "groups migrated between shards by the "
    "cost-driven placement pass (stage-out/stage-in, watermarks "
    "preserved)",
    _MESH + "migration_ms": "stage-out to stage-in wall time per group "
    "migration",
    _MESH + "dispatch_concurrency": "shard dispatch streams observed "
    "simultaneously in flight per fan-out (the no-global-mutex "
    "evidence: >1 means two shards dispatched concurrently)",
    # closed-loop recovery plane (obs/recovery.py, ISSUE 17)
    _RECOV + "actions_total": "remediations the RecoveryController "
    "executed, by detector and action (evict_dead / promote_standby / "
    "transfer_leader / devsm_release / fastlane_redrive)",
    _RECOV + "dryrun_total": "remediations the controller WOULD have "
    "executed but only logged (dry-run mode), by detector and action",
    _RECOV + "skipped_total": "open events the controller declined to "
    "act on, by reason (not_leader / rate_limited / cooldown / "
    "suppressed / observe_only / no_target)",
    _RECOV + "suppressed_keys": "detector keys currently flap-damped "
    "(an action re-opened its detector max_reopens times), by detector",
    _RECOV + "failures_total": "remediations that raised or timed out, "
    "by detector and action",
    _RECOV + "action_seconds": "wall seconds one executed remediation "
    "took (decide-to-commit, e.g. config-change round trip), by action",
    # device telemetry fold (ops/kernels.py telem_fold, ISSUE 20)
    _TELEM + "folds_total": "device telemetry aggregates published to "
    "the health sampler (one fixed-size fold per harvested dispatch)",
    _TELEM + "groups": "live device-backed groups per raft state in the "
    "last fold, by state (follower / candidate / leader / observer / "
    "witness)",
    _TELEM + "stalled_groups": "groups whose commit watermark stayed "
    "flat since the previous fold despite pending appended entries",
    _TELEM + "commit_lag": "groups per log2 commit-lag bucket "
    "(last_index minus committed) in the last fold, by bucket lower "
    "bound",
    _TELEM + "worst_lag": "largest commit lag across live groups in the "
    "last fold (the top-K drill-down's first row)",
    _TELEM + "read_slots": "engine read-plane slots occupied in the "
    "last fold",
    _TELEM + "kv_ents": "devsm entry-buffer slots holding unapplied ops "
    "in the last fold",
    _HEALTH + "busy_rows_total": "per-group sample rows skipped because "
    "the raft_mu walk hit its budget mid-pass (nonzero means the "
    "sampler is degrading at this group count — the silent-O(G) "
    "blowup detector)",
}


def _describe(registry: MetricsRegistry, names) -> None:
    for name in names:
        text = _HELP.get(name)
        if text:
            registry.describe(name, text)


class _StepScope:
    """One engine call as a ``dbtpu:step`` annotation (not re-entered);
    the span the call opened gets the call's wall time as ``step_ms``."""

    __slots__ = ("obs", "ann", "t")

    def __init__(self, obs):
        self.obs = obs
        self.ann = annotate("step")

    def __enter__(self):
        obs = self.obs
        obs._in_step = True
        obs._scope_span = None
        self.ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        obs = self.obs
        span, obs._scope_span = obs._scope_span, None
        if span is not None:
            span["step_ms"] = round(
                (time.perf_counter() - self.t) * 1e3, 4
            )
        self.ann.__exit__(*exc)
        obs._in_step = False
        return False


class EngineObs:
    """Device-plane instruments for one ``BatchedQuorumEngine``.

    The engine keeps ``self._obs = None`` until ``enable_obs``; every
    hot-path call site is gated on that ``is not None`` check, so the
    obs-off host path stays bit-identical (module docstring contract).
    """

    __slots__ = ("recorder", "registry", "shard", "host", "parent", "t0",
                 "ph", "_in_step", "_scope_span", "_last_span")

    _COUNTERS = (
        _DEV + "dispatch_total",
        _DEV + "rounds_total",
        _DEV + "acks_staged_total",
        _DEV + "votes_staged_total",
        _DEV + "recycles_total",
        _DEV + "reads_staged_total",
        _DEV + "read_echoes_total",
        _DEV + "reads_released_total",
        _DEV + "upload_bytes_total",
        _DEV + "egress_rows_total",
        _DEV + "multidev_wait_ms_total",
        _DEV + "stalls_total",
        # AOT warm-compile (ISSUE 7): wall seconds spent pre-compiling
        # device programs and how many were warmed — the "warm-enable
        # cost" column of the perf ledger reads these
        _DEV + "warmup_seconds",
        _DEV + "warmup_programs_total",
        # device state machine (devsm, ISSUE 11): staged vs applied KV
        # entry ops and the reads the plane served — applied/staged
        # converging is the "apply rides the commit dispatch" invariant,
        # reads_served is the zero-host-apply read traffic
        _DEVSM + "ops_staged_total",
        _DEVSM + "applied_total",
        _DEVSM + "reads_staged_total",
        _DEVSM + "reads_served_total",
    )

    def __init__(
        self,
        recorder: FlightRecorder,
        registry: Optional[MetricsRegistry] = None,
        shard: Optional[int] = None,
        host: Optional[str] = None,
    ):
        self.recorder = recorder
        self.registry = registry or DEFAULT_REGISTRY
        #: shard index when the engine is one shard of a mesh dispatch
        #: plane — stamped into dispatch spans so the ring shows which
        #: stream launched what (the span-overlap evidence keys on it)
        self.shard = shard
        #: the owning NodeHost (raft address) and the seq of the
        #: coordinator round driving the engine (None: a bare engine) —
        #: stamped into every span as ``host`` / ``parent``
        self.host = host
        self.parent: Optional[int] = None
        #: perf_counter at the current step's start and the phase
        #: milliseconds accumulated since (``begin_step`` resets both)
        self.t0 = 0.0
        self.ph: dict = {}
        self._in_step = False  # inside a dbtpu:step scope
        self._scope_span = None  # the span that scope's call opened
        self._last_span = None  # the newest dispatch span (``retire``)
        r = self.registry
        _describe(r, self._COUNTERS + (
            _DEV + "staged_rounds", _DEV + "read_slots_in_use",
            _DEV + "dispatch_latency_ms", _DEV + "egress_latency_ms",
            _DEVSM + "slot_occupancy",
        ))
        for name in self._COUNTERS:
            r.counter_add(name, 0)
        r.gauge_set(_DEV + "staged_rounds", 0)
        r.gauge_set(_DEV + "read_slots_in_use", 0)
        r.gauge_set(_DEVSM + "slot_occupancy", 0)
        r.histogram_declare(
            _DEV + "dispatch_latency_ms", buckets=LATENCY_BUCKETS_MS
        )
        r.histogram_declare(
            _DEV + "egress_latency_ms", buckets=LATENCY_BUCKETS_MS
        )

    def begin_step(self) -> None:
        """A step starts here (after the previous block's harvest): the
        span ``dispatch`` opens carries this ``t0`` and the dispatch
        phases accumulated from now on."""
        ph = self.ph
        for k in DISPATCH_PHASES:
            ph[k] = 0.0
        self.t0 = time.perf_counter()

    def step_scope(self):
        """``with obs.step_scope():`` around one ``step`` /
        ``step_rounds`` call: the ``dbtpu:step`` annotation that holds
        the call's phases in a capture, so that what lies between them
        (the round thread waiting for the interpreter, the bookkeeping
        here) has a name there too, and its wall time lands on the span the call opened
        as ``step_ms``.  ``step`` rerouting into ``step_rounds`` stays
        one scope."""
        return OFF if self._in_step else _StepScope(self)

    def phase(self, name: str) -> Phase:
        """``with obs.phase("stage"):`` — one occurrence of a phase of
        the current step (annotation + ``<name>_ms`` accumulation)."""
        return Phase(self.ph, name)

    def _take(self, keys) -> dict:
        """The accumulated phase fields ``keys``, rounded, zeroed."""
        ph = self.ph
        out = {k: round(ph.get(k, 0.0), 4) for k in keys}
        for k in keys:
            ph[k] = 0.0
        return out

    def warmup(self, *, variant: str, seconds: float) -> dict:
        """One AOT-warmed device program (engine ``_warmup_main``):
        accumulate ``dragonboat_device_warmup_seconds`` and record a
        ``warmup`` span.  The compile wall time deliberately lands in a
        field the stall watchdog does NOT inspect (``compile_ms``) — a
        multi-second warm compile is the expected out-of-band cost, not
        a stall, and must not trigger an auto-dump."""
        r = self.registry
        r.counter_add(_DEV + "warmup_seconds", seconds)
        r.counter_add(_DEV + "warmup_programs_total")
        now = time.perf_counter()
        return self.recorder.record(
            "warmup",
            t0=now - seconds,
            host=self.host,
            variant=variant,
            compile_ms=round(seconds * 1e3, 4),
        )

    def apply_kernel(
        self, *, ops: int, reads: int, rounds: int, slot_occupancy: int
    ) -> dict:
        """One dispatch's devsm work launched (the ``apply_kernel`` span
        kind, ISSUE 11): staged entry ops and KV reads riding the fused
        program, plus the host view of entry-buffer occupancy.  The
        applied/served counts land at harvest via :meth:`devsm_egress` —
        the fold runs inside the same program as the commit advancement,
        so the span brackets exactly the apply stage the host no longer
        runs."""
        r = self.registry
        if ops:
            r.counter_add(_DEVSM + "ops_staged_total", ops)
        if reads:
            r.counter_add(_DEVSM + "reads_staged_total", reads)
        r.gauge_set(_DEVSM + "slot_occupancy", slot_occupancy)
        return self.recorder.record(
            "apply_kernel",
            host=self.host,
            parent=self.parent,
            ops=ops,
            reads=reads,
            rounds=rounds,
            slot_occupancy=slot_occupancy,
        )

    def devsm_egress(self, span: dict, *, applied: int, reads_served: int) -> None:
        """Close an ``apply_kernel`` span at harvest: what the fold
        applied and how many KV reads came back captured."""
        r = self.registry
        if applied:
            r.counter_add(_DEVSM + "applied_total", applied)
        if reads_served:
            r.counter_add(_DEVSM + "reads_served_total", reads_served)
        self.recorder.update(
            span, applied=applied, reads_served=reads_served
        )

    def dispatch(
        self,
        kind: str,
        *,
        rounds: int,
        acks: int,
        votes: int,
        recycles: int,
        reads: int,
        echoes: int,
        upload_bytes: int,
        dispatch_ms: float,
        gate: str,
        k_rounds: Optional[int] = None,
        mu_wait_ms: float = 0.0,
        pending_rounds: int = 0,
        read_slots_in_use: Optional[int] = None,
        n_dispatches: int = 1,
        arrays_made: int = 0,
        arrays_retired: int = 0,
        ack_blocks_stale: int = 0,
        read_blocks_stale: int = 0,
        reads_scalar: int = 0,
        echoes_scalar: int = 0,
    ) -> dict:
        """One logical step's device work launched: publish counters +
        latency, and open its span (egress fields land via
        :meth:`egress`).  ``n_dispatches`` counts the actual device
        programs — an oversized sparse backlog chunks into several per
        step — so ``dispatch_total`` tracks programs, not steps.
        ``k_rounds`` is the LIVE round count of the block (real staged
        rounds, or ticked rounds when a deficit replay ticks into the
        padding) vs ``rounds``, the padded program K.  ``arrays_made``
        (device arrays the step made by put: none, the ingress block
        rides the launch) and ``arrays_retired`` (state blocks its launch
        replaced; :meth:`egress` adds the egress block) count what costs
        the round thread a hand-off of the interpreter each;
        ``ack_blocks_stale`` the staged ack blocks that took the per-row
        epoch comparison (staged before a row transition),
        ``read_blocks_stale`` the read-stage and echo blocks that did;
        ``reads_scalar`` / ``echoes_scalar`` the tuple-staged read stages
        (cancels too) and echoes that were filtered as tuples, on scalars
        (of ``reads`` / ``echoes``; the rest rode blocks)."""
        r = self.registry
        r.counter_add(_DEV + "dispatch_total", n_dispatches)
        r.counter_add(_DEV + "rounds_total", rounds)
        if acks:
            r.counter_add(_DEV + "acks_staged_total", acks)
        if votes:
            r.counter_add(_DEV + "votes_staged_total", votes)
        if recycles:
            r.counter_add(_DEV + "recycles_total", recycles)
        if reads:
            r.counter_add(_DEV + "reads_staged_total", reads)
        if echoes:
            r.counter_add(_DEV + "read_echoes_total", echoes)
        if upload_bytes:
            r.counter_add(_DEV + "upload_bytes_total", upload_bytes)
        if mu_wait_ms:
            r.counter_add(_DEV + "multidev_wait_ms_total", mu_wait_ms)
        r.histogram_observe(
            _DEV + "dispatch_latency_ms", dispatch_ms,
            buckets=LATENCY_BUCKETS_MS,
        )
        r.gauge_set(_DEV + "staged_rounds", pending_rounds)
        if read_slots_in_use is not None:
            r.gauge_set(_DEV + "read_slots_in_use", read_slots_in_use)
        stalls = self.recorder.stalls
        extra = {"dispatches": n_dispatches} if n_dispatches > 1 else {}
        if k_rounds is not None:
            extra["k_rounds"] = k_rounds
        if self.shard is not None:
            extra["shard"] = self.shard
        span = self.recorder.record(
            kind,
            t0=self.t0,
            host=self.host,
            parent=self.parent,
            gate=gate,
            rounds=rounds,
            **extra,
            acks=acks,
            votes=votes,
            recycles=recycles,
            reads=reads,
            echoes=echoes,
            upload_bytes=upload_bytes,
            dispatch_ms=round(dispatch_ms, 4),
            **self._take(DISPATCH_PHASES),
            mu_wait_ms=round(mu_wait_ms, 4),
            arrays_made=arrays_made,
            arrays_retired=arrays_retired,
            ack_blocks_stale=ack_blocks_stale,
            read_blocks_stale=read_blocks_stale,
            reads_scalar=reads_scalar,
            echoes_scalar=echoes_scalar,
        )
        if self.recorder.stalls != stalls:
            r.counter_add(_DEV + "stalls_total")
        self._scope_span = self._last_span = span
        return span

    def retire(self) -> None:
        """The late half of ``retire_ms``: the blocks the newest step's
        launch replaced were dropped after that step returned (the
        engine's ``drop_retired``, a coordinator's call once its round's
        commits are offloaded); the time joins that step's span, as
        :meth:`egress` writes its half late."""
        span = self._last_span
        late = self._take(("retire_ms",))["retire_ms"]
        if span is not None:  # in place: the span's t1 stays its egress
            span["retire_ms"] = round(span.get("retire_ms", 0.0) + late, 4)

    def egress(
        self, span: dict, *, egress_ms: float, egress_rows: int,
        reads_released: int, arrays_retired: int = 0, decode_pairs: int = 0,
    ) -> None:
        """Close a dispatch span at harvest: blocking egress wall time
        plus what the block released; ``arrays_retired`` (the egress
        block, dropped once fetched) adds to the span's count;
        ``decode_pairs`` the (row, slot) pairs the read decode visited on
        scalars (0: no read plane, or the whole plane was scanned)."""
        r = self.registry
        r.histogram_observe(
            _DEV + "egress_latency_ms", egress_ms, buckets=LATENCY_BUCKETS_MS
        )
        if egress_rows:
            r.counter_add(_DEV + "egress_rows_total", egress_rows)
        if reads_released:
            r.counter_add(_DEV + "reads_released_total", reads_released)
        stalls = self.recorder.stalls
        self.recorder.update(
            span,
            egress_ms=round(egress_ms, 4),
            **self._take(EGRESS_PHASES),
            egress_rows=egress_rows,
            reads_released=reads_released,
            arrays_retired=span.get("arrays_retired", 0) + arrays_retired,
            decode_pairs=decode_pairs,
        )
        if self.recorder.stalls != stalls:
            r.counter_add(_DEV + "stalls_total")


class HostObs:
    """Compartmentalized host-plane instruments (hostplane.py, ISSUE 8).

    Families (``dragonboat_host_*``):

    - ``ingress_submitted_total`` / ``ingress_drains_total`` /
      ``ingress_drained_total`` — ring traffic; drained/drains is the
      drain batch size (the batcher's amortization)
    - gauge ``ingress_ring_depth`` — staged commands still ringed at the
      end of a drain
    - ``wal_flushes_total`` / ``wal_riders_total`` /
      ``wal_updates_total`` — group-commit flusher cycles, committer
      submissions merged per cycle (riders/flushes = the fsync
      amortization factor, published as gauge ``wal_amortization``) and
      raft updates persisted
    - histogram ``wal_flush_latency_ms`` — merged save+fsync wall time
    - ``apply_batches_total`` / ``apply_groups_total`` — decoupled apply
      executor wakeups and the groups they covered
    - ``egress_notified_total`` — client completions delivered off the
      apply workers

    Stage spans land in the shared flight recorder (``ingress_drain`` /
    ``wal_flush`` kinds) next to the device-plane spans; the same
    ``is not None`` latch keeps the obs-off host plane bit-identical.
    """

    __slots__ = ("recorder", "registry")

    _COUNTERS = (
        _HOST + "ingress_submitted_total",
        _HOST + "ingress_drains_total",
        _HOST + "ingress_drained_total",
        _HOST + "wal_flushes_total",
        _HOST + "wal_riders_total",
        _HOST + "wal_updates_total",
        _HOST + "apply_batches_total",
        _HOST + "apply_groups_total",
        _HOST + "egress_notified_total",
    )

    def __init__(
        self,
        recorder: Optional[FlightRecorder] = None,
        registry: Optional[MetricsRegistry] = None,
    ):
        from . import default_recorder

        self.recorder = recorder or default_recorder()
        self.registry = registry or DEFAULT_REGISTRY
        r = self.registry
        _describe(r, self._COUNTERS + (
            _HOST + "ingress_ring_depth", _HOST + "wal_amortization",
            _HOST + "wal_flush_latency_ms",
        ))
        for name in self._COUNTERS:
            r.counter_add(name, 0)
        r.gauge_set(_HOST + "ingress_ring_depth", 0)
        r.gauge_set(_HOST + "wal_amortization", 0)
        r.histogram_declare(
            _HOST + "wal_flush_latency_ms", buckets=LATENCY_BUCKETS_MS
        )

    def ingress_submit(self, n: int) -> None:
        self.registry.counter_add(_HOST + "ingress_submitted_total", n)

    def ingress_drain(
        self, *, groups: int, cmds: int, wall_ms: float, ring_depth: int
    ) -> dict:
        r = self.registry
        r.counter_add(_HOST + "ingress_drains_total")
        if cmds:
            r.counter_add(_HOST + "ingress_drained_total", cmds)
        r.gauge_set(_HOST + "ingress_ring_depth", ring_depth)
        return self.recorder.record(
            "ingress_drain",
            groups=groups,
            cmds=cmds,
            wall_ms=round(wall_ms, 4),
        )

    def wal_flush(
        self, *, riders: int, updates: int, wall_ms: float,
        amortization: float,
    ) -> dict:
        r = self.registry
        r.counter_add(_HOST + "wal_flushes_total")
        r.counter_add(_HOST + "wal_riders_total", riders)
        if updates:
            r.counter_add(_HOST + "wal_updates_total", updates)
        r.gauge_set(_HOST + "wal_amortization", round(amortization, 3))
        r.histogram_observe(
            _HOST + "wal_flush_latency_ms", wall_ms,
            buckets=LATENCY_BUCKETS_MS,
        )
        return self.recorder.record(
            "wal_flush",
            riders=riders,
            updates=updates,
            wall_ms=round(wall_ms, 4),
        )

    def apply_batch(self, *, groups: int) -> None:
        r = self.registry
        r.counter_add(_HOST + "apply_batches_total")
        if groups:
            r.counter_add(_HOST + "apply_groups_total", groups)

    def egress_batch(self, n: int) -> None:
        if n:
            self.registry.counter_add(_HOST + "egress_notified_total", n)


class HostProcObs:
    """Multi-process host-tier instruments (hostproc/, ISSUE 12).

    Families (``dragonboat_hostproc_*``):

    - gauge ``workers_alive`` — worker processes currently alive
    - ``worker_restarts_total`` — crash respawns (the monitor's bounded
      restart path)
    - gauge ``ring_depth`` — bytes staged across all shared-memory
      rings, sampled by the monitor thread
    - ``ring_full_total{role}`` — sustained-full pushes that raised
      SystemBusy
    - ``fallbacks_total{role}`` — stage executions that fell back
      in-process (worker gone/busy)
    - ``calls_total{role}`` — completed worker round trips
    - histogram ``worker_wall_ms{role}`` — worker-side execution wall
      per round trip (the per-stage worker wall the latency attribution
      table wants next to the ``ipc`` trace stage)

    Same ``is not None`` latch contract as every other plane: obs off
    keeps the hostproc hot path bit-identical.
    """

    __slots__ = ("registry",)

    _ROLES = ("encode", "wal", "apply")

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry or DEFAULT_REGISTRY
        r = self.registry
        _describe(r, (
            _HPROC + "workers_alive", _HPROC + "worker_restarts_total",
            _HPROC + "ring_depth", _HPROC + "ring_full_total",
            _HPROC + "fallbacks_total", _HPROC + "calls_total",
            _HPROC + "worker_wall_ms",
        ))
        r.gauge_set(_HPROC + "workers_alive", 0)
        r.gauge_set(_HPROC + "ring_depth", 0)
        r.counter_add(_HPROC + "worker_restarts_total", 0)
        for role in self._ROLES:
            labels = {"role": role}
            r.counter_add(_HPROC + "ring_full_total", 0, labels=labels)
            r.counter_add(_HPROC + "fallbacks_total", 0, labels=labels)
            r.counter_add(_HPROC + "calls_total", 0, labels=labels)
            r.histogram_declare(
                _HPROC + "worker_wall_ms", buckets=LATENCY_BUCKETS_MS,
                labels=labels,
            )

    def workers_alive(self, n: int) -> None:
        self.registry.gauge_set(_HPROC + "workers_alive", n)

    def restart(self) -> None:
        self.registry.counter_add(_HPROC + "worker_restarts_total")

    def ring_depth(self, n: int) -> None:
        self.registry.gauge_set(_HPROC + "ring_depth", n)

    def ring_full(self, role: str) -> None:
        self.registry.counter_add(
            _HPROC + "ring_full_total", labels={"role": role}
        )

    def fallback(self, role: str) -> None:
        self.registry.counter_add(
            _HPROC + "fallbacks_total", labels={"role": role}
        )

    def call(self, role: str, wall_ms: float) -> None:
        labels = {"role": role}
        r = self.registry
        r.counter_add(_HPROC + "calls_total", labels=labels)
        r.histogram_observe(
            _HPROC + "worker_wall_ms", wall_ms,
            buckets=LATENCY_BUCKETS_MS, labels=labels,
        )


class HealthObs:
    """Cluster-health-plane instruments (obs/health.py, ISSUE 13).

    Families (``dragonboat_health_*``):

    - ``samples_total`` + histogram ``sample_ms`` — sampling cadence and
      per-sample wall cost (the overhead evidence next to the bench
      axis's <5% assertion)
    - gauge ``groups`` — groups covered by the last sample
    - ``events_total{detector}`` — detector OPEN events
    - gauge ``open{detector}`` — events currently open (the ``/healthz``
      verdict is "degraded" whenever any is nonzero)
    - histogram ``recovery_seconds{detector}`` — open→close durations:
      the recovery-time attribution (failover / worker-respawn /
      devsm-rebind p99s the perf ledger publishes)
    - ``busy_rows_total`` — per-group rows skipped by the raft_mu
      budget mid-walk (ISSUE 20 satellite: sampler degradation must be
      itself detectable, not silent)

    Same ``is not None`` latch contract as every other plane: health off
    registers none of this.
    """

    __slots__ = ("registry",)

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 detectors=()):
        self.registry = registry or DEFAULT_REGISTRY
        r = self.registry
        _describe(r, (
            _HEALTH + "samples_total", _HEALTH + "sample_ms",
            _HEALTH + "groups", _HEALTH + "events_total",
            _HEALTH + "open", _HEALTH + "recovery_seconds",
            _HEALTH + "busy_rows_total",
        ))
        r.counter_add(_HEALTH + "samples_total", 0)
        r.counter_add(_HEALTH + "busy_rows_total", 0)
        r.gauge_set(_HEALTH + "groups", 0)
        r.histogram_declare(_HEALTH + "sample_ms", buckets=LATENCY_BUCKETS_MS)
        for det in detectors:
            labels = {"detector": det}
            r.counter_add(_HEALTH + "events_total", 0, labels=labels)
            r.gauge_set(_HEALTH + "open", 0, labels=labels)
            r.histogram_declare(
                _HEALTH + "recovery_seconds", buckets=RECOVERY_BUCKETS_S,
                labels=labels,
            )

    def sample(self, *, wall_ms: float, groups: int) -> None:
        r = self.registry
        r.counter_add(_HEALTH + "samples_total")
        r.gauge_set(_HEALTH + "groups", groups)
        r.histogram_observe(
            _HEALTH + "sample_ms", wall_ms, buckets=LATENCY_BUCKETS_MS
        )

    def busy_rows(self, n: int) -> None:
        if n:
            self.registry.counter_add(_HEALTH + "busy_rows_total", n)

    def event_open(self, detector: str, *, open_count: int) -> None:
        labels = {"detector": detector}
        r = self.registry
        r.counter_add(_HEALTH + "events_total", labels=labels)
        r.gauge_set(_HEALTH + "open", open_count, labels=labels)

    def event_close(self, detector: str, *, duration_s: float,
                    open_count: int) -> None:
        labels = {"detector": detector}
        r = self.registry
        r.gauge_set(_HEALTH + "open", open_count, labels=labels)
        r.histogram_observe(
            _HEALTH + "recovery_seconds", duration_s,
            buckets=RECOVERY_BUCKETS_S, labels=labels,
        )


class TelemObs:
    """Device-telemetry-fold instruments (ops/kernels.py ``telem_fold``,
    ISSUE 20).

    Families (``dragonboat_telem_*``), all refreshed from the latest
    harvested aggregate — snapshots of the device fold, not host-side
    accumulation:

    - ``folds_total`` — aggregates published to the sampler
    - gauge ``groups{state}`` — live groups per raft state
    - gauge ``stalled_groups`` — commit watermark flat with pending work
    - gauge ``commit_lag{bucket}`` — log2 lag histogram, labeled by the
      bucket's lower bound (``0``, ``1``, ``2``, ``4`` … capped top)
    - gauge ``worst_lag`` — the top-K drill-down's first row
    - gauge ``read_slots`` / ``kv_ents`` — plane slot occupancy

    Same ``is not None`` latch contract as every other plane: aggregate
    sampling off registers none of this.
    """

    __slots__ = ("registry", "_bucket_labels")

    _STATES = ("follower", "candidate", "leader", "observer", "witness")

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 buckets: int = 16):
        self.registry = registry or DEFAULT_REGISTRY
        r = self.registry
        _describe(r, (
            _TELEM + "folds_total", _TELEM + "groups",
            _TELEM + "stalled_groups", _TELEM + "commit_lag",
            _TELEM + "worst_lag", _TELEM + "read_slots",
            _TELEM + "kv_ents",
        ))
        r.counter_add(_TELEM + "folds_total", 0)
        r.gauge_set(_TELEM + "stalled_groups", 0)
        r.gauge_set(_TELEM + "worst_lag", 0)
        r.gauge_set(_TELEM + "read_slots", 0)
        r.gauge_set(_TELEM + "kv_ents", 0)
        for s in self._STATES:
            r.gauge_set(_TELEM + "groups", 0, labels={"state": s})
        # bucket i counts lags in [2^(i-1), 2^i) (bucket 0 = lag 0;
        # top bucket capped) — label by the inclusive lower bound
        self._bucket_labels = tuple(
            {"bucket": str(0 if i == 0 else 1 << (i - 1))}
            for i in range(buckets)
        )
        for lbl in self._bucket_labels:
            r.gauge_set(_TELEM + "commit_lag", 0, labels=lbl)

    def fold(self, snap: dict) -> None:
        """Publish one harvested aggregate (the ``telem_snapshot``
        dict) into the registry."""
        r = self.registry
        r.counter_add(_TELEM + "folds_total")
        for s, n in zip(self._STATES, snap.get("state_counts", ())):
            r.gauge_set(_TELEM + "groups", n, labels={"state": s})
        r.gauge_set(_TELEM + "stalled_groups", snap.get("stalled", 0))
        topk = snap.get("topk") or ()
        r.gauge_set(_TELEM + "worst_lag", topk[0][1] if topk else 0)
        r.gauge_set(_TELEM + "read_slots", snap.get("read_slots", 0))
        r.gauge_set(_TELEM + "kv_ents", snap.get("kv_ents", 0))
        for lbl, n in zip(self._bucket_labels, snap.get("lag_hist", ())):
            r.gauge_set(_TELEM + "commit_lag", n, labels=lbl)


class RecoveryObs:
    """Closed-loop recovery instruments (obs/recovery.py, ISSUE 17).

    Families (``dragonboat_recovery_*``):

    - ``actions_total{detector,action}`` — remediations executed
    - ``dryrun_total{detector,action}`` — remediations logged-only
      (dry-run mode)
    - ``skipped_total{reason}`` — open events declined (not leader on
      this host, rate limit, cooldown, flap-suppressed, observe-only
      detector, no viable target)
    - gauge ``suppressed_keys{detector}`` — keys currently flap-damped
    - ``failures_total{detector,action}`` — remediations that raised
    - histogram ``action_seconds{action}`` — decide-to-commit wall per
      executed remediation

    Zero-registered per detector/action at construction (the HealthObs
    precedent: a scrape distinguishes "recovery off" — families absent
    — from "on but idle" — families at zero).  Same ``is not None``
    latch contract as every other plane.
    """

    __slots__ = ("registry",)

    #: skip-reason vocabulary (zero-registered)
    SKIP_REASONS = (
        "not_leader", "rate_limited", "cooldown", "suppressed",
        "observe_only", "no_target", "stopped",
    )

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 matrix=()):
        """``matrix`` — iterable of ``(detector, action)`` pairs to
        zero-register (the controller's actuation matrix)."""
        self.registry = registry or DEFAULT_REGISTRY
        r = self.registry
        _describe(r, (
            _RECOV + "actions_total", _RECOV + "dryrun_total",
            _RECOV + "skipped_total", _RECOV + "suppressed_keys",
            _RECOV + "failures_total", _RECOV + "action_seconds",
        ))
        for det, action in matrix:
            labels = {"detector": det, "action": action}
            r.counter_add(_RECOV + "actions_total", 0, labels=labels)
            r.counter_add(_RECOV + "dryrun_total", 0, labels=labels)
            r.counter_add(_RECOV + "failures_total", 0, labels=labels)
            r.gauge_set(
                _RECOV + "suppressed_keys", 0, labels={"detector": det}
            )
            r.histogram_declare(
                _RECOV + "action_seconds", buckets=RECOVERY_BUCKETS_S,
                labels={"action": action},
            )
        for reason in self.SKIP_REASONS:
            r.counter_add(
                _RECOV + "skipped_total", 0, labels={"reason": reason}
            )

    def action(self, detector: str, action: str, *,
               duration_s: float) -> None:
        r = self.registry
        labels = {"detector": detector, "action": action}
        r.counter_add(_RECOV + "actions_total", labels=labels)
        r.histogram_observe(
            _RECOV + "action_seconds", duration_s,
            buckets=RECOVERY_BUCKETS_S, labels={"action": action},
        )

    def dryrun(self, detector: str, action: str) -> None:
        self.registry.counter_add(
            _RECOV + "dryrun_total",
            labels={"detector": detector, "action": action},
        )

    def skipped(self, reason: str) -> None:
        self.registry.counter_add(
            _RECOV + "skipped_total", labels={"reason": reason}
        )

    def failure(self, detector: str, action: str) -> None:
        self.registry.counter_add(
            _RECOV + "failures_total",
            labels={"detector": detector, "action": action},
        )

    def suppressed(self, detector: str, count: int) -> None:
        self.registry.gauge_set(
            _RECOV + "suppressed_keys", count,
            labels={"detector": detector},
        )


class DevProfObs:
    """Device capacity & profiling instruments (obs/devprof.py, ISSUE 15).

    Families (``dragonboat_devprof_*``):

    - gauges ``hbm_bytes{plane,artifact}`` / ``hbm_plane_bytes{plane}``
      — the HBM ledger: every resident device artifact priced by bytes
    - gauges ``bytes_per_group`` / ``capacity_groups`` /
      ``model_error_pct`` — the capacity model (max groups per device;
      prediction vs actually-allocated bytes)
    - histogram ``device_ms`` + gauge ``duty_cycle`` — the sampled
      device-time estimator (block_until_ready deltas, 1-in-N)
    - ``dispatches_total`` / ``sampled_total`` /
      ``padded_rounds_total`` / ``wasted_rounds_total`` + gauge
      ``padding_waste_ratio`` — fused padding-waste accounting
    - gauge ``programs`` + histogram ``program_compile_ms`` + gauges
      ``program_{flops,bytes,temp_bytes}{variant}`` — the warm-set
      program registry (XLA cost/memory analysis per program)
    - ``captures_total`` + gauge ``capture_active`` — on-demand
      ``jax.profiler`` capture windows

    Same ``is not None`` latch contract as every other plane: devprof
    off registers none of this.
    """

    __slots__ = ("registry",)

    _PLANES = ("quorum", "read", "devsm", "dispatch")

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry or DEFAULT_REGISTRY
        r = self.registry
        _describe(r, (
            _DEVPROF + "hbm_bytes", _DEVPROF + "hbm_plane_bytes",
            _DEVPROF + "bytes_per_group", _DEVPROF + "capacity_groups",
            _DEVPROF + "model_error_pct", _DEVPROF + "device_ms",
            _DEVPROF + "duty_cycle", _DEVPROF + "dispatches_total",
            _DEVPROF + "sampled_total", _DEVPROF + "padded_rounds_total",
            _DEVPROF + "wasted_rounds_total",
            _DEVPROF + "padding_waste_ratio", _DEVPROF + "programs",
            _DEVPROF + "program_compile_ms", _DEVPROF + "program_flops",
            _DEVPROF + "program_bytes", _DEVPROF + "program_temp_bytes",
            _DEVPROF + "captures_total", _DEVPROF + "capture_active",
        ))
        for name in (
            "dispatches_total", "sampled_total", "padded_rounds_total",
            "wasted_rounds_total", "captures_total",
        ):
            r.counter_add(_DEVPROF + name, 0)
        for name in (
            "bytes_per_group", "capacity_groups", "model_error_pct",
            "duty_cycle", "padding_waste_ratio", "programs",
            "capture_active",
        ):
            r.gauge_set(_DEVPROF + name, 0)
        for plane in self._PLANES:
            r.gauge_set(
                _DEVPROF + "hbm_plane_bytes", 0, labels={"plane": plane}
            )
        r.histogram_declare(
            _DEVPROF + "device_ms", buckets=LATENCY_BUCKETS_MS
        )
        r.histogram_declare(
            _DEVPROF + "program_compile_ms", buckets=LATENCY_BUCKETS_MS
        )

    def device_ms(self, ms: float) -> None:
        self.registry.histogram_observe(
            _DEVPROF + "device_ms", ms, buckets=LATENCY_BUCKETS_MS
        )

    def flush_dispatch(
        self, *, dispatches: int, sampled: int, padded: int, wasted: int,
        waste_ratio: float, duty_cycle: float,
    ) -> None:
        """Counter DELTAS accumulated since the last flush (the tracer's
        local-accumulate/periodic-flush discipline — a registry bump per
        dispatch would tax the round thread) plus the window gauges."""
        r = self.registry
        if dispatches:
            r.counter_add(_DEVPROF + "dispatches_total", dispatches)
        if sampled:
            r.counter_add(_DEVPROF + "sampled_total", sampled)
        if padded:
            r.counter_add(_DEVPROF + "padded_rounds_total", padded)
        if wasted:
            r.counter_add(_DEVPROF + "wasted_rounds_total", wasted)
        r.gauge_set(_DEVPROF + "padding_waste_ratio", round(waste_ratio, 4))
        r.gauge_set(_DEVPROF + "duty_cycle", round(duty_cycle, 4))

    def ledger(
        self, *, artifacts: dict, planes: dict, bytes_per_group: float,
        capacity_groups: int, model_error_pct: Optional[float],
        shard_artifacts: Optional[list] = None,
    ) -> None:
        """``shard_artifacts`` (mesh-sharded facade, ops/mesh.py): a list
        of per-shard artifact dicts — each publishes its own
        ``hbm_bytes{plane,artifact,shard}`` rows alongside the
        aggregated shard-less rows, so a scrape sees both the whole
        mesh's residency and each device's."""
        r = self.registry
        for (plane, artifact), nbytes in artifacts.items():
            r.gauge_set(
                _DEVPROF + "hbm_bytes", nbytes,
                labels={"plane": plane, "artifact": artifact},
            )
        if shard_artifacts:
            for i, per_shard in enumerate(shard_artifacts):
                for (plane, artifact), nbytes in per_shard.items():
                    r.gauge_set(
                        _DEVPROF + "hbm_bytes", nbytes,
                        labels={"plane": plane, "artifact": artifact,
                                "shard": str(i)},
                    )
        for plane in self._PLANES:
            r.gauge_set(
                _DEVPROF + "hbm_plane_bytes", planes.get(plane, 0),
                labels={"plane": plane},
            )
        r.gauge_set(_DEVPROF + "bytes_per_group", round(bytes_per_group, 1))
        r.gauge_set(_DEVPROF + "capacity_groups", capacity_groups)
        if model_error_pct is not None:
            r.gauge_set(
                _DEVPROF + "model_error_pct", round(model_error_pct, 3)
            )

    def program(
        self, *, variant: str, flops: float, bytes_accessed: float,
        temp_bytes: int, compile_ms: float,
    ) -> None:
        r = self.registry
        labels = {"variant": variant}
        r.gauge_set(_DEVPROF + "program_flops", flops, labels=labels)
        r.gauge_set(_DEVPROF + "program_bytes", bytes_accessed, labels=labels)
        r.gauge_set(
            _DEVPROF + "program_temp_bytes", temp_bytes, labels=labels
        )
        r.histogram_observe(
            _DEVPROF + "program_compile_ms", compile_ms,
            buckets=LATENCY_BUCKETS_MS,
        )

    def programs_done(self, n: int) -> None:
        self.registry.gauge_set(_DEVPROF + "programs", n)

    def capture(self, active: bool) -> None:
        r = self.registry
        if active:
            r.counter_add(_DEVPROF + "captures_total")
        r.gauge_set(_DEVPROF + "capture_active", 1 if active else 0)


#: dispatch-concurrency buckets: how many shard streams were in flight
#: at once (mesh sizes are small powers of two; >1 is the headline)
CONCURRENCY_BUCKETS = (1, 2, 4, 8, 16, 32)


class MeshObs:
    """Mesh-dispatch-plane instruments (ops/mesh.py, ISSUE 16).

    Families (``dragonboat_mesh_*``):

    - gauge ``shards`` — per-shard engines behind the facade
    - gauge ``groups{shard}`` — the live group→shard assignment table
    - ``migrations_total`` + histogram ``migration_ms`` — cost-driven
      placement moves and their stage-out→stage-in wall time
    - histogram ``dispatch_concurrency`` — shard dispatch streams
      simultaneously in flight per fan-out; any observation above 1 is
      the direct "two shards dispatched concurrently" evidence the old
      global mutex made impossible

    Holds the SHARED recorder the per-shard ``EngineObs`` publish into
    (one ring, so per-shard dispatch spans interleave and overlap is
    assertable from span timestamps alone) — same ``recorder`` /
    ``registry`` surface as ``EngineObs`` so the coordinator's obs
    wiring is facade-agnostic.
    """

    __slots__ = ("recorder", "registry", "n_shards")

    def __init__(
        self,
        recorder: FlightRecorder,
        registry: Optional[MetricsRegistry] = None,
        n_shards: int = 1,
    ):
        self.recorder = recorder
        self.registry = registry or DEFAULT_REGISTRY
        self.n_shards = n_shards
        r = self.registry
        _describe(r, (
            _MESH + "shards", _MESH + "groups",
            _MESH + "migrations_total", _MESH + "migration_ms",
            _MESH + "dispatch_concurrency",
        ))
        r.gauge_set(_MESH + "shards", n_shards)
        for i in range(n_shards):
            r.gauge_set(_MESH + "groups", 0, labels={"shard": str(i)})
        r.counter_add(_MESH + "migrations_total", 0)
        r.histogram_declare(
            _MESH + "migration_ms", buckets=LATENCY_BUCKETS_MS
        )
        r.histogram_declare(
            _MESH + "dispatch_concurrency", buckets=CONCURRENCY_BUCKETS
        )

    def placement(self, counts) -> None:
        """Publish the live assignment table (groups per shard)."""
        r = self.registry
        for i, n in enumerate(counts):
            r.gauge_set(_MESH + "groups", n, labels={"shard": str(i)})

    def migration(self, cluster_id, src, dst, wall_ms, counts) -> dict:
        r = self.registry
        r.counter_add(_MESH + "migrations_total")
        r.histogram_observe(
            _MESH + "migration_ms", wall_ms, buckets=LATENCY_BUCKETS_MS
        )
        self.placement(counts)
        return self.recorder.record(
            "mesh_migration",
            cluster_id=cluster_id,
            src_shard=src,
            dst_shard=dst,
            wall_ms=round(wall_ms, 4),
        )

    def concurrency(self, peak: int) -> None:
        """One fan-out's high-water mark of simultaneously in-flight
        shard dispatch streams."""
        if peak > 0:
            self.registry.histogram_observe(
                _MESH + "dispatch_concurrency", peak,
                buckets=CONCURRENCY_BUCKETS,
            )


class ReadCtx:
    """One sampled ReadIndex context on its way through a leader's read
    plane (ISSUE 39): the instants ``CoordObs.read_ctx`` writes its span
    from, each taken on ``perf_counter`` at its site (the table in
    ``docs/overview.md``).  The step workers write ``a`` / ``e1`` / ``eq``
    / ``r`` (and ``c`` on the scalar path), the round thread ``s`` / ``d``
    / ``c``: no field has two writers."""

    __slots__ = (
        "cluster_id", "low", "high", "term", "origin", "tid",
        "trace_origin", "need", "peers", "eq_peer", "path", "round0",
        "rounds", "stage_round", "confirm_round",
        "a", "s", "e1", "eq", "d", "c", "r",
        "lease_fallback", "remaining_ticks",
    )

    def __init__(self, cluster_id: int, low: int, high: int, term: int,
                 remote: bool, trace, need: int, round0: int):
        self.cluster_id = cluster_id
        self.low = low
        self.high = high
        self.term = term
        self.origin = "remote" if remote else "local"
        # the requester's identifier: its tracer's host and trace id
        self.tid = trace.tid
        self.trace_origin = trace.origin
        # echoes of distinct followers that make the quorum with the
        # leader's own; the followers heard from; whose echo made it
        self.need = need
        self.peers: set = set()
        self.eq_peer = None
        # ``device`` (given a slot), ``scalar:<cause>`` (the echoes are
        # tallied by the step worker), ``dropped`` (a transition took it)
        self.path = None
        self.round0 = round0  # the host's dispatched rounds as of ``a``
        self.rounds = None
        self.stage_round = None
        self.confirm_round = None
        self.a = time.perf_counter()
        self.s = self.e1 = self.eq = self.d = self.c = self.r = None
        # a lease group (ISSUE 41): its leader found the lease not valid
        # and the ctx took the ReadIndex plane; or, ``path = lease``, the
        # ticks of validity left when the lease answered it
        self.lease_fallback = False
        self.remaining_ticks = None


def _leg_ms(t_from, t_to):
    if t_from is None or t_to is None:
        return None
    return round((t_to - t_from) * 1e3, 4)


class CoordObs:
    """Round-loop instruments for one ``TpuQuorumCoordinator``."""

    __slots__ = ("recorder", "registry", "host", "ph")

    _COUNTERS = (
        _COORD + "rounds_total",
        _COORD + "ops_drained_total",
        _COORD + "tick_deficit_total",
        _COORD + "commits_offloaded_total",
        # adaptive K-round batching (ISSUE 7): rounds served by ONE fused
        # multi-round dispatch, and the fused rounds they carried — the
        # ratio to rounds_total is the live fused duty cycle
        _COORD + "fused_dispatch_total",
        _COORD + "fused_rounds_total",
        _COORD + "read_acks_total",
        _COORD + "ticks_replayed_total",
        _COORD + "ticks_dropped_total",
        _COORD + "elections_held_total",
        _COORD + "hb_block_rows_total",
        _COORD + "hb_lite_rows_total",
        _COORD + "quiesce_enters_total",
        _COORD + "quiesce_wakes_total",
    )

    def __init__(
        self, recorder: FlightRecorder,
        registry: Optional[MetricsRegistry] = None,
        host: Optional[str] = None,
    ):
        self.recorder = recorder
        self.registry = registry or DEFAULT_REGISTRY
        self.host = host
        #: phase milliseconds of the round in progress (drain, fanout)
        self.ph: dict = {}
        r = self.registry
        _describe(r, self._COUNTERS + (
            _COORD + "staged_depth", _COORD + "read_fallbacks_total",
            _COORD + "round_latency_ms", _COORD + "tick_flags_total",
            _COORD + "hb_single_total", _COORD + "rows",
            _COORD + "reads_total", _COORD + "rows_quiesced",
        ))
        for name in self._COUNTERS:
            r.counter_add(name, 0)
        for cause in READ_FALLBACK_CAUSES:
            r.counter_add(
                _COORD + "read_fallbacks_total", 0, {"cause": cause}
            )
        for cause in HB_SINGLE_CAUSES:
            r.counter_add(_COORD + "hb_single_total", 0, {"cause": cause})
        for kind in ("heartbeat", "elect", "demote"):
            r.counter_add(_COORD + "tick_flags_total", 0, {"kind": kind})
        for origin in ("local", "remote"):
            r.counter_add(_COORD + "reads_total", 0, {"origin": origin})
        r.gauge_set(_COORD + "staged_depth", 0)
        r.gauge_set(_COORD + "rows", 0)
        r.gauge_set(_COORD + "rows_quiesced", 0)
        r.histogram_declare(
            _COORD + "round_latency_ms", buckets=LATENCY_BUCKETS_MS
        )

    def phase(self, name: str) -> Phase:
        """``with obs.phase("drain"):`` — a phase of the round in
        progress (annotation + ``<name>_ms`` accumulation)."""
        return Phase(self.ph, name)

    def round_open(self, *, t0: float, gate: str, wait_ms: float) -> dict:
        """A round that WILL dispatch opens its span before its first
        dispatch (quiet early-return rounds are not recorded), so the
        engine's spans can name it as ``parent`` and the tracer's
        ``device_round`` stamp links a span with an interval.  ``t0`` is
        the round's start on ``perf_counter``; ``wait_ms`` how long its
        oldest staged op or tick had waited by then."""
        return self.recorder.record(
            "coord_round",
            t0=t0,
            host=self.host,
            gate=gate,
            wait_ms=round(wait_ms, 4),
        )

    @staticmethod
    def campaign(cluster_id: int) -> None:
        """A replica of ``cluster_id`` on some NodeHost of this process
        became a candidate (``quiesce_wake``'s ``elected``)."""
        _CAMPAIGNS[cluster_id] = time.perf_counter()

    def quiesce_wake(self, trace, events) -> dict:
        """A SAMPLED request that found its group asleep reached its end
        (``Tracer.finish``; ``trace.woke`` is the instant and the role of
        the replica its step woke, the first of the group to wake): one
        ``quiesce_wake`` span from that wake to the operation's commit (a
        write: its last ``device_round`` stamp) or confirmation (a read:
        ``read_confirm``), or to its end where it has no such stamp
        (``events`` are its stamps in time order).  ``elected``: a
        replica of the group on a NodeHost of this process campaigned
        between the two."""
        t0, role = trace.woke
        stage = "device_round" if trace.kind == "write" else "read_confirm"
        t1 = next(
            (t for s, t, _th in reversed(events) if s == stage and t >= t0),
            events[-1][1],
        )
        return self.recorder.record(
            "quiesce_wake", t0=t0, t1=t1, host=self.host,
            wake_ms=round((t1 - t0) * 1e3, 4), op=trace.kind,
            woke=role, elected=t0 <= _CAMPAIGNS.get(trace.cluster_id, -1.0) <= t1,
            cluster_id=trace.cluster_id, outcome=trace.outcome, tid=trace.tid,
        )

    def read_ctx(self, rc: ReadCtx) -> dict:
        """A sampled ReadIndex context reached its end on this leader:
        write its one ``read_ctx`` span, ``t0`` the instant the leader
        accepted it (``a``), ``t1`` the instant its requesters were
        answered (``r``; a dropped context ends where it was dropped).
        The chain ``echo_trip_ms`` + ``echo_wait_ms`` + ``confirm_ms`` +
        ``release_ms`` is ``leader_ms``; a leg whose ends were not both
        seen (a context released by a later one's quorum before its own
        echo was drained) is left out, and so is ``leader_ms`` of a
        dropped context.  A read answered under the leader's lease
        (``path = lease``) has ``leader_ms`` and ``remaining_ticks`` and
        none of the chain; one that found the lease not valid is today's
        span with ``lease_fallback``."""
        fields = {
            "cluster_id": rc.cluster_id, "low": rc.low, "high": rc.high,
            "origin": rc.origin, "path": rc.path or "unstaged",
            "tid": rc.tid, "trace_origin": rc.trace_origin,
            "echoes": len(rc.peers),
        }
        for name, value in (
            ("leader_ms", _leg_ms(rc.a, rc.r)),
            ("echo_trip_ms", _leg_ms(rc.a, rc.eq)),
            ("echo_wait_ms", _leg_ms(rc.eq, rc.d)),
            ("confirm_ms", _leg_ms(rc.d, rc.c)),
            ("release_ms", _leg_ms(rc.c, rc.r)),
            ("stage_wait_ms", _leg_ms(rc.a, rc.s)),
            ("first_echo_ms", _leg_ms(rc.a, rc.e1)),
            ("rounds", rc.rounds),
            ("stage_round", rc.stage_round),
            ("confirm_round", rc.confirm_round),
            ("remaining_ticks", rc.remaining_ticks),
            ("lease_fallback", rc.lease_fallback or None),
        ):
            if value is not None:
                fields[name] = value
        t1 = rc.r if rc.r is not None else time.perf_counter()
        return self.recorder.record(
            "read_ctx", t0=rc.a, t1=t1, host=self.host, **fields
        )

    def round(
        self,
        span: dict,
        *,
        ops: int,
        deficit: int,
        commits: int,
        staged_depth: int,
        k_rounds: int = 1,
        fused: bool = False,
        fuse_skip: Optional[str] = None,
        read_acks: int = 0,
        read_fallbacks: Optional[dict] = None,
        reads_staged: int = 0,
        reads_refused: int = 0,
        plane: Optional[dict] = None,
        fan_in: Optional[dict] = None,
    ) -> dict:
        """Close a dispatched round's span (``round_open``).  The
        recorder's stall check on ``wall_ms`` IS the round-gate watchdog:
        a round outlasting ``stall_ms`` auto-dumps the ring with this
        span as the trigger.

        ``k_rounds`` is the adaptive K the round chose (1 = the
        single-round path); ``fused`` marks a fused multi-round dispatch;
        ``fuse_skip`` names why a K>1 backlog did NOT fuse
        (``"warmup"`` — programs still compiling, ``"votes"`` — an
        election rode this round, ``"churn"`` — unwarmed in-program
        recycles/pre-staged rounds in the backlog, ``"mesh_warmup"`` —
        a mesh coordinator's per-shard program sets still warming) so
        the warmup gate can assert proposals never blocked on
        compilation.  ``read_acks`` / ``read_fallbacks`` (cause -> count)
        are this round's heartbeat read echoes, tallied on the device /
        scalar-side; ``reads_staged`` / ``reads_refused`` the ReadIndex
        contexts given / refused a device slot.  ``plane`` is the tick
        and heartbeat plane's account of the round: ``ticks_replayed`` /
        ``ticks_dropped`` / ``elect_held``, the ``hb_flags`` /
        ``elect_flags`` / ``demote_flags`` fanned out, ``hb_block_rows``
        (``hb_lite_rows`` of them served without the group's lock)
        against ``hb_single`` (cause -> count, since the last recorded
        round: block messages arrive between rounds), and the ``rows``
        registered.  ``fan_in`` is what the drains since the last recorded
        round handed on: ``acks_drained`` (follower acknowledgements),
        ``reads_local`` / ``reads_remote`` (ReadIndex contexts a leader
        staged for the host's own clients / that a follower forwarded)."""
        r = self.registry
        t1 = time.perf_counter()
        wall_ms = (t1 - span["t0"]) * 1e3
        r.counter_add(_COORD + "rounds_total")
        if ops:
            r.counter_add(_COORD + "ops_drained_total", ops)
        if deficit:
            r.counter_add(_COORD + "tick_deficit_total", deficit)
        if commits:
            r.counter_add(_COORD + "commits_offloaded_total", commits)
        if fused:
            r.counter_add(_COORD + "fused_dispatch_total")
            r.counter_add(_COORD + "fused_rounds_total", k_rounds)
        if read_acks:
            r.counter_add(_COORD + "read_acks_total", read_acks)
        r.gauge_set(_COORD + "staged_depth", staged_depth)
        r.histogram_observe(
            _COORD + "round_latency_ms", wall_ms, buckets=LATENCY_BUCKETS_MS
        )
        extra = {}
        if fused:
            extra["fused"] = True
        if fuse_skip:
            extra["fuse_skip"] = fuse_skip
        for cause, n in (read_fallbacks or {}).items():
            if n:
                r.counter_add(
                    _COORD + "read_fallbacks_total", n, {"cause": cause}
                )
            extra["read_fallback_" + cause] = n
        if plane is not None:
            for key, name in (
                ("ticks_replayed", "ticks_replayed_total"),
                ("ticks_dropped", "ticks_dropped_total"),
                ("elect_held", "elections_held_total"),
                ("hb_block_rows", "hb_block_rows_total"),
                ("hb_lite_rows", "hb_lite_rows_total"),
            ):
                if plane[key]:
                    r.counter_add(_COORD + name, plane[key])
                extra[key] = plane[key]
            for key, kind in (
                ("hb_flags", "heartbeat"), ("elect_flags", "elect"),
                ("demote_flags", "demote"),
            ):
                if plane[key]:
                    r.counter_add(
                        _COORD + "tick_flags_total", plane[key],
                        {"kind": kind},
                    )
                extra[key] = plane[key]
            single = 0
            for cause, n in plane["hb_single"].items():
                if n:
                    r.counter_add(
                        _COORD + "hb_single_total", n, {"cause": cause}
                    )
                    extra["hb_single_" + cause] = n
                    single += n
            extra["hb_single"] = single
            extra["rows"] = plane["rows"]
            r.gauge_set(_COORD + "rows", plane["rows"])
            if "rows_quiesced" in plane:  # a host with a quiesce group
                for key in ("quiesce_enters", "quiesce_wakes"):
                    if plane[key]:
                        r.counter_add(_COORD + key + "_total", plane[key])
                    extra[key] = plane[key]
                extra["rows_quiesced"] = plane["rows_quiesced"]
                r.gauge_set(_COORD + "rows_quiesced", plane["rows_quiesced"])
        if fan_in is not None:
            for origin in ("local", "remote"):
                if fan_in["reads_" + origin]:
                    r.counter_add(
                        _COORD + "reads_total", fan_in["reads_" + origin],
                        {"origin": origin},
                    )
            extra.update(fan_in)
        ph = self.ph
        self.recorder.update(
            span,
            t1=t1,
            wall_ms=round(wall_ms, 4),
            drain_ms=round(ph.pop("drain_ms", 0.0), 4),
            fanout_ms=round(ph.pop("fanout_ms", 0.0), 4),
            ops=ops,
            deficit=deficit,
            k_rounds=k_rounds,
            commits=commits,
            read_acks=read_acks,
            reads_staged=reads_staged,
            reads_refused=reads_refused,
            **extra,
        )
        return span


#: what a ``snapshot_save`` span's ``save_kind`` says: the periodic save that
#: ``snapshot_entries`` makes fall due, a user's ``request_snapshot``
#: (exported ones too), an on-disk state machine streamed to a follower
SNAPSHOT_KINDS = ("periodic", "requested", "stream")
#: whole seconds of ``ReplicaObs.window`` kept (oldest dropped)
REPLICA_SECONDS_KEEP = 900

_LIVE_REPLICA_OBS: list = []
#: cluster id -> ``perf_counter`` of the newest campaign any replica of the
#: group on a NodeHost of this process started (``CoordObs.campaign``)
_CAMPAIGNS: dict = {}


def replica_obs_live() -> list:
    """Every ``ReplicaObs`` of this process whose NodeHost has not stopped
    (co-hosted NodeHosts have one each)."""
    return list(_LIVE_REPLICA_OBS)


class _SaveScope:
    """One snapshot task on a pool worker: a ``dbtpu:snapshot_save``
    annotation around it and, at its end, one ``snapshot_save`` span."""

    __slots__ = ("obs", "ann", "t0", "lap_t", "fields")

    def __init__(self, obs, fields: dict):
        self.obs = obs
        self.ann = annotate("snapshot_save")
        self.fields = fields

    def lap(self, name: str) -> None:
        """``<name>_ms``: the time since the task began or the lap before
        (``sm_save``, ``commit``: self time of ``dbtpu:snapshot_save``)."""
        now = time.perf_counter()
        self.fields[name + "_ms"] = round((now - self.lap_t) * 1e3, 4)
        self.lap_t = now

    def update_lock_held(self, seconds: float) -> None:
        """``update_lock_ms``: how long a regular state machine's
        ``_update_mu`` was held for this save (``rsm.StateMachine.save``):
        what the group's applies waited."""
        self.fields["update_lock_ms"] = round(seconds * 1e3, 4)

    def logdb_commit(self) -> None:
        """The save issued one fsynced LogDB batch (``logdb_commits``, and
        one of ``fsyncs``): told by whoever issued it, right behind the
        call."""
        fields = self.fields
        fields["logdb_commits"] = fields.get("logdb_commits", 0) + 1
        fields["fsyncs"] = fields.get("fsyncs", 0) + 1

    def saved(self, ss, env) -> Phase:
        """The image is committed, so what ``env`` counted is final
        (``image_buffered``, its share of ``fsyncs``); what is left is the
        compaction behind it: ``with scope.saved(ss, env):`` is
        ``compact_ms`` and an annotation of its own (``dbtpu:compact``)."""
        fields = self.fields
        fields["saved"] = True
        fields["index"] = ss.index
        fields["image_bytes"] = ss.file_size
        fields["image_buffered"] = env.image_buffered
        fields["fsyncs"] = fields.get("fsyncs", 0) + env.fsyncs
        fields.setdefault("update_lock_ms", 0.0)
        return Phase(fields, "compact")

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = self.lap_t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.obs._save_done(self, t1)
        return False


class ReplicaObs:
    """What a group's own settings turn on, for the replicas of one
    NodeHost: snapshot saves and the log compaction behind them
    (``snapshot_entries``, ``compaction_overhead``), InstallSnapshot
    traffic, the snapshot pool, and the check-quorum windows a leader
    closes (``check_quorum``).

    A save is one ``snapshot_save`` span (``t0``..``t1`` on
    ``perf_counter``, the pool worker's time on it): ``queue_ms`` from the
    task's enqueue to the worker taking it, ``sm_save_ms`` the state
    machine's ``save`` (the wait for a regular state machine's update lock
    is in it; ``update_lock_ms`` is how long that lock was then held, what
    the group's applies waited: the image's capture, and its disk writes
    only where it outgrew a block), ``commit_ms`` ``snapshotter.commit``
    (rename, fsync, the LogDB batch of the record and the stale records'
    deletes), ``compact_ms`` the log compaction behind it (the LogReader's,
    the LogDB's range delete, the stale directories), ``save_ms`` all of
    it; ``image_buffered`` (the image went out as one write),
    ``logdb_commits`` and ``fsyncs`` (of files, directories and LogDB
    batches), counted where they are issued (``SSEnv``, ``Snapshotter``,
    ``Node``); ``image_bytes``, ``entries_since`` (applied less the
    previous snapshot's index when the worker took the task) beside the
    group's ``snapshot_entries``, ``save_kind`` (``SNAPSHOT_KINDS``; a
    span's ``kind`` is ``snapshot_save``) and ``saved`` once the image was
    committed.  ``save_ms`` is no stall
    field: a slow user ``save_snapshot`` is the user's.

    Counts are kept twice: in the registry (``dragonboat_snapshot_*``,
    ``dragonboat_checkq_*``) and by the whole ``perf_counter`` second they
    fell in, so a reader takes a window's from ``window(lo, hi)`` whatever
    the span ring still holds.  Same ``is not None`` latch as every other
    plane: with tracer and ``enable_metrics`` off a NodeHost builds none,
    and ``Node.replica_obs`` / ``Engine.replica_obs`` stay ``None``."""

    __slots__ = ("recorder", "registry", "host", "workers", "_mu", "_secs")

    _COUNTERS = (
        _SNAP + "saves_refused_total",
        _SNAP + "compactions_total",
        _SNAP + "pool_busy_seconds_total",
        _CHECKQ + "windows_total",
        _CHECKQ + "stepdowns_total",
        "dragonboat_node_scalar_ticks_total",
    )

    def __init__(
        self, recorder: Optional[FlightRecorder] = None,
        registry: Optional[MetricsRegistry] = None,
        host: Optional[str] = None, workers: int = 0,
    ):
        from . import default_recorder

        self.recorder = recorder or default_recorder()
        self.registry = registry or DEFAULT_REGISTRY
        self.host = host
        #: snapshot-pool workers of the host's execution engine
        self.workers = workers
        self._mu = threading.Lock()
        self._secs: dict = {}
        r = self.registry
        _describe(r, self._COUNTERS + (
            _SNAP + "saves_total", _SNAP + "save_latency_ms",
            _SNAP + "installs_total", _SNAP + "pool_queue_depth",
        ))
        for name in self._COUNTERS:
            r.counter_add(name, 0)
        for kind in SNAPSHOT_KINDS:
            r.counter_add(_SNAP + "saves_total", 0, {"kind": kind})
        for direction in ("sent", "received"):
            r.counter_add(
                _SNAP + "installs_total", 0, {"direction": direction}
            )
        r.gauge_set(_SNAP + "pool_queue_depth", 0)
        r.histogram_declare(
            _SNAP + "save_latency_ms", buckets=LATENCY_BUCKETS_MS
        )
        _LIVE_REPLICA_OBS.append(self)

    def close(self) -> None:
        try:
            _LIVE_REPLICA_OBS.remove(self)
        except ValueError:
            pass

    def _add_locked(self, sec: int, name: str, n) -> None:
        secs = self._secs
        bucket = secs.get(sec)
        if bucket is None:
            bucket = secs[sec] = {}
            if len(secs) > REPLICA_SECONDS_KEEP:
                del secs[min(secs)]
        bucket[name] = bucket.get(name, 0) + n

    def _count(self, name: str) -> None:
        sec = int(time.perf_counter())
        with self._mu:
            self._add_locked(sec, name, 1)

    def window(self, lo: float, hi: float) -> dict:
        """name -> count over the whole seconds whose middle lies in
        ``[lo, hi)`` on ``perf_counter``: ``saves``, ``saves_refused``,
        ``compactions``, ``installs_sent``, ``installs_received``,
        ``pool_busy_s``, ``checkq_windows``, ``checkq_stepdowns``,
        ``scalar_ticks``."""
        out: dict = {}
        with self._mu:
            for sec, bucket in self._secs.items():
                if lo <= sec + 0.5 < hi:
                    for name, n in bucket.items():
                        out[name] = out.get(name, 0) + n
        return out

    # ---- snapshot saves (node.py, on a snapshot-pool worker) ----------

    def save(self, *, kind: str, cluster_id: int, node_id: int,
             queued_at: float, entries_since: int,
             snapshot_entries: int) -> _SaveScope:
        """``with obs.save(...) as scope:`` around one snapshot task."""
        fields = {
            "save_kind": kind, "cluster_id": cluster_id, "node_id": node_id,
            "entries_since": entries_since,
            "snapshot_entries": snapshot_entries, "saved": False,
        }
        if queued_at:
            fields["queue_ms"] = round(
                (time.perf_counter() - queued_at) * 1e3, 4
            )
        return _SaveScope(self, fields)

    def _save_done(self, scope: _SaveScope, t1: float) -> None:
        fields = scope.fields
        save_ms = (t1 - scope.t0) * 1e3
        if "compact_ms" in fields:
            fields["compact_ms"] = round(fields["compact_ms"], 4)
        self.recorder.record(
            "snapshot_save", t0=scope.t0, t1=t1, host=self.host,
            save_ms=round(save_ms, 4), **fields
        )
        if fields["saved"] or fields["save_kind"] == "stream":
            r = self.registry
            r.counter_add(
                _SNAP + "saves_total", 1, {"kind": fields["save_kind"]}
            )
            r.histogram_observe(
                _SNAP + "save_latency_ms", save_ms,
                buckets=LATENCY_BUCKETS_MS,
            )
            self._count("saves")

    def save_refused(self) -> None:
        """A periodic save fell due while the one before it was still
        queued or running: it is skipped, not queued behind it."""
        self.registry.counter_add(_SNAP + "saves_refused_total")
        self._count("saves_refused")

    def compaction(self) -> None:
        self.registry.counter_add(_SNAP + "compactions_total")
        self._count("compactions")

    def install(self, direction: str) -> None:
        """An InstallSnapshot ``sent`` to a follower that fell behind the
        compacted log, or ``received`` whole from a leader."""
        self.registry.counter_add(
            _SNAP + "installs_total", 1, {"direction": direction}
        )
        self._count("installs_" + direction)

    # ---- the snapshot pool (engine.py) --------------------------------

    def pool_task(self, t0: float, t1: float, queue_depth: int) -> None:
        """A pool worker was busy from ``t0`` to ``t1``; each whole second
        gets the part of it that fell in it, so that a window's busy
        seconds never pass its workers times its length."""
        r = self.registry
        r.counter_add(_SNAP + "pool_busy_seconds_total", t1 - t0)
        r.gauge_set(_SNAP + "pool_queue_depth", queue_depth)
        sec = int(t0)
        with self._mu:
            while t0 < t1:
                end = min(t1, sec + 1.0)
                self._add_locked(sec, "pool_busy_s", end - t0)
                t0, sec = end, sec + 1

    # ---- scalar ticks (node.py, under raftMu) -------------------------

    def scalar_ticks(self, n: int) -> None:
        """A step worker's turn took ``n`` LOCAL_TICK messages off a
        replica's queue: what a replica costs the host a tick where its
        raft clock is the host's (``window``: ``scalar_ticks``)."""
        self.registry.counter_add("dragonboat_node_scalar_ticks_total", n)
        sec = int(time.perf_counter())
        with self._mu:
            self._add_locked(sec, "scalar_ticks", n)

    # ---- check-quorum (node.py, under raftMu) -------------------------

    def checkq_window(self, stepped_down: bool) -> None:
        """A leader's check-quorum window closed (the device's
        ``checkq_demote`` flag ran the scalar CHECK_QUORUM);
        ``stepped_down`` if it had not heard from a quorum."""
        self.registry.counter_add(_CHECKQ + "windows_total")
        self._count("checkq_windows")
        if stepped_down:
            self.registry.counter_add(_CHECKQ + "stepdowns_total")
            self._count("checkq_stepdowns")
