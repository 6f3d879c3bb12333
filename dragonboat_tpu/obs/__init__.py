"""Device-plane observability: flight recorder + quorum-engine metrics.

The fused ``(K,G,P)`` dispatch path is the system's hot core but was a
runtime black box: round-loop behavior, dispatch latency, staging depth,
recycle churn and read-slot occupancy were visible only in offline bench
artifacts, and both the sharded-XLA deadlock and the contact-loss stall
were diagnosed by printf archaeology.  This package gives the device
plane its own telemetry surface (the per-component metrics argument of
the compartmentalization line in PAPERS.md; BlackWater's "failure
handling lives on cheap continuous telemetry"):

- :mod:`recorder` — a lock-light fixed-size ring of per-dispatch span
  records (rounds in block, staged ack/vote/recycle/read counts, upload
  bytes, dispatch/egress wall time, multidev-mutex wait, egress rows and
  reads released, gate reason), dumpable as JSON on demand and
  AUTO-dumped when a span trips the stall threshold — the round-gate
  watchdog and the multi-device dispatch-lock wait feed the same check.
  A span is an interval with a cause: ``t0``/``t1`` on
  ``perf_counter`` (the tracer's clock), ``host``, ``parent`` (a
  dispatch names its ``coord_round``), and its phases as ``*_ms``
  fields that are also ``jax.profiler`` annotations
  (``recorder.ANNOTATIONS``: ``dbtpu:round``, ``dbtpu:drain``,
  ``dbtpu:step``, ``dbtpu:stage``, ``dbtpu:launch``,
  ``dbtpu:retire``, ``dbtpu:egress_wait``, ``dbtpu:decode``,
  ``dbtpu:fanout``, …); ``ops.engine.compilation_log()`` names every
  compile beside it;
- :mod:`instruments` — ``EngineObs`` / ``CoordObs``: counters, gauges
  and latency histograms published into the existing
  :class:`dragonboat_tpu.events.MetricsRegistry`, so
  ``write_health_metrics`` exposes device-plane health next to the
  transport/node counters;
- :mod:`trace` — cross-plane REQUEST tracing (ISSUE 9): a sampled
  1-in-N of proposals/reads carries a per-stage trace context through
  ingress → raft step → WAL → device round → apply → egress, with
  stage histograms, a Perfetto/Chrome-trace export
  (``NodeHost.dump_trace``) and a stage-level stall watchdog that
  dumps the stuck request's partial trace plus this recorder's ring.
- :mod:`health` — the cluster health plane (ISSUE 13): continuous
  per-group/host health sampling into a rolling ring, anomaly
  detectors with open/close events and recovery-time attribution
  (``dragonboat_health_*`` families, ``NodeHost.health_report``), and
  the live scrape endpoint (``/metrics``, ``/healthz``,
  ``/debug/health``, ``/debug/trace``, ``/debug/devprof``).
- :mod:`recovery` — the closed-loop recovery plane (ISSUE 17): a
  RecoveryController subscribed to detector OPEN events drives
  guard-railed remediations (quorum_at_risk → evict dead voter +
  promote standing observer / add standby witness, leader_flap →
  transfer away from flapping hosts, devsm_rebind → force device
  release, commit_stall → fast-lane redrive; worker_flap
  observe-only), rate-limited per group, cooldown-gated, flap-damped,
  with a dry-run mode (``dragonboat_recovery_*`` families,
  ``NodeHost.recovery_report``).
- :mod:`devprof` — the device capacity & profiling plane (ISSUE 15):
  the HBM memory ledger + capacity model
  (``dragonboat_devprof_hbm_bytes{plane,artifact}``, max groups per
  device), the warm-set program registry (per-program XLA cost/memory
  analysis), a sampled device-time estimator with fused padding-waste
  accounting, and on-demand ``jax.profiler`` capture windows
  (``NodeHost.profile_device``).

Overhead contract (the ``_read_plane_used`` precedent; PR 3 took a −43%
host-path regression from ungated per-transition work): observability is
OFF by default.  ``BatchedQuorumEngine._obs`` stays ``None`` and every
hot-path site gates on a plain ``is not None`` attribute check, so an
obs-off engine keeps a bit-identical host path and eager-op set
(regression axis: ``bench._run_obs_axis`` asserts obs-on throughput
within 5% of obs-off).  The module-level latch below flips newly
constructed engines/coordinators on (tests, bench axes); live wiring
goes through ``NodeHostConfig.enable_metrics`` — or the request tracer
(``trace_sample_every`` / ``DBTPU_TRACE_SAMPLE``): recorder and tracer
share one switch and one clock — → ``TpuQuorumCoordinator.enable_obs``.
With both off ``_obs`` and ``tracer`` stay ``None``: no annotation is
built, no clock read, no dict written.
"""
from __future__ import annotations

import threading
from typing import Optional

from .recorder import FlightRecorder  # noqa: F401

_mu = threading.Lock()
_enabled = False
_recorder: Optional[FlightRecorder] = None


def enable(
    recorder: Optional[FlightRecorder] = None, stall_ms: Optional[float] = None
) -> FlightRecorder:
    """Flip the module latch: engines/coordinators constructed AFTER this
    call attach instruments automatically (existing instances opt in via
    their ``enable_obs()``).  Returns the recorder new instances share."""
    global _enabled, _recorder
    with _mu:
        if recorder is not None:
            _recorder = recorder
        elif _recorder is None:
            _recorder = FlightRecorder()
        if stall_ms is not None:
            _recorder.stall_ms = float(stall_ms)
        _enabled = True
        return _recorder


def disable() -> None:
    """Drop the latch; already-attached instruments stay attached."""
    global _enabled
    with _mu:
        _enabled = False


def enabled() -> bool:
    return _enabled


def default_recorder() -> FlightRecorder:
    """The shared recorder (created on first use)."""
    global _recorder
    with _mu:
        if _recorder is None:
            _recorder = FlightRecorder()
        return _recorder
