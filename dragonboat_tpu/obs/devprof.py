"""Device capacity & profiling plane (ISSUE 15 tentpole).

The obs stack attributes latency end-to-end on the host (``trace.py``),
across hosts (``replattr.py``) and over time (``health.py``) — but the
device plane that does the actual work was a black box: nobody could
answer "how many HBM bytes does a G=100k coordinator hold", "what does
each warmed fused program cost", or "how much of a dispatch's wall is
device execution vs host dispatch overhead".  ROADMAP items 2 and 3
(devsm scale-out past ``n_kv_ents``, 1M+ groups sharded across a mesh)
are capacity-planning problems that start from exactly this ledger.
Four pillars:

- **HBM memory ledger** (:meth:`DevProf.hbm_ledger`): walks the
  engine's resident state — the ``ops/state.py`` quorum tensors, the
  pending-read ctx slots, the devsm ``kv_value``/``kv_ent_*`` slabs and
  the in-flight pipelined dispatch's egress accumulators (the
  staged-round double buffer) — and publishes
  ``dragonboat_devprof_hbm_bytes{plane,artifact}`` gauges.  Every
  artifact is priced from the live arrays' own ``nbytes`` (pure
  metadata, no transfer), so the ledger can never drift from what is
  actually allocated.

- **Capacity model** (:func:`predict_bytes` /
  :meth:`DevProf.capacity_model`): extrapolates resident bytes for any
  ``(G, P, S, V, E)`` geometry from a ``jax.eval_shape`` walk of the
  SAME ``make_state`` constructor the engine allocates through (a new
  state field can't escape the model), plus the per-dispatch transient
  upload term at a given fused K bucket (mirroring
  ``engine.upload_nbytes`` over the fused argument tuple).  Asserted
  against actually-allocated bytes (tests/bench: within 10%) and
  against ``device.memory_stats()`` where the backend provides one —
  the sizing input for ROADMAP items 2/3.

- **Program registry** (:meth:`DevProf.collect_programs`): walks the
  warm set (``BatchedQuorumEngine.warm_plan`` — K buckets × reads ×
  votes × kv variants, the same enumeration ``warmup_fused`` /
  ``warmup_devsm`` compile) and records each program's
  ``lower().compile().cost_analysis()`` / ``memory_analysis()`` —
  flops, bytes accessed, peak temp allocation, compile wall (cache-hot
  compiles deserialize via the persistent compilation cache).  Rendered
  as the perf ledger's "Device programs" table.

- **Device-time estimator** (:meth:`DevProf.note_dispatch`, called from
  the engine's dispatch sites behind the ``_devprof is not None``
  latch): 1-in-N dispatches measure a post-launch
  ``block_until_ready`` delta — the device-execution estimate the
  FlightRecorder's host walls (``dispatch_ms``/``egress_ms``) do not
  separate — feeding the ``dragonboat_devprof_device_ms`` histogram, a
  duty-cycle gauge, and fused **padding-waste** accounting (padded
  program K minus live/ticked rounds is provable no-op device work).
  The sampled delta is also stamped onto the dispatch's recorder span
  as ``device_ms``.

On-demand ``jax.profiler`` capture windows
(:meth:`DevProf.capture` ← ``NodeHost.profile_device``) land their
artifacts beside the ``dump_trace``/``debug_dump`` outputs (the node
host dir), and the read-only ``/debug/devprof`` handler on the existing
MetricsServer serves :meth:`DevProf.to_json` so trace sessions and
device profiles are collected from one place.

Overhead contract (the ``_obs is not None`` latch precedent): OFF by
default.  ``NodeHostConfig.device_profile = 0`` constructs nothing —
the engine keeps ``_devprof = None`` and a bit-identical host path —
and with the plane on, per-dispatch cost is a few counter bumps under
one micro-lock; the sampled ``block_until_ready`` runs 1-in-N
(``sample_every``, default 16) and is priced by the bench devprof axis
(<5% + 2·SEM asserted).
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from ..logger import get_logger
from ..ops.state import field_plane, state_layout
# nearest-rank percentile, shared with the health plane (one
# implementation — divergent copies would make device_ms percentiles
# incomparable with the health plane's latency percentiles)
from .health import _pctile

plog = get_logger("devprof")

#: device-time sampling stride (1-in-N dispatches pay a blocking
#: block_until_ready); NodeHostConfig.device_profile overrides
DEFAULT_SAMPLE_EVERY = 16

#: HBM-ledger gauge refresh cadence (rides the sampling tick — the walk
#: is pure array metadata, but republishing ~30 gauges per dispatch
#: would be registry traffic for nothing)
LEDGER_REFRESH_S = 1.0

#: bounded device-time sample window for the estimator percentiles
_SAMPLE_WINDOW = 512


def predict_bytes(
    n_groups: int,
    n_peers: int,
    n_read_slots: Optional[int] = None,
    n_kv_slots: Optional[int] = None,
    n_kv_ents: Optional[int] = None,
    n_kv_reads: Optional[int] = None,
    k_bucket: int = 0,
    include_reads: bool = False,
    include_kv: bool = False,
) -> dict:
    """The capacity model: predicted device-resident bytes for a group
    geometry, decomposed per plane, plus the transient per-dispatch
    upload term at fused bucket ``k_bucket`` (0 = no dispatch term).

    The resident half walks ``jax.eval_shape`` over the engine's own
    ``make_state`` (``ops.state.state_layout``), so it is exact by
    construction and every field scales linearly with the group axis:
    ``bytes_per_group = state_bytes / n_groups``.  The dispatch half
    is the ONE ingress block a fused ``quorum_multiround`` dispatch
    ships, by the layout rule the engine stages through
    (``ops.packed.ingress_sections``) — the read/kv sections only count
    when those planes are live, exactly like the engine's
    ``has_reads``/``has_kv`` statics.
    """
    layout = state_layout(
        n_groups, n_peers,
        n_read_slots=n_read_slots,
        n_kv_slots=n_kv_slots,
        n_kv_ents=n_kv_ents,
    )
    planes: Dict[str, int] = {}
    for field in layout.values():
        planes[field["plane"]] = planes.get(field["plane"], 0) + field["nbytes"]
    state_bytes = sum(planes.values())
    out = {
        "n_groups": n_groups,
        "n_peers": n_peers,
        "state_bytes": state_bytes,
        "planes": planes,
        "bytes_per_group": state_bytes / max(1, n_groups),
        "dispatch_bytes": 0,
    }
    if k_bucket > 0:
        from ..ops.packed import ingress_sections, ingress_size
        from ..ops.state import (
            KV_ENT_SLOTS, KV_READ_SLOTS, KV_SLOTS, READ_SLOTS,
        )

        # the value-slot width (V) does not ride the dispatch — only
        # the entry/read stage sections do
        dims = tuple(
            d if v is None else v for v, d in (
                (n_read_slots, READ_SLOTS), (n_kv_slots, KV_SLOTS),
                (n_kv_ents, KV_ENT_SLOTS), (n_kv_reads, KV_READ_SLOTS),
            )
        )
        # the live coordinator's fused block: ack maxima (K,G,P), the
        # tick mask (K,), no votes, no churn; all int32 lanes
        out["dispatch_bytes"] = 4 * ingress_size(ingress_sections(
            "fused", n_groups, n_peers, dims, k=k_bucket, do_tick=True,
            has_reads=include_reads, has_kv=include_kv,
        ))
        out["k_bucket"] = k_bucket
    out["total_bytes"] = state_bytes + out["dispatch_bytes"]
    return out




def _spec_nbytes(args) -> int:
    """Total bytes of a tuple of ``ShapeDtypeStruct`` stand-ins (``None``
    entries skipped) — the abstract twin of ``engine.upload_nbytes``."""
    import numpy as np

    return int(sum(
        int(np.prod(a.shape, dtype=np.int64)) * np.dtype(a.dtype).itemsize
        for a in args if a is not None
    ))


class DevProf:
    """The device capacity & profiling plane for one engine.

    Constructed by NodeHost when ``device_profile > 0`` (or directly by
    tests/bench), bound to a :class:`BatchedQuorumEngine` via
    :meth:`bind_engine` — which flips the engine's ``_devprof`` latch.
    ``registry=None`` keeps everything local (no families registered);
    with a registry the :class:`~.instruments.DevProfObs` families
    publish on the estimator's flush cadence.
    """

    def __init__(
        self,
        registry=None,
        recorder=None,
        sample_every: int = DEFAULT_SAMPLE_EVERY,
        artifact_dir: Optional[str] = None,
        ledger_refresh_s: float = LEDGER_REFRESH_S,
    ):
        if sample_every < 1:
            raise ValueError("devprof sample_every must be >= 1")
        self.engine = None
        self.coord = None  # optional: set by TpuQuorumCoordinator wiring
        self.recorder = recorder
        self.sample_every = int(sample_every)
        self.artifact_dir = artifact_dir
        self.ledger_refresh_s = float(ledger_refresh_s)
        self._obs = None
        if registry is not None:
            from .instruments import DevProfObs

            self._obs = DevProfObs(registry=registry)
        self._mu = threading.Lock()
        # estimator state (all under _mu; flushed totals track what the
        # registry has seen so counter families only receive deltas)
        self._dispatches = 0
        self._sampled = 0
        self._padded = 0
        self._wasted = 0
        self._since_sample = self.sample_every - 1  # sample the 1st
        self._flushed = {"dispatches": 0, "sampled": 0, "padded": 0,
                         "wasted": 0}
        self._device_ms: deque = deque(maxlen=_SAMPLE_WINDOW)
        self._duty = 0.0
        self._win_t0 = time.monotonic()
        self._ledger_mono = 0.0
        self._last_ledger: Optional[dict] = None
        # predict_bytes is an invariant of the engine geometry + the
        # plane latches: cache it per latch combination so the ~1s
        # ledger refresh on the dispatch thread never re-traces
        # make_state through eval_shape (review-caught)
        self._predict_cache: Dict[Tuple[bool, bool], dict] = {}
        # program registry (compiled lazily, guarded by its own lock —
        # a collect must not block the estimator's micro-lock)
        self._prog_mu = threading.Lock()
        self._programs: Optional[List[dict]] = None
        # capture windows.  _mu only guards the STATE (the active-window
        # slot); the actual jax.profiler start/stop calls — which can
        # spend seconds serializing the artifact — run under this
        # dedicated lock so note_dispatch's micro-lock never waits on
        # profiler I/O (review-caught: stop_trace under _mu froze the
        # round loop for the whole artifact write)
        self._prof_mu = threading.Lock()
        self._capture: Optional[dict] = None
        # the window being torn down right now: claimed out of _capture
        # but its stop_trace/artifact write still in flight —
        # capture_active stays True (and new windows refuse) until the
        # profiler is genuinely free again
        self._stopping: Optional[dict] = None
        self._captures: List[dict] = []
        self._capture_seq = 0  # disambiguates same-second window dirs

    # ------------------------------------------------------------------
    # wiring
    # ------------------------------------------------------------------

    def bind_engine(self, engine) -> None:
        """Attach to the engine (flips its ``_devprof`` latch) and take
        the first ledger snapshot so the families are live — a scrape
        distinguishes "devprof off" (families absent) from "on, idle"."""
        self.engine = engine
        self._predict_cache.clear()
        engine.enable_devprof(self)
        try:
            self.refresh_ledger()
        except Exception:
            plog.exception("initial devprof ledger refresh failed")

    def unbind(self) -> None:
        eng, self.engine = self.engine, None
        if eng is not None and eng._devprof is self:
            eng.disable_devprof()

    # ------------------------------------------------------------------
    # pillar 3: device-time estimator + padding waste (engine hook)
    # ------------------------------------------------------------------

    def note_dispatch(
        self, kind: str, leaf, *, rounds: int, live_rounds: int, span=None
    ) -> None:
        """Engine dispatch hook (behind the ``_devprof is not None``
        latch).  Unsampled dispatches pay a few counter bumps under one
        micro-lock; every ``sample_every``-th dispatch blocks on
        ``leaf`` (post-launch → completion, the device-execution
        estimate including queueing) and flushes the accumulated
        counters + window gauges to the registry."""
        with self._mu:
            self._dispatches += 1
            if kind == "fused":
                # padding waste is a FUSED-path metric (only padded
                # K-batched programs ship no-op rounds); counting the
                # single-round sparse/dense dispatches into the base
                # would dilute the ratio toward 0 on quiet clusters
                self._padded += rounds
                if rounds > live_rounds:
                    self._wasted += rounds - live_rounds
            self._since_sample += 1
            if self._since_sample < self.sample_every:
                return
            self._since_sample = 0
        t0 = time.perf_counter()
        ms = None
        try:
            import jax

            jax.block_until_ready(leaf)
            ms = (time.perf_counter() - t0) * 1e3
        except Exception as e:
            # a device fault during the sampled wait is the single most
            # interesting event this plane can see — surface it, and
            # still flush the accumulated counters below (swallowing it
            # silently stalled dispatches_total until the next sample)
            plog.warning("devprof sampled block_until_ready failed: %r", e)
        if ms is not None and span is not None:
            # producer-thread span mutation (the recorder's egress-field
            # pattern): the estimator's delta lands on the very span the
            # FlightRecorder holds for this dispatch
            span["device_ms"] = round(ms, 4)
        with self._mu:
            self._sampled += 1
            if ms is not None:
                self._device_ms.append(ms)
            now = time.monotonic()
            wall_ms = (now - self._win_t0) * 1e3
            # duty estimate over the stride window: the sampled
            # dispatch's device time extrapolated across the stride,
            # over the wall the stride spanned (clamped — it IS an
            # extrapolation, documented as such)
            if ms is not None and wall_ms > 0:
                self._duty = min(1.0, (ms * self.sample_every) / wall_ms)
            self._win_t0 = now
            deltas = {
                k: getattr(self, "_" + k) - self._flushed[k]
                for k in self._flushed
            }
            for k in self._flushed:
                self._flushed[k] = getattr(self, "_" + k)
            waste_ratio = self._wasted / self._padded if self._padded else 0.0
            duty = self._duty
        obs = self._obs
        if obs is not None:
            if ms is not None:
                obs.device_ms(ms)
            obs.flush_dispatch(
                dispatches=deltas["dispatches"],
                sampled=deltas["sampled"],
                padded=deltas["padded"],
                wasted=deltas["wasted"],
                waste_ratio=waste_ratio,
                duty_cycle=duty,
            )
        if time.monotonic() - self._ledger_mono >= self.ledger_refresh_s:
            try:
                self.refresh_ledger()
            except Exception:
                plog.exception("devprof ledger refresh failed")

    def estimator_stats(self) -> dict:
        with self._mu:
            samples = list(self._device_ms)
            padded, wasted = self._padded, self._wasted
            out = {
                "dispatches": self._dispatches,
                "sampled": self._sampled,
                "sample_every": self.sample_every,
                "padded_rounds": padded,
                "wasted_rounds": wasted,
                "padding_waste_ratio": (
                    round(wasted / padded, 4) if padded else 0.0
                ),
                "duty_cycle": round(self._duty, 4),
            }
        if samples:
            out["device_ms"] = {
                "n": len(samples),
                "p50": round(_pctile(samples, 50), 4),
                "p99": round(_pctile(samples, 99), 4),
                "max": round(max(samples), 4),
            }
        return out

    # ------------------------------------------------------------------
    # pillar 1: the HBM memory ledger
    # ------------------------------------------------------------------

    @staticmethod
    def _engine_artifacts(eng) -> Dict[Tuple[str, str], int]:
        """Price ONE engine's resident device state: the quorum state
        tensors plus the in-flight pipelined dispatch's egress
        accumulators (live ``nbytes`` — pure metadata, no transfer)."""
        artifacts: Dict[Tuple[str, str], int] = {}
        for name, nbytes in eng.state_nbytes().items():
            artifacts[(field_plane(name), name)] = nbytes
        inflight = eng._inflight
        if inflight is not None:
            # the double buffer: the in-flight program's state blocks
            # already ARE the engine's (donated chain), so only its
            # egress block is extra residency
            artifacts[("dispatch", "inflight_egress")] = int(
                inflight[0].nbytes
            )
        return artifacts

    def hbm_ledger(self) -> dict:
        """Walk the engine's resident device state and price every
        artifact, plus the in-flight pipelined dispatch's egress
        accumulators.  Also publishes the ledger gauges and the
        capacity-model summary.

        On a mesh-sharded facade (``ops/mesh.py``) every per-shard
        engine is walked: the top-level artifacts/planes aggregate
        across shards (residency totals stay comparable with the
        single-device ledger), a ``shards`` section itemizes each
        shard's residency, and the gauges publish BOTH the aggregate
        rows and per-shard ``dragonboat_devprof_hbm_bytes{shard}``
        rows."""
        eng = self.engine
        if eng is None:
            return {}
        shards = getattr(eng, "shards", None)
        shard_rows: Optional[list] = None
        shard_artifacts: Optional[list] = None
        if shards:
            artifacts = {}
            shard_rows, shard_artifacts = [], []
            for s in shards:
                arts = self._engine_artifacts(s)
                arts.setdefault(("dispatch", "inflight_egress"), 0)
                shard_artifacts.append(arts)
                splanes: Dict[str, int] = {}
                for (plane, art), nbytes in arts.items():
                    artifacts[(plane, art)] = (
                        artifacts.get((plane, art), 0) + nbytes
                    )
                    splanes[plane] = splanes.get(plane, 0) + nbytes
                shard_rows.append({
                    "planes": splanes,
                    "state_bytes": sum(
                        b for (p, _), b in arts.items() if p != "dispatch"
                    ),
                    "total_bytes": sum(splanes.values()),
                })
        else:
            artifacts = self._engine_artifacts(eng)
        planes: Dict[str, int] = {}
        for (plane, _), nbytes in artifacts.items():
            planes[plane] = planes.get(plane, 0) + nbytes
        state_bytes = sum(
            b for (plane, _), b in artifacts.items() if plane != "dispatch"
        )
        ledger = {
            "artifacts": {
                plane: {
                    art: b
                    for (pl, art), b in sorted(artifacts.items())
                    if pl == plane
                }
                for plane in sorted(planes)
            },
            "planes": planes,
            "state_bytes": state_bytes,
            "total_bytes": sum(planes.values()),
        }
        if shard_rows is not None:
            ledger["shards"] = shard_rows
        model = self.capacity_model(ledger_state_bytes=state_bytes)
        ledger["capacity"] = model
        obs = self._obs
        if obs is not None:
            # the GAUGE set always carries the dispatch artifact — a
            # harvested inflight must rewrite its gauge to 0, or the
            # exposition keeps advertising residency that no longer
            # exists (review-caught: hbm_bytes disagreed with the
            # zeroed hbm_plane_bytes forever after one pipelined block)
            gauge_artifacts = dict(artifacts)
            gauge_artifacts.setdefault(("dispatch", "inflight_egress"), 0)
            obs.ledger(
                artifacts=gauge_artifacts,
                planes=planes,
                bytes_per_group=model["bytes_per_group"],
                capacity_groups=model.get("max_groups") or 0,
                model_error_pct=model.get("model_error_pct"),
                shard_artifacts=shard_artifacts,
            )
        with self._mu:
            self._ledger_mono = time.monotonic()
            self._last_ledger = ledger
        return ledger

    def refresh_ledger(self) -> dict:
        return self.hbm_ledger()

    # ------------------------------------------------------------------
    # pillar 1b: the capacity model
    # ------------------------------------------------------------------

    def capacity_model(
        self,
        budget_bytes: Optional[int] = None,
        ledger_state_bytes: Optional[int] = None,
    ) -> dict:
        """Predict resident bytes for the bound engine's geometry and
        extrapolate max groups per device.  ``budget_bytes`` overrides
        the device's own ``memory_stats()['bytes_limit']`` (absent on
        backends that don't report one, e.g. cpu — ``max_groups`` is
        then None unless a budget is passed).

        On a mesh-sharded facade the geometry half models ONE SHARD
        (each per-shard engine is an independent single-device
        allocation) and the capacity answer multiplies by mesh size:
        ``max_groups_per_device`` from the tightest per-device budget,
        ``max_groups`` = that × ``mesh_shards``."""
        eng = self.engine
        if eng is None:
            return {}
        from ..ops.engine import WARM_K_BUCKETS

        shards = getattr(eng, "shards", None)
        # geometry donor: one shard's engine on a mesh (per-device
        # residency), the engine itself otherwise
        geng = shards[0] if shards else eng
        n_shards = len(shards) if shards else 1
        key = (bool(eng._read_plane_used), bool(eng._devsm_used))
        base = self._predict_cache.get(key)
        if base is None:
            k = max(WARM_K_BUCKETS)
            base = predict_bytes(
                geng.n_groups, geng.n_peers,
                n_read_slots=geng.n_read_slots,
                n_kv_slots=geng.n_kv_slots,
                n_kv_ents=geng.n_kv_ents,
                n_kv_reads=geng.n_kv_reads,
                k_bucket=k,
                include_reads=key[0],
                include_kv=key[1],
            )
            # with a live engine, the dispatch term is DERIVED from the
            # same abstract argument spec the warmup/lowering builder
            # produces — structurally incapable of drifting from the
            # tensors a fused dispatch actually ships (predict_bytes's
            # closed form is the engine-less twin; the test suite
            # asserts the two agree on every plane combination)
            _, ing, _ = geng._variant_args(
                "fused", k, key[0], key[1], abstract=True
            )
            base["dispatch_bytes"] = _spec_nbytes((ing,))
            base["total_bytes"] = base["state_bytes"] + base["dispatch_bytes"]
            self._predict_cache[key] = base
        # shallow copy: the measured/budget fields below are per-call,
        # the cached geometry half is immutable
        pred = dict(base)
        if ledger_state_bytes is None:
            engines = shards if shards else [eng]
            ledger_state_bytes = sum(
                sum(e.state_nbytes().values()) for e in engines
            )
        measured = ledger_state_bytes
        predicted_state = pred["state_bytes"] * n_shards
        if measured:
            pred["measured_state_bytes"] = measured
            pred["model_error_pct"] = round(
                (predicted_state - measured) / measured * 100.0, 4
            )
        per_device_budgets = None
        if budget_bytes is None:
            budget_bytes, per_device_budgets = self._device_budget()
        pred["budget_bytes"] = budget_bytes
        # every term scales linearly with G, so one division extrapolates:
        # resident bytes/group plus the fused dispatch's per-group upload
        per_group = (
            pred["bytes_per_group"]
            + pred["dispatch_bytes"] / max(1, geng.n_groups)
        )
        pred["bytes_per_group_with_dispatch"] = per_group
        per_dev = int(budget_bytes // per_group) if budget_bytes else None
        if n_shards > 1:
            pred["mesh_shards"] = n_shards
            pred["state_bytes_total"] = predicted_state
            pred["total_bytes_total"] = pred["total_bytes"] * n_shards
            if per_device_budgets is not None:
                pred["device_budgets"] = per_device_budgets
            pred["max_groups_per_device"] = per_dev
            pred["max_groups"] = (
                per_dev * n_shards if per_dev is not None else None
            )
        else:
            pred["max_groups"] = per_dev
        return pred

    def _device_budget(self) -> Tuple[Optional[int], Optional[list]]:
        """The backend-reported memory budget of the device(s) holding
        the engine state: ``(per_device_budget, per_shard_budgets)``.
        On a mesh the per-device budget is the TIGHTEST shard's (a
        capacity plan must fit the worst device); per_shard_budgets
        lists them all.  ``(None, None)`` where the backend has no
        ``memory_stats`` — the cpu client."""
        eng = self.engine
        shards = getattr(eng, "shards", None)
        engines = shards if shards else [eng]
        budgets: list = []
        for e in engines:
            try:
                dev = next(iter(e._blk.i32.devices()))
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if not stats:
                budgets.append(None)
                continue
            budgets.append(
                stats.get("bytes_limit")
                or stats.get("bytes_reservable_limit")
            )
        known = [b for b in budgets if b]
        if not known:
            return None, None
        if shards:
            return min(known), budgets
        return known[0], None

    # ------------------------------------------------------------------
    # pillar 2: the program registry
    # ------------------------------------------------------------------

    def collect_programs(
        self, include_kv: Optional[bool] = None, force: bool = False
    ) -> List[dict]:
        """AOT-analyze the engine's warm set: one
        ``lower().compile()`` per warm-plan variant (the SAME
        enumeration and shapes the warmup compiled —
        ``engine.warm_plan`` / ``_variant_args``), recording
        cost-analysis flops / bytes accessed, memory-analysis peak temp
        and argument/output bytes, and compile wall.  Cached after the
        first collection (``force`` re-runs); ``include_kv=None``
        follows the engine's devsm state."""
        with self._mu:
            if self._programs is not None and not force:
                return list(self._programs)
        with self._prog_mu:  # serializes COLLECTORS only — readers
            # (the programs property, to_json, /debug/devprof) take the
            # cheap _mu and never wait out a multi-second compile loop
            with self._mu:
                if self._programs is not None and not force:
                    return list(self._programs)
            eng = self.engine
            if eng is None:
                return []
            if include_kv is None:
                include_kv = bool(eng._devsm_used or eng.kv_fused_ready)
            rows: List[dict] = []
            for kind, arg, hr, kv in eng.warm_plan(include_kv=include_kv):
                label = eng.variant_label(kind, arg, hr, kv)
                t0 = time.perf_counter()
                try:
                    compiled = eng.lower_variant(kind, arg, hr, kv).compile()
                except Exception as e:  # a variant failing must not
                    # hide the rest of the table
                    plog.warning("devprof lower/compile %s: %r", label, e)
                    rows.append({"variant": label, "error": repr(e)})
                    continue
                compile_ms = (time.perf_counter() - t0) * 1e3
                row = {
                    "variant": label,
                    "kind": kind,
                    "compile_ms": round(compile_ms, 2),
                }
                try:
                    ca = compiled.cost_analysis()
                    if isinstance(ca, (list, tuple)):
                        ca = ca[0] if ca else {}
                    ca = ca or {}
                    row["flops"] = float(ca.get("flops", 0.0))
                    row["bytes_accessed"] = float(
                        ca.get("bytes accessed", 0.0)
                    )
                except Exception as e:
                    row["cost_error"] = repr(e)
                try:
                    ma = compiled.memory_analysis()
                    if ma is not None:
                        row["temp_bytes"] = int(ma.temp_size_in_bytes)
                        row["argument_bytes"] = int(
                            ma.argument_size_in_bytes
                        )
                        row["output_bytes"] = int(ma.output_size_in_bytes)
                        row["code_bytes"] = int(
                            ma.generated_code_size_in_bytes
                        )
                except Exception as e:
                    row["memory_error"] = repr(e)
                rows.append(row)
                obs = self._obs
                if obs is not None and "flops" in row:
                    obs.program(
                        variant=label,
                        flops=row["flops"],
                        bytes_accessed=row.get("bytes_accessed", 0.0),
                        temp_bytes=row.get("temp_bytes", 0),
                        compile_ms=compile_ms,
                    )
            with self._mu:
                self._programs = rows
        obs = self._obs
        if obs is not None:
            obs.programs_done(len(rows))
        return rows

    @property
    def programs(self) -> Optional[List[dict]]:
        """The collected registry (None until :meth:`collect_programs`
        ran — reading never triggers compiles NOR waits on one)."""
        with self._mu:
            return list(self._programs) if self._programs is not None else None

    # ------------------------------------------------------------------
    # pillar 4: on-demand jax.profiler capture windows
    # ------------------------------------------------------------------

    def capture(self, ms: float = 1000.0, path: Optional[str] = None) -> str:
        """Open one ``jax.profiler`` capture window for ``ms``
        milliseconds (stopped by a background timer, or early via
        :meth:`stop_capture`).  Returns the artifact directory —
        default: a timestamped ``devprof-*`` dir beside the
        ``dump_trace``/``debug_dump`` artifacts.  One window at a time:
        the profiler is process-global."""
        import jax

        base = self.artifact_dir
        if not base or base == ":memory:":
            import tempfile

            base = tempfile.gettempdir()
        with self._mu:
            self._capture_seq += 1
            seq = self._capture_seq
        # the sequence suffix keeps back-to-back short windows from
        # landing in one same-second directory and interleaving their
        # profiles in a single Perfetto session
        d = path or os.path.join(
            base, time.strftime("devprof-%Y%m%d-%H%M%S") + f"-{seq}"
        )
        rec = {"dir": d, "started": time.time(), "ms": float(ms),
               "stopped": None}
        with self._mu:
            if self._capture is not None or self._stopping is not None:
                raise RuntimeError(
                    "a device profile capture window is already active"
                )
            self._capture = rec  # claim the slot; profiler I/O runs
            self._captures.append(rec)  # outside the estimator lock
        try:
            with self._prof_mu:
                os.makedirs(d, exist_ok=True)
                jax.profiler.start_trace(d)
        except Exception:
            with self._mu:  # roll the claim back — nothing started
                if self._capture is rec:
                    self._capture = None
                self._captures.remove(rec)
            raise
        obs = self._obs
        if obs is not None:
            obs.capture(active=True)
        if self.recorder is not None:
            self.recorder.record("devprof", window_ms=float(ms), dir=d)
        t = threading.Thread(
            target=self._capture_deadline, args=(rec, ms),
            name="devprof-capture", daemon=True,
        )
        t.start()
        return d

    def _capture_deadline(self, rec: dict, ms: float) -> None:
        time.sleep(max(0.0, ms) / 1e3)
        self._stop_capture(rec)

    def stop_capture(self) -> Optional[str]:
        """Stop the active capture window early (None when idle);
        returns its artifact directory."""
        with self._mu:
            rec = self._capture
        if rec is None:
            return None
        self._stop_capture(rec)
        return rec["dir"]

    def _stop_capture(self, rec: dict) -> None:
        import jax

        with self._mu:
            if self._capture is not rec:  # already stopped (early stop
                return  # raced the deadline timer)
            self._capture = None  # claim atomically; the artifact
            self._stopping = rec  # write below must not hold _mu but
            # the window is not OVER until it lands (capture_active)
        with self._prof_mu:
            try:
                jax.profiler.stop_trace()
            except Exception:
                plog.exception("jax.profiler.stop_trace failed")
            rec["stopped"] = time.time()
        obs = self._obs
        if obs is not None:
            obs.capture(active=False)
        with self._mu:
            self._stopping = None
        plog.info("device profile capture written to %s", rec["dir"])

    @property
    def capture_active(self) -> bool:
        with self._mu:
            return self._capture is not None or self._stopping is not None

    def captures(self) -> List[dict]:
        with self._mu:
            return [dict(c) for c in self._captures]

    # ------------------------------------------------------------------
    # introspection (/debug/devprof, debug dumps, bench artifacts)
    # ------------------------------------------------------------------

    def to_json(self) -> dict:
        """Read-only JSON snapshot (never triggers compiles or
        captures): the ledger + capacity model (refreshed), estimator
        stats, any already-collected program registry, capture history
        and — when the coordinator wired a devsm plane — its shadow
        residency."""
        out = {
            "sample_every": self.sample_every,
            "estimator": self.estimator_stats(),
            "ledger": self.hbm_ledger(),
            "programs": self.programs,
            "captures": self.captures(),
        }
        coord = self.coord
        devsm = getattr(coord, "devsm", None) if coord is not None else None
        if devsm is not None:
            try:
                out["devsm"] = devsm.devprof_snapshot()
            except Exception:
                plog.exception("devsm devprof snapshot failed")
        return out

    def stop(self) -> None:
        """Detach from the engine and close any open capture window
        (NodeHost.stop).  Blocks until the stop lands: the deadline
        thread may have claimed the window and still be inside the
        profiler's artifact write — returning before it finishes would
        let NodeHost tear the engine down (or the process exit) under a
        live capture and truncate the profile."""
        self.stop_capture()
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            with self._mu:
                if self._capture is None and self._stopping is None:
                    break
            time.sleep(0.01)
        self.unbind()
