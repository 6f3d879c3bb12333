"""The device-plane flight recorder: a fixed-size ring of span records.

One span per device dispatch (and per coordinator round): what was
staged, how long the host spent launching it, how long the blocking
egress took, and WHY the dispatch fired (its gate reason).  The ring is
bounded — memory is O(capacity) no matter the load — and recording is
lock-light: one micro-lock bump for the ring slot; span dicts are
mutated in place by their single producing thread afterwards (the
egress fields land at harvest time), so a dump taken mid-flight shows
the in-flight dispatch with its egress still pending — exactly the span
a stall investigation needs.

A span is an INTERVAL with a cause (ISSUE 26): ``t0``/``t1`` on
``time.perf_counter()`` — the clock of the request tracer's stamps
(``obs/trace.py``) — ``host`` (the owning coordinator's NodeHost, so
co-hosted NodeHosts can share ``obs.default_recorder()``) and ``parent``
(the ``seq`` of the span that caused it: a ``dispatch``/``fused`` span
names the ``coord_round`` that ran it).  The same phases are also
``jax.profiler.TraceAnnotation`` events (``ANNOTATIONS`` below), so a
profiler capture shows them on the device trace's clock.  The ring and
the tracer share one switch: a NodeHost whose tracer is on
(``trace_sample_every`` / ``DBTPU_TRACE_SAMPLE``) attaches the ring
exactly as ``enable_metrics`` does.

The stall watchdog rides the same records: any span whose wall fields
(``wall_ms`` / ``dispatch_ms`` / ``egress_ms`` / ``mu_wait_ms``) reach
``stall_ms`` is marked ``stalled`` and triggers an automatic dump —
logged, kept on ``last_dump``, and written to ``dump_path`` when set
(``DBTPU_OBS_DUMP``).  ``stall_ms <= 0`` disables the watchdog (the
bench overhead axis measures with it off).
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import nullcontext
from typing import List, Optional

from ..logger import get_logger

plog = get_logger("obs")

#: a span is a small dict and the ring exists only while obs is on.  It has
#: to hold a traced benchmark window whole, or every reader of it describes
#: the window's tail: three co-hosted NodeHosts with a 7 ms round write a
#: ``coord_round`` and a ``dispatch`` span a round each, ~900 spans a
#: second, 43,710-59,165 in a 48 s window and its drain (ISSUE 39; 16,384
#: wrapped inside every such window).  ~1.5 KB a span: ~200 MB when full
DEFAULT_CAPACITY = 131072

#: the profiler-annotation vocabulary: constant names, one per phase of a
#: coordinator round / engine dispatch / execution-engine batch.  Phases
#: that are also ``*_ms`` span fields map to the field name.
ANNOTATIONS = {
    "round_idle": "dbtpu:round_idle",    # round thread waiting for work
    "round": "dbtpu:round",              # coord_round t0..t1
    "drain": "dbtpu:drain",              # coord_round drain_ms
    "fanout": "dbtpu:fanout",            # coord_round fanout_ms
    "step": "dbtpu:step",                # one engine step()/step_rounds()
                                         # call: holds the phases below;
                                         # its self time is step_ms less
                                         # the phases
    "row_sync": "dbtpu:row_sync",        # dispatch/fused row_sync_ms
    "stage": "dbtpu:stage",              # dispatch/fused stage_ms
    "transfer": "dbtpu:transfer",        # dispatch/fused transfer_ms:
                                         # 0.0, the put rides the launch
    "launch": "dbtpu:launch",            # dispatch/fused launch_ms
    "retire": "dbtpu:retire",            # dispatch/fused retire_ms: the
                                         # drops of the state blocks a
                                         # launch replaced and of its
                                         # fetched egress block, after
                                         # the round's fan-out
    "egress_wait": "dbtpu:egress_wait",  # dispatch/fused egress_wait_ms
    "decode": "dbtpu:decode",            # dispatch/fused decode_ms
    "compile": "dbtpu:compile",          # ops.engine.compilation_log()
    "raft_step": "dbtpu:raft_step",      # execution engine step batch
    "wal_sync": "dbtpu:wal_sync",        # log save + fsync
    "apply": "dbtpu:apply",              # apply batch
    "snapshot_save": "dbtpu:snapshot_save",  # snapshot_save t0..t1: one
                                         # save on a snapshot-pool worker
                                         # (its sm_save_ms and commit_ms
                                         # are self time of this event)
    "compact": "dbtpu:compact",          # snapshot_save compact_ms
    "quiesce_wake": "dbtpu:quiesce_wake",  # the instant a message or a
                                         # request woke a sleeping
                                         # replica (a quiesce_wake span
                                         # starts at its group's first)
}

#: what ``with (obs.phase(...) if obs is not None else OFF):`` enters while
#: observability is off: nothing is built, timed or written
OFF = nullcontext()

_TRACE_ANNOTATION = None


def annotate(phase: str):
    """A ``jax.profiler.TraceAnnotation`` context for one phase of the
    vocabulary (a TraceMe: near-free while no profiler is capturing).
    Callers sit behind their ``_obs`` / ``tracer`` latch — nothing is
    built while observability is off."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(ANNOTATIONS[phase])


class Phase:
    """``with Phase(acc, "stage"):`` — one occurrence of a phase: a
    profiler annotation plus its wall milliseconds added to
    ``acc["stage_ms"]`` (a span's phase field is the sum over the span's
    occurrences; a step may chunk into several programs)."""

    __slots__ = ("acc", "key", "ann", "t")

    def __init__(self, acc: dict, phase: str):
        self.acc = acc
        self.key = phase + "_ms"
        self.ann = annotate(phase)

    def __enter__(self):
        self.ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        acc = self.acc
        acc[self.key] = acc.get(self.key, 0.0) + (
            time.perf_counter() - self.t
        ) * 1e3
        self.ann.__exit__(*exc)
        return False

#: span fields the stall watchdog inspects, in attribution order
_STALL_KEYS = ("wall_ms", "dispatch_ms", "egress_ms", "mu_wait_ms")


def _default_stall_ms() -> float:
    try:
        return float(os.environ.get("DBTPU_OBS_STALL_MS", "1000"))
    except ValueError:
        plog.warning("malformed DBTPU_OBS_STALL_MS; using 1000")
        return 1000.0


class FlightRecorder:
    """Bounded ring of span records with a stall watchdog.

    Span schema (all producers; absent fields simply weren't measured):

    ======================  ==================================================
    field                   meaning
    ======================  ==================================================
    ``seq``                 monotonically increasing record number
    ``kind``                ``"dispatch"`` (engine, single-round),
                            ``"fused"`` (engine, K-round block),
                            ``"coord_round"`` (tpuquorum round loop),
                            ``"warmup"`` (one AOT-warmed program),
                            ``"snapshot_save"`` (one save, stream or
                            requested snapshot on a snapshot-pool
                            worker: ``instruments.ReplicaObs``),
                            ``"read_ctx"`` (one sampled ReadIndex
                            context from a leader's accept to its
                            release: ``instruments.CoordObs.read_ctx``;
                            ``t0``/``t1`` are those two instants, the
                            legs ``echo_trip_ms`` / ``echo_wait_ms`` /
                            ``confirm_ms`` / ``release_ms`` add up to
                            ``leader_ms``)
    ``t0`` ``t1``           the span's interval on ``time.perf_counter()``
                            (the tracer's clock): opened at the round's
                            / step's start, ``t1`` moved by every
                            ``update`` until the span is final
    ``host``                the owning coordinator's NodeHost (its raft
                            address); None for a bare engine
    ``parent``              ``seq`` of the span that caused this one (a
                            dispatch names its ``coord_round``); None at
                            the top
    ``ts``                  wall-clock time the record was WRITTEN — for
                            a human reading a JSON dump only; nothing
                            orders or overlaps spans by it (use ``t0``)
    ``gate``                why the dispatch fired: ``+``-joined subset of
                            ``tick``/``acks``/``reads``/``churn``/``dirty``,
                            or ``drain``
    ``rounds``              scanned rounds in the block (padded program K)
    ``k_rounds``            LIVE rounds: real staged rounds, or the
                            ticked count when a deficit replay ticks
                            into the padding (coord spans: the adaptive
                            K the round chose; 1 = single-round path)
    ``fused`` ``fuse_skip`` coord spans: this round used a fused
                            multi-round dispatch / why a K>1 backlog
                            did not (``warmup``/``votes``/``churn``)
    ``variant``             warmup spans: which program was warmed
    ``compile_ms``          warmup spans: compile wall time (NOT a
                            stall-watchdog field — warm compiles are
                            expected to be slow)
    ``acks`` ``votes``      staged event counts ingested by the dispatch
    ``recycles``            in-program membership recycles in the block
    ``reads`` ``echoes``    staged ReadIndex batches / heartbeat echoes
    ``upload_bytes``        host→device event-tensor bytes
    ``dispatch_ms``         host wall time staging + launching the
                            program, from the step's start: the sum of
                            ``row_sync_ms`` (dirty-row upload, row pulls,
                            committed-cache refresh: every
                            gather/scatter-rows program), ``stage_ms``
                            (event gathering, padding, the K-round block
                            build), ``transfer_ms`` (0.0: the ingress
                            block rides the launch), ``launch_ms`` (the
                            jitted call until it returns), and what is
                            left over:
                            time inside no phase, the round thread
                            waiting for the interpreter between two
    ``egress_ms``           blocking device→host egress wall time (set at
                            harvest; an in-flight span lacks it): the sum
                            of ``egress_wait_ms`` (the ``device_get``)
                            and ``decode_ms`` (egress translation)
    ``step_ms``             the whole ``step`` / ``step_rounds`` call that
                            opened the span (what a caller sees): its
                            phases, the time between them and the span
                            bookkeeping
    ``wait_ms``             coord spans: how long the round's oldest
                            staged op / tick waited for the round thread
    ``drain_ms``            coord spans: draining staged ops into the
                            engine (its row syncs and scalar read-echo
                            fallbacks included)
    ``fanout_ms``           coord spans: everything after the engine
                            returned (trace stamps, commit / read-confirm
                            / tick-flag offloads, election term reads)
    ``read_acks``           coord spans: heartbeat echoes the device
                            tallied this round; ``read_fallback_<cause>``
                            those tallied scalar-side, by cause
                            (``slot_overflow``/``after_confirm``/
                            ``purged``); ``reads_staged`` /
                            ``reads_refused`` contexts given / refused a
                            device slot
    ``egress_rows``         rows whose commit watermark advanced
    ``reads_released``      client reads released by confirmed slots
    ``mu_wait_ms``          time spent waiting on the engine's multi-
                            device dispatch lock (zero on single-device
                            and mesh-sharded engines)
    ``shard``               mesh shard index of the launching stream
                            (mesh-sharded engines only, ops/mesh.py)
    ``wall_ms``             whole-round wall time (coordinator spans)
    ``device_ms``           sampled post-launch ``block_until_ready``
                            delta (the devprof device-time estimator,
                            ISSUE 15; only on sampled dispatch spans —
                            deliberately NOT a stall-watchdog field,
                            the blocking sample is the measurement)
    ``stalled``             set by the watchdog: which field tripped
    ======================  ==================================================

    ``devprof`` spans mark on-demand ``jax.profiler`` capture windows
    (``window_ms``/``dir``, obs/devprof.py).
    """

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        stall_ms: Optional[float] = None,
        dump_path: Optional[str] = None,
    ):
        if capacity < 1:
            raise ValueError("recorder capacity must be >= 1")
        self.capacity = capacity
        self.stall_ms = (
            _default_stall_ms() if stall_ms is None else float(stall_ms)
        )
        self.dump_path = dump_path or os.environ.get("DBTPU_OBS_DUMP")
        self._buf: List[Optional[dict]] = [None] * capacity
        self._n = 0
        self._mu = threading.Lock()
        self.stalls = 0
        self.dumps = 0
        self.last_dump: Optional[dict] = None

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------

    def record(self, kind: str, **fields) -> dict:
        """Append a span; returns the (mutable) span dict so the producer
        can finalize it later (``update``)."""
        now = time.perf_counter()
        span = {"kind": kind, "ts": time.time(), "t0": now, "t1": now,
                "host": None, "parent": None}
        span.update(fields)
        with self._mu:
            span["seq"] = self._n
            self._buf[self._n % self.capacity] = span
            self._n += 1
        self._stall_check(span)
        return span

    def update(self, span: dict, **fields) -> None:
        """Finalize a span in place (egress fields land at harvest);
        ``t1`` follows unless the caller passes its own."""
        span["t1"] = time.perf_counter()
        span.update(fields)
        self._stall_check(span)

    def _stall_check(self, span: dict) -> None:
        th = self.stall_ms
        if th <= 0 or span.get("stalled"):
            return
        over = [
            k for k in _STALL_KEYS if float(span.get(k) or 0.0) >= th
        ]
        if over:
            span["stalled"] = "+".join(over)
            self.stalls += 1
            self.dump(
                reason=f"stall:{span['stalled']} >= {th:g}ms", trigger=span
            )

    # ------------------------------------------------------------------
    # introspection / dumping
    # ------------------------------------------------------------------

    def __len__(self) -> int:
        return min(self._n, self.capacity)

    def spans(self) -> List[dict]:
        """Recorded spans, oldest → newest."""
        with self._mu:  # two slices: every writer waits out this hold
            n = self._n
            if n <= self.capacity:
                return self._buf[:n]
            i = n % self.capacity
            return self._buf[i:] + self._buf[:i]

    def to_json(self, limit: Optional[int] = None) -> dict:
        """JSON-serializable snapshot (``limit`` keeps only the newest N
        spans — artifact writers cap the payload)."""
        spans = self.spans()
        if limit is not None and len(spans) > limit:
            spans = spans[-limit:]
        return {
            "capacity": self.capacity,
            "count": self._n,
            "stall_ms": self.stall_ms,
            "stalls": self.stalls,
            "spans": spans,
        }

    def dump(self, reason: str = "on-demand", trigger: Optional[dict] = None) -> dict:
        """Snapshot the ring (plus the triggering span) — kept on
        ``last_dump``, logged, and written to ``dump_path`` when set.
        Called automatically by the stall watchdog; callers (bench rung
        watchdog, operators) may invoke it on demand."""
        d = {"reason": reason, "time": time.time(), "trigger": trigger}
        d.update(self.to_json())
        self.last_dump = d
        self.dumps += 1
        path = self.dump_path
        if path:
            try:
                with open(path, "w") as f:
                    json.dump(d, f, indent=1, default=str)
            except OSError as e:
                plog.warning("flight recorder dump to %s failed: %r", path, e)
        plog.warning(
            "flight recorder dump (%s): %d spans, trigger=%s%s",
            reason,
            len(d["spans"]),
            (trigger or {}).get("kind"),
            f" -> {path}" if path else "",
        )
        return d
