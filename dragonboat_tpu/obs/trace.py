"""Cross-plane request tracing: follow one proposal (or read) through
every host-plane stage and device round (ISSUE 9 tentpole).

The host plane is a multi-stage pipeline (ingress rings → batcher →
raft step → group-commit WAL → device dispatch → apply pool → egress
sink), but per-stage aggregates cannot say which stage owns a given
request's tail latency.  This module adds the missing connective
tissue: a lightweight trace context allocated at ``propose`` /
``read`` time for a sampled 1-in-N of requests and carried through the
``RequestState`` future; each pipeline stage stamps the context as the
request passes, and the coordinator links the FlightRecorder span seq
of the device round that carried its commit.  The result is

- per-stage latency histograms ``dragonboat_trace_stage_seconds{stage}``
  (stage = time from the previous stamp to this one) plus an always-on
  end-to-end histogram ``dragonboat_trace_e2e_seconds`` fed by every
  request (non-sampled requests carry only a single monotonic enqueue
  timestamp — no allocation, no registration);
- an exportable Chrome-trace / Perfetto JSON (``NodeHost.dump_trace``)
  where one request renders as ONE flow across host threads and device
  rounds (flow events bind the stage slices; linked recorder spans are
  emitted on a ``device-plane`` track);
- a stage-level stall watchdog: a sampled request stuck longer than
  ``stall_ms`` in any one stage auto-dumps its partial trace PLUS the
  flight-recorder ring (the cross-plane twin of the recorder's own
  span watchdog).

Stage vocabulary (a request only carries the stages its path visits):

==============  =========================================================
stage           stamped when
==============  =========================================================
``propose``     the trace is allocated (t0; the enqueue timestamp)
``ipc``         the shared-memory handoff to the hostproc encode worker
                completed (ring enqueue → worker dequeue → encoded burst
                returned) — workers-on path only (ISSUE 12), so the
                latency attribution table can price the process handoff
``ingress``     the entry is staged for raft — after ``entry_q.add`` /
                the native fast-lane append on the direct path, after
                the batcher drain on the compartmentalized path (so the
                ring wait + drain time is the ingress stage)
``raft_step``   raft ingested the entry (``peer.propose_entries``); for
                reads: the ReadIndex ctx was submitted
``wal``         the update carrying the entry is fsynced (committer /
                group-commit WAL release)
``device_round``the coordinator round whose dispatch released the
                group's commit (tpu engine only; replace-style — the
                LAST such round before apply wins — and the recorder
                seqs of the dispatch span and of its ``coord_round``
                span are linked into ``Trace.spans``; the round span's
                ``t0``/``t1`` are on this module's clock and hold the
                stamp)
``read_confirm``the ReadIndex ctx was quorum-confirmed (reads only): the
                requester filed its ``ready_to_read``.  Between this
                stamp and ``raft_step`` lies the leader's half of the
                read, which the leader's coordinator writes as one
                ``read_ctx`` span (``instruments.CoordObs.read_ctx``)
                named by this trace's ``(tracer.host, tid)``
``lease_read``  the read was served locally under a valid leader lease
                (ISSUE 10) — replaces ``read_confirm``; no confirmation
                round ran, so the trace shows the short path
``apply``       the user SM applied the entry / the read's apply
                watermark was reached
``egress``      the client future was notified (trace completes)
==============  =========================================================

Overhead contract (the PR-5 ``is not None`` latch precedent): tracing
is OFF by default — ``NodeHost.tracer`` / ``Node.tracer`` /
``Engine.tracer`` / coordinator ``tracer`` stay ``None``,
``RequestState.trace`` stays ``None``, and every hot-path hook gates on
a plain attribute check, so the trace-off host path is bit-identical
(``tests/test_trace.py``).
"""
from __future__ import annotations

import bisect
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ..events import DEFAULT_REGISTRY, MetricsRegistry
from ..logger import get_logger

plog = get_logger("trace")

_T = "dragonboat_trace_"

#: seconds-scale stage/e2e histogram buckets: sub-ms direct-path stages
#: at the bottom, a wedged WAL or dispatch stall at the top
STAGE_BUCKETS_S = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0,
)

#: newest-enabled tracer — introspection only (``active()``); every
#: request token carries its owning tracer, so completions never route
#: through this global.
_ACTIVE: Optional["Tracer"] = None
#: every tracer constructed and not yet closed, oldest first
#: (``live()``): co-hosted NodeHosts each own one
_LIVE: List["Tracer"] = []

#: request kinds as ``outcomes()`` names them
_OUTCOME_KIND = {"write": "propose", "read": "read"}
#: non-``COMPLETED`` completions remembered for ``outcomes()["events"]``
OUTCOME_EVENTS_KEEP = 65536
#: whole seconds of ``outcomes()["by_second"]`` kept (oldest dropped)
OUTCOME_SECONDS_KEEP = 900
#: finished traces ``traces()`` returns (the newest: dumps, stage stats)
DEFAULT_KEEP = 256
#: finished traces a tracer remembers in all (``finished()``): a 48 s
#: window of the busiest cell samples ~6,000 requests a host (3,000 ops/s
#: over three hosts, 1 in 8); a finished trace is ~1.6 KB: ~13 MB when full
DEFAULT_HISTORY = 8192


def _default_stall_ms() -> float:
    try:
        return float(os.environ.get("DBTPU_TRACE_STALL_MS", "1000"))
    except ValueError:
        plog.warning("malformed DBTPU_TRACE_STALL_MS; using 1000")
        return 1000.0


class Trace:
    """One sampled request's context: an append-only list of
    ``[stage, perf_counter_t, thread_name]`` stamps plus the recorder
    span seqs linked along the way.  Mutation is GIL-atomic appends from
    the pipeline threads; the tracer's lock guards only the in-flight
    index, never the stamp path."""

    __slots__ = (
        "tracer", "tid", "kind", "cluster_id", "key", "t0",
        "events", "spans", "outcome", "stalled", "done",
        "applied", "_round_ev", "repl", "read_ctx", "read_origin", "woke",
    )

    def __init__(self, tracer: "Tracer", tid: int, kind: str,
                 cluster_id: int, key: int, t0: float):
        self.tracer = tracer
        self.tid = tid
        self.kind = kind
        self.cluster_id = cluster_id
        self.key = key
        self.t0 = t0
        self.events: List[list] = [["propose", t0, _tname()]]
        self.spans: List[int] = []
        self.outcome: Optional[str] = None
        self.stalled: Optional[str] = None
        self.done = False
        self.applied = False       # an "apply" stamp landed
        self._round_ev = None      # cached device_round event (replace)
        # per-commit quorum attribution summary (obs/replattr.py,
        # ISSUE 14): set by the leader's ReplAttr when the commit
        # covering this proposal closes — None until then / off-plane
        self.repl: Optional[dict] = None
        # reads only (ISSUE 39): the ReadIndex context ``(low, high)``
        # whose batch covers this request, and whether the replica that
        # formed it led the group (``local``) or forwarded the context
        # to the leader (``forwarded``); the key a leader's ``read_ctx``
        # span is joined by
        self.read_ctx: Optional[tuple] = None
        self.read_origin: Optional[str] = None
        # a request that found its group asleep (ISSUE 44): ``(instant,
        # role)`` of the replica it woke, the first its step reached;
        # at its end the tracer's ``wake_sink`` writes a ``quiesce_wake``
        # span from it
        self.woke: Optional[tuple] = None

    def add(self, stage: str) -> None:
        self.events.append([stage, time.perf_counter(), _tname()])
        if stage == "apply":
            self.applied = True

    def add_round(self, span_seq: Optional[int], now: float,
                  thread: str, round_seq: Optional[int] = None) -> None:
        """Replace-style ``device_round`` stamp: a request can sit through
        several coordinator rounds while waiting for apply — the LAST
        round before apply is the one whose dispatch released its commit,
        so later stamps overwrite earlier ones (every linked span seq is
        kept in ``spans`` for the flow export).  Runs once per in-flight
        trace per commit round — the caller hoists the timestamp/thread
        lookup so this is flag checks plus two list stores (a
        per-trace ``perf_counter`` here measured ~10% off the tpu e2e
        loop on the 1-vCPU box)."""
        if self.applied or self.done:
            # already applied: a later round touching this group can no
            # longer be the one that released this request
            return
        spans = self.spans
        if span_seq is not None and span_seq not in spans[-2:]:
            spans.append(span_seq)
        if round_seq is not None and round_seq not in spans[-2:]:
            spans.append(round_seq)
        ev = self._round_ev
        if ev is not None:
            ev[1] = now
            ev[2] = thread
        else:
            self._round_ev = ev = ["device_round", now, thread]
            self.events.append(ev)

    def to_dict(self) -> dict:
        """JSON-ready snapshot (stall dumps, SIGUSR2 debug dumps)."""
        t0 = self.t0
        return {
            "trace_id": self.tid,
            "kind": self.kind,
            "cluster_id": self.cluster_id,
            "key": self.key,
            "outcome": self.outcome,
            "stalled": self.stalled,
            "done": self.done,
            "spans": list(self.spans),
            "repl": self.repl,
            "read_ctx": self.read_ctx,
            "read_origin": self.read_origin,
            "events": [
                {
                    "stage": s,
                    "t_ms": round((t - t0) * 1e3, 4),
                    "thread": th,
                }
                for s, t, th in sorted(self.events, key=lambda e: e[1])
            ],
        }


def _tname() -> str:
    return threading.current_thread().name


class Tracer:
    """Sampling allocator + in-flight index + stage histogram publisher.

    ``sample_every=N`` traces 1 request in N (N=1 traces everything —
    tests and targeted debugging).  Hot-path cost for the other N-1:
    one float timestamp on the future and one e2e histogram observation
    at completion.  The in-flight index is keyed two ways: by entry key
    (``mark_entries``/``mark_updates`` — the raft-step and WAL hooks see
    entries, not futures) and by cluster id (``mark_clusters`` — the
    coordinator round fan-out sees groups)."""

    def __init__(
        self,
        sample_every: int = 64,
        registry: Optional[MetricsRegistry] = None,
        recorder=None,
        stall_ms: Optional[float] = None,
        keep: int = DEFAULT_KEEP,
    ):
        if sample_every < 1:
            raise ValueError("sample_every must be >= 1")
        self.sample_every = int(sample_every)
        self.registry = registry or DEFAULT_REGISTRY
        self.recorder = recorder  # FlightRecorder or None
        self.stall_ms = (
            _default_stall_ms() if stall_ms is None else float(stall_ms)
        )
        self.dump_path = os.environ.get("DBTPU_TRACE_DUMP")
        # Who takes which lock (ISSUE 39).  ``_mu`` guards the in-flight
        # index and the folded accumulators: a SAMPLED request's attach
        # and finish, the round thread's ``mark_clusters``, the tick
        # worker's flush.  The submitting thread's sampling counter has
        # ``_n_mu`` to itself, and the per-message hooks (``observe_done``,
        # ``add_repl_leg``, the accounting half of ``finish``) take no
        # lock at all: they append to a deque, which ``_fold_locked``
        # drains.  With one unfair lock for all of it, ten committers and
        # apply workers a host took it per message and the one submitting
        # thread could not win it: every attempt at that host's groups
        # timed out while its peers ran.
        self._mu = threading.Lock()
        self._n_mu = threading.Lock()
        self._n = 0          # requests seen (sampling counter)
        self._tid = 0        # trace ids
        self._by_key: Dict[int, Trace] = {}
        self._by_cluster: Dict[int, set] = {}
        self._done: deque = deque(maxlen=max(1, keep))
        # the same traces, a window's worth of them: a reader that cuts
        # by a window's instants takes ``finished()``; ``traces()`` stays
        # the newest ``keep`` (its readers take all they are given, and
        # the thousands of writes a benchmark's prefill makes would be
        # most of a longer list)
        self._history: deque = deque(maxlen=max(keep, DEFAULT_HISTORY))
        self.sampled = 0
        self.completed = 0
        self.discarded = 0  # contexts whose submission was rejected
        self.stall_dumps = 0
        self.last_stall_dump: Optional[dict] = None
        # ---- replication tracing (ISSUE 14) --------------------------
        # this host's raft address (dump/merge identity) and the
        # leader-side attribution plane — both wired by NodeHost
        self.host = ""
        self.replattr = None
        # where a finished request that woke its group is written
        # (``CoordObs.quiesce_wake``; wired by NodeHost on the device
        # quorum engine, None otherwise)
        self.wake_sink = None
        # follower-leg records: a sampled REPLICATE from ANOTHER host's
        # leader stamped its stages here; the ack-send hook files the
        # completed leg so dump_trace renders the follower half of the
        # flow and tools/trace_merge.py can join it to the leader's
        self._repl_legs: deque = deque(maxlen=max(16, keep))
        # completions not yet accounted: ``(now, t0, kind, CODE, events)``
        # appended by whichever thread notified the future (``events`` the
        # time-sorted stamps of a sampled trace, None otherwise), folded
        # into the accumulators below under ``_mu``
        self._fin_q: deque = deque()
        # ---- local metric accumulators (hot-path cost control) -------
        # The propose/notify paths run at full request rate; a registry
        # histogram observe per completion (lock + label-key build)
        # measured ~20% off on the 1-vCPU e2e loop.  Observations are
        # queued lock-free (``_fin_q``), folded into these plain lists and
        # flushed to the registry in ONE merge per tick (check_stalls) or
        # when the last in-flight trace completes — exposition lag <= one
        # RTT.
        self._bk = STAGE_BUCKETS_S
        nb = len(self._bk) + 1
        self._e2e_acc = [[0] * nb, 0.0, 0]        # counts, sum, n
        self._stage_acc: Dict[str, list] = {}     # stage -> same shape
        self._pend_requests = 0
        self._pend_sampled = 0
        self._pend_completed = 0
        # ---- attempt outcomes by result code (ISSUE 26) --------------
        # every completed request, sampled or not: (kind, CODE) -> count
        # since construction, the part not yet flushed to the registry,
        # and the non-COMPLETED ones as (perf_counter, kind, CODE) so a
        # reader can take a window's (bounded; guarded by _mu)
        self._outcomes: Dict[tuple, int] = {}
        self._pend_outcomes: Dict[tuple, int] = {}
        self._outcome_events: deque = deque(maxlen=OUTCOME_EVENTS_KEEP)
        self._outcome_secs: Dict[int, Dict[tuple, int]] = {}
        # ---- apply hand-offs (ISSUE 42) -------------------------------
        # updates whose committed entries went to the apply queue before
        # their persist / after it, queued by the engine's step workers
        # and committers as (perf_counter, early, after) and folded by
        # whole second like the outcomes
        self._handoff_q: deque = deque()
        self._handoff_secs: Dict[int, List[int]] = {}
        # ---- committer cycles (ISSUE 45) ------------------------------
        # one (perf_counter, 1, sync batches, updates, save seconds) a
        # cycle of any committer of the engine, folded the same way
        self._wal_q: deque = deque()
        self._wal_secs: Dict[int, list] = {}
        # clock anchor: stamps are perf_counter (monotonic); the export
        # maps them onto the wall clock the recorder spans already use
        self._wall0 = time.time()
        self._pc0 = time.perf_counter()
        r = self.registry
        r.describe(
            _T + "requests_total",
            "requests that entered the traced pipeline (sampled or not)",
        )
        r.describe(
            _T + "requests_done_total",
            "requests completed while tracing is on (sampled or not), by "
            "kind (propose/read) and result code",
        )
        r.describe(_T + "sampled_total", "requests allocated a full trace")
        r.describe(_T + "completed_total", "sampled traces completed")
        r.describe(
            _T + "stalls_total",
            "sampled requests stuck >stall_ms in one stage (auto-dumped)",
        )
        r.describe(_T + "inflight", "sampled traces currently in flight")
        r.describe(
            _T + "stage_seconds",
            "per-stage latency of sampled requests (time from the "
            "previous pipeline stamp to this stage's stamp)",
        )
        r.describe(
            _T + "e2e_seconds",
            "end-to-end request latency (enqueue to future notify), "
            "observed for EVERY request while tracing is on",
        )
        r.counter_add(_T + "requests_total", 0)
        r.counter_add(_T + "sampled_total", 0)
        r.counter_add(_T + "completed_total", 0)
        r.counter_add(_T + "stalls_total", 0)
        r.gauge_set(_T + "inflight", 0)
        r.histogram_declare(_T + "e2e_seconds", buckets=STAGE_BUCKETS_S)
        global _ACTIVE
        _ACTIVE = self
        _LIVE.append(self)

    # ------------------------------------------------------------------
    # allocation (propose / read time)
    # ------------------------------------------------------------------

    def attach_all(self, states, cluster_id: int, t0: float,
                   kind: str = "write") -> None:
        """Allocate contexts for a burst of freshly created futures:
        1-in-N gets a :class:`Trace` (registered by key + cluster), the
        rest share one ``(tracer, t0)`` token (the always-on enqueue
        timestamp feeding the e2e histogram at notify).  The common
        no-sample-in-this-burst case touches the counter's own lock,
        which only submitting threads take, and one attribute store per
        future — nothing else."""
        n = self.sample_every
        nstates = len(states)
        tok = (self, t0, kind)  # ONE shared token per burst: non-sampled
        # futures carry (tracer, t0, kind) so completion observes e2e
        # and its result code into the tracer that owns them (a
        # module-global sink misattributed multi-NodeHost processes), at
        # zero per-request allocation
        with self._n_mu:
            base = self._n
            self._n = base + nstates
            self._pend_requests += nstates
            first = (-base) % n  # index of the first sampled slot
            if first < nstates:
                tid = self._tid
                self._tid = tid + (nstates - first + n - 1) // n
        if first >= nstates:
            for rs in states:
                rs.trace = tok
            return
        sampled = []
        for i, rs in enumerate(states):
            if (i - first) % n == 0:
                tid += 1
                rs.trace = Trace(self, tid, kind, cluster_id, rs.key, t0)
                sampled.append(rs)
            else:
                rs.trace = tok
        with self._mu:
            for rs in sampled:
                tr = rs.trace
                if tr.done:
                    continue  # notified since the store above: finished
                if rs.key:
                    self._by_key[rs.key] = tr
                self._by_cluster.setdefault(cluster_id, set()).add(tr)
            self.sampled += len(sampled)
            self._pend_sampled += len(sampled)
        # a future that completed before its context landed (the pipeline
        # can beat the attach on a hot box) must not leak in flight
        for rs in sampled:
            if rs.done():
                self.finish(rs.trace, rs.trace.outcome or "completed")

    def attach_one(self, rs, cluster_id: int, t0: float,
                   kind: str = "write") -> None:
        self.attach_all((rs,), cluster_id, t0, kind=kind)

    def discard(self, states) -> None:
        """Unregister contexts whose submission failed BEFORE the future
        could ever be notified (e.g. the ingress ring-cap SystemBusy
        raise happens after attach but before the futures reach any
        tracker — no notify will ever finish these, so they must not
        linger in flight for the stall watchdog to chase)."""
        with self._mu:
            for rs in states:
                t = rs.trace
                if t.__class__ is not Trace or t.done:
                    continue
                t.done = True
                t.outcome = "unsubmitted"
                if t.key:
                    self._by_key.pop(t.key, None)
                s = self._by_cluster.get(t.cluster_id)
                if s is not None:
                    s.discard(t)
                    if not s:
                        del self._by_cluster[t.cluster_id]
                # sampled_total is NOT decremented: the sample did
                # happen, and a tick flush may already have published it
                # — a negative delta would read as a Prometheus counter
                # reset.  sampled - completed - inflight = discarded.
                self.discarded += 1

    # ------------------------------------------------------------------
    # stage stamps (pipeline hooks)
    # ------------------------------------------------------------------

    @staticmethod
    def mark(rs, stage: str) -> None:
        """Stamp a stage on a future's trace (no-op for the non-sampled
        token, and for a COMPLETED trace — a burst's dropped tail
        finishes before the caller's post-staging mark loop runs, and a
        post-egress stamp would corrupt the time-sorted export).
        Callers gate on ``rs.trace is not None`` first."""
        t = rs.trace
        if t.__class__ is Trace and not t.done:
            t.add(stage)

    def mark_entries(self, entries, stage: str) -> None:
        """Stamp by entry key (raft-step hook: the staged entries are in
        hand, the futures are not)."""
        bk = self._by_key
        if not bk:
            return
        for e in entries:
            t = bk.get(e.key)
            if t is not None and not t.done:
                t.add(stage)

    def mark_woke(self, entries, role: str) -> None:
        """The step that took ``entries`` woke its sleeping replica
        (``role``): noted on their sampled requests (``Trace.woke``)."""
        bk = self._by_key
        if not bk:
            return
        now = time.perf_counter()
        for e in entries:
            t = bk.get(e.key)
            if t is not None and not t.done and t.woke is None:
                t.woke = (now, role)

    def mark_updates(self, updates, stage: str) -> None:
        """Stamp every sampled entry carried by a persisted update batch
        (WAL hook, after the fsync)."""
        bk = self._by_key
        if not bk:
            return
        for ud in updates:
            for e in ud.entries_to_save:
                t = bk.get(e.key)
                if t is not None and not t.done:
                    t.add(stage)

    def mark_clusters(self, cids, span_seq: Optional[int] = None,
                      round_seq: Optional[int] = None) -> None:
        """The coordinator round released commits/read-confirms for these
        groups: stamp ``device_round`` (replace-style) on every in-flight
        trace of those groups and link the dispatch span seq and the
        round's own ``coord_round`` span seq (whose interval holds the
        stamp)."""
        if not self._by_cluster:
            return
        now = time.perf_counter()
        thread = _tname()
        with self._mu:
            # stamp UNDER the lock: every set mutator (attach/finish/
            # discard) holds _mu too, so direct iteration is safe and
            # skips a per-round snapshot list — this runs on the
            # coordinator round thread, the tpu path's bottleneck, so
            # per-round allocations here are throughput (one lock per
            # ROUND, add_round is flag checks + two list stores)
            bc = self._by_cluster
            get = bc.get
            for cid in cids:
                for t in get(cid, ()):
                    t.add_round(span_seq, now, thread, round_seq)

    # ------------------------------------------------------------------
    # replication legs (ISSUE 14, follower side)
    # ------------------------------------------------------------------

    def add_repl_leg(self, ctx) -> None:
        """File one completed follower leg of a sampled replication (the
        inbound REPLICATE's :class:`~dragonboat_tpu.wire.ReplTrace`
        stamps, recorded when the ack leaves this host).  The leg
        renders as ``follower_append`` / ``follower_fsync`` /
        ``ack_send`` slices in this host's Perfetto dump, carrying the
        LEADER's trace id + origin so ``tools/trace_merge.py`` can bind
        it into the leader's flow."""
        # a bounded deque's append is atomic: the committers take no lock
        self._repl_legs.append({
            "tid": ctx.tid,
            "origin": ctx.origin,
            "index": ctx.index,
            "t_recv": ctx.t_recv,
            "t_append": ctx.t_append,
            "t_fsync": ctx.t_fsync,
            "t_ack": ctx.t_ack,
        })

    def repl_legs(self) -> List[dict]:
        return list(self._repl_legs)

    # ------------------------------------------------------------------
    # completion
    # ------------------------------------------------------------------

    def _acc(self, acc: list, seconds: float) -> None:
        """Accumulate one observation into a local [counts, sum, n]
        triple; caller holds ``_mu``."""
        acc[0][bisect.bisect_left(self._bk, seconds)] += 1
        acc[1] += seconds
        acc[2] += 1

    def _count_outcome(self, kind: str, code: str, now: float) -> None:
        """One completed request of ``kind`` with result ``code``; caller
        holds ``_mu``."""
        key = (_OUTCOME_KIND.get(kind, kind), code)
        self._outcomes[key] = self._outcomes.get(key, 0) + 1
        self._pend_outcomes[key] = self._pend_outcomes.get(key, 0) + 1
        secs = self._outcome_secs
        sec = secs.get(int(now))
        if sec is None:
            sec = secs[int(now)] = {}
            if len(secs) > OUTCOME_SECONDS_KEEP:
                del secs[min(secs)]
        sec[key] = sec.get(key, 0) + 1
        if code != "COMPLETED":
            self._outcome_events.append((now, key[0], code))

    def observe_done(self, t0: float, kind: str, code: str) -> None:
        """A non-sampled request completed: its e2e latency and its
        ``(kind, code)`` count, queued for the next fold.  No lock: this
        runs on every apply worker, once a request."""
        self._fin_q.append((time.perf_counter(), t0, kind, code, None))

    def count_apply_handoffs(self, early: int, after: int) -> None:
        """``early`` / ``after`` updates handed their committed entries to
        the apply queue before / after their persist
        (``Engine.count_apply_handoffs``).  No lock: queued for the next
        fold."""
        self._handoff_q.append((time.perf_counter(), early, after))

    def apply_handoffs(self) -> Dict[int, tuple]:
        """``{int(perf_counter): (early, after_sync)}``: commit-carrying
        updates by the whole second in which their committed entries went
        to the apply queue, before their persist against after it (the
        newest ``OUTCOME_SECONDS_KEEP`` seconds), so a reader can take a
        window's."""
        with self._mu:
            self._fold_locked()
            return {s: tuple(c) for s, c in self._handoff_secs.items()}

    def count_wal_cycle(
        self, sync_batches: int, updates: int, commit_s: float
    ) -> None:
        """One committer cycle (``engine._Committer._commit``): the durable
        write batches its log save committed, the updates it merged and the
        seconds the save took.  No lock: queued for the next fold."""
        self._wal_q.append(
            (time.perf_counter(), 1, sync_batches, updates, commit_s)
        )

    def wal_cycles(self) -> Dict[int, tuple]:
        """``{int(perf_counter): (cycles, sync_batches, updates,
        commit_s)}``: the engine's committer cycles by the whole second in
        which their save returned (the newest ``OUTCOME_SECONDS_KEEP``
        seconds).  ``sync_batches / cycles`` is 1 where every cycle is one
        shard, one durable write."""
        with self._mu:
            self._fold_locked()
            return {s: tuple(c) for s, c in self._wal_secs.items()}

    @staticmethod
    def _fold_seconds(q: deque, secs: Dict[int, list]) -> None:
        """Add each queued ``(perf_counter, *counts)`` to its whole
        second's sums."""
        for _ in range(len(q)):
            now, *counts = q.popleft()
            sec = secs.get(int(now))
            if sec is None:
                secs[int(now)] = list(counts)
                if len(secs) > OUTCOME_SECONDS_KEEP:
                    del secs[min(secs)]
            else:
                for i, c in enumerate(counts):
                    sec[i] += c

    def _fold_locked(self) -> None:
        """Account the completions queued since the last fold (caller
        holds ``_mu``): outcome counts, e2e and, for a sampled trace, its
        stage observations; and the apply hand-offs and committer
        cycles."""
        self._fold_seconds(self._handoff_q, self._handoff_secs)
        self._fold_seconds(self._wal_q, self._wal_secs)
        q = self._fin_q
        for _ in range(len(q)):
            now, t0, kind, code, evs = q.popleft()
            self._count_outcome(kind, code, now)
            self._acc(self._e2e_acc, max(0.0, now - t0))
            if evs is None:
                continue
            prev = evs[0][1]
            for stage, t, _th in evs[1:]:
                acc = self._stage_acc.get(stage)
                if acc is None:
                    acc = self._stage_acc[stage] = [
                        [0] * (len(self._bk) + 1), 0.0, 0,
                    ]
                self._acc(acc, max(0.0, t - prev))
                prev = t
            self._pend_completed += 1

    def outcomes(self) -> dict:
        """Attempt outcomes by result code since construction:
        ``{"counts": {(kind, CODE): n}, "events": [(perf_counter, kind,
        CODE), ...], "by_second": {int(perf_counter): {(kind, CODE):
        n}}}`` with kind ``propose`` / ``read``, CODE the
        ``RequestResultCode`` name, ``events`` the non-``COMPLETED``
        completions (bounded, oldest first) and ``by_second`` every
        completion by the whole second it fell in (the newest
        ``OUTCOME_SECONDS_KEEP``), so a reader can take a window's.
        Counts every request notified while tracing is on, sampled or
        not."""
        with self._mu:
            self._fold_locked()
            return {
                "counts": dict(self._outcomes),
                "events": list(self._outcome_events),
                "by_second": {
                    s: dict(c) for s, c in self._outcome_secs.items()
                },
            }

    def finish(self, trace: Trace, outcome: str) -> None:
        """Trace completes (future notified): final ``egress`` stamp,
        move to the completed ring; its stage + e2e observations are
        queued like any other completion's (folded and flushed to the
        registry on the tick cadence)."""
        with self._mu:
            # atomic claim: attach_all's already-done cleanup and the
            # notify thread's request_done can race here — exactly one
            # may run the completion half
            if trace.done:
                return
            trace.done = True
            if trace.key:
                self._by_key.pop(trace.key, None)
            s = self._by_cluster.get(trace.cluster_id)
            if s is not None:
                s.discard(trace)
                if not s:
                    del self._by_cluster[trace.cluster_id]
            self.completed += 1
            idle = not self._by_cluster
        trace.outcome = outcome
        trace.add("egress")
        evs = sorted(trace.events, key=lambda e: e[1])
        self._done.append(trace)
        self._history.append(trace)
        self._fin_q.append(
            (evs[-1][1], trace.t0, trace.kind, outcome.upper(), evs)
        )
        if trace.woke is not None and self.wake_sink is not None:
            self.wake_sink(trace, evs)
        if idle:
            # the last in-flight trace just completed: flush now so a
            # quiet scrape (or a test right after the load) sees it —
            # under sustained load the tick-worker flush covers instead
            self.flush_metrics()

    def flush_metrics(self) -> None:
        """Publish the locally accumulated observations to the registry
        in one pass (called by the NodeHost tick worker via
        :meth:`check_stalls`, on going idle, and at :meth:`close`)."""
        with self._n_mu:
            reqs, self._pend_requests = self._pend_requests, 0
        with self._mu:
            self._fold_locked()
            e2e, self._e2e_acc = self._e2e_acc, [
                [0] * (len(self._bk) + 1), 0.0, 0,
            ]
            stages, self._stage_acc = self._stage_acc, {}
            samp, self._pend_sampled = self._pend_sampled, 0
            comp, self._pend_completed = self._pend_completed, 0
            outs, self._pend_outcomes = self._pend_outcomes, {}
            inflight = sum(len(v) for v in self._by_cluster.values())
        reg = self.registry
        for (kind, code), n in outs.items():
            reg.counter_add(
                _T + "requests_done_total", n,
                {"kind": kind, "code": code},
            )
        if reqs:
            reg.counter_add(_T + "requests_total", reqs)
        if samp:
            reg.counter_add(_T + "sampled_total", samp)
        if comp:
            reg.counter_add(_T + "completed_total", comp)
        if samp or comp:
            reg.gauge_set(_T + "inflight", inflight)
        if e2e[2]:
            reg.histogram_merge(
                _T + "e2e_seconds", e2e[0], e2e[1], e2e[2],
                buckets=self._bk,
            )
        for stage, acc in stages.items():
            reg.histogram_merge(
                _T + "stage_seconds", acc[0], acc[1], acc[2],
                labels={"stage": stage}, buckets=self._bk,
            )

    def close(self) -> None:
        """Flush and detach from the module-level e2e sink
        (NodeHost.stop)."""
        global _ACTIVE
        if _ACTIVE is self:
            _ACTIVE = None
        try:
            _LIVE.remove(self)
        except ValueError:
            pass
        self.flush_metrics()

    # ------------------------------------------------------------------
    # stall watchdog (the host-stage extension of the recorder's)
    # ------------------------------------------------------------------

    def check_stalls(self) -> int:
        """Scan in-flight traces for one stuck longer than ``stall_ms``
        since its last stamp; each trips at most once and auto-dumps its
        partial trace plus the recorder ring.  Driven by the NodeHost
        tick worker (and callable on demand); returns newly stalled
        count.  Doubles as the metric-flush cadence.  The fast path —
        nothing sampled in flight, nothing pending — is a few
        truthiness checks."""
        if (
            self._pend_requests or self._fin_q or self._pend_completed
            or self._e2e_acc[2] or self._pend_outcomes or self._handoff_q
            or self._wal_q
        ):
            self.flush_metrics()
        if not self._by_cluster and not self._by_key:
            return 0
        th = self.stall_ms
        if th <= 0:
            return 0
        now = time.perf_counter()
        with self._mu:
            traces = {t for s in self._by_cluster.values() for t in s}
            traces.update(self._by_key.values())
        newly: List[Trace] = []
        for t in traces:
            if t.done or t.stalled:
                continue
            evs = t.events
            if not evs:
                continue
            last_stage, last_t, _ = max(evs, key=lambda e: e[1])
            if (now - last_t) * 1e3 >= th:
                t.stalled = last_stage
                newly.append(t)
        if newly:
            self.registry.counter_add(_T + "stalls_total", len(newly))
            # ONE aggregate dump per pass: a systemic stall trips many
            # sampled traces at once, and per-trace dumps would
            # serialize the recorder ring N times inline on the tick
            # worker — the thread driving raft timers — exactly when
            # the system is already degraded
            self._stall_dump(newly, now)
        return len(newly)

    def _stall_dump(self, stalled: List[Trace], now: float) -> None:
        head = stalled[0]
        last_t = max(e[1] for e in head.events)
        d = {
            "reason": (
                f"trace-stall: {len(stalled)} sampled request(s) stuck "
                f">= {self.stall_ms:g}ms in one stage (first: {head.kind} "
                f"trace {head.tid}, {(now - last_t) * 1e3:.0f}ms after "
                f"stage {head.stalled!r})"
            ),
            "time": time.time(),
            "trace": head.to_dict(),  # the first/triggering trace
            "traces": [t.to_dict() for t in stalled],
            "recorder": (
                self.recorder.to_json() if self.recorder is not None
                else None
            ),
        }
        self.last_stall_dump = d
        self.stall_dumps += 1
        path = self.dump_path
        if path:
            try:
                with open(path, "w") as f:
                    json.dump(d, f, indent=1, default=str)
            except OSError as e:
                plog.warning("trace stall dump to %s failed: %r", path, e)
        plog.warning("%s%s", d["reason"], f" -> {path}" if path else "")

    # ------------------------------------------------------------------
    # introspection / export
    # ------------------------------------------------------------------

    def reset_completed(self, keep: Optional[int] = None) -> None:
        """Clear the completed-trace ring (optionally resizing it) —
        bench phases scope an attribution measurement to one window this
        way; steady state keeps the bounded default."""
        with self._mu:
            self._done = deque(maxlen=max(1, keep or self._done.maxlen))
            self._history = deque(maxlen=self._history.maxlen)

    def inflight(self) -> List[Trace]:
        with self._mu:
            s = {t for v in self._by_cluster.values() for t in v}
            s.update(self._by_key.values())
            return sorted(s, key=lambda t: t.tid)

    def traces(self) -> List[Trace]:
        """Completed (oldest→newest) then in-flight traces."""
        with self._mu:  # reset_completed swaps the ring under it
            done = list(self._done)
        seen = set(map(id, done))
        return done + [t for t in self.inflight() if id(t) not in seen]

    def finished(self) -> List[Trace]:
        """Every finished trace still remembered, oldest first
        (``DEFAULT_HISTORY``: a traced window's worth)."""
        return list(self._history)

    def to_json(self) -> dict:
        return {
            "sample_every": self.sample_every,
            "requests": self._n,
            "sampled": self.sampled,
            "completed": self.completed,
            "discarded": self.discarded,
            "stall_dumps": self.stall_dumps,
            "inflight": [t.to_dict() for t in self.inflight()],
            "traces": [t.to_dict() for t in self.traces() if t.done],
        }

    def stage_stats(self) -> dict:
        """Per-stage p50/p99 (ms) + share-of-e2e over the completed ring
        — the data behind the perf ledger's latency-attribution table."""
        with self._mu:
            done = list(self._done)
        return compute_stage_stats(done)

    def _wall_us(self, t_perf: float) -> float:
        return (self._wall0 + (t_perf - self._pc0)) * 1e6

    def export_chrome(self, include_recorder: bool = True,
                      limit: Optional[int] = None) -> dict:
        """Chrome-trace / Perfetto JSON: each sampled request is a chain
        of ``X`` slices (one per stage, on the thread that stamped it)
        bound into ONE flow by ``s``/``t``/``f`` events with
        ``id=trace_id``; linked recorder spans render on a
        ``device-plane`` track next to them.  Load in Perfetto / about:
        //tracing, or ship to teammates as-is."""
        events: List[dict] = []
        tids: Dict[str, int] = {}

        def tid_of(name: str) -> int:
            tid = tids.get(name)
            if tid is None:
                tid = tids[name] = len(tids) + 1
                events.append({
                    "ph": "M", "name": "thread_name", "pid": 1,
                    "tid": tid, "args": {"name": name},
                })
            return tid

        traces = self.traces()
        if limit is not None:
            traces = traces[-limit:]
        for t in traces:
            evs = sorted(t.events, key=lambda e: e[1])
            if len(evs) < 2:
                continue
            flow = []
            prev_t = evs[0][1]
            for stage, ts, thread in evs[1:]:
                tid = tid_of(thread)
                ev = {
                    "name": stage,
                    "cat": t.kind,
                    "ph": "X",
                    "pid": 1,
                    "tid": tid,
                    "ts": round(self._wall_us(prev_t), 1),
                    "dur": round(max(0.0, ts - prev_t) * 1e6, 1),
                    "args": {
                        "trace_id": t.tid,
                        "cluster_id": t.cluster_id,
                        "outcome": t.outcome,
                    },
                }
                if stage == "device_round" and t.spans:
                    ev["args"]["recorder_spans"] = list(t.spans)
                events.append(ev)
                flow.append((tid, prev_t))
                prev_t = ts
            flow.append((tid_of(evs[-1][2]), prev_t))
            for i, (tid, ts) in enumerate(flow):
                ph = "s" if i == 0 else ("f" if i == len(flow) - 1 else "t")
                ev = {
                    "name": f"{t.kind}-{t.tid}",
                    "cat": "request",
                    "ph": ph,
                    "id": t.tid,
                    "pid": 1,
                    "tid": tid,
                    "ts": round(self._wall_us(ts), 1),
                }
                if ph == "f":
                    ev["bp"] = "e"
                events.append(ev)
        # follower legs of OTHER hosts' sampled replications (ISSUE 14):
        # stage slices in this host's wall clock, flow-stepped under the
        # leader's trace id so a cross-host merge binds them into the
        # leader's request flow (tools/trace_merge.py)
        for leg in self.repl_legs():
            t_recv = leg["t_recv"]
            if not t_recv:
                continue
            leg_tid = tid_of("repl-follower")
            prev = t_recv
            for stage, key in (
                ("follower_append", "t_append"),
                ("follower_fsync", "t_fsync"),
                ("ack_send", "t_ack"),
            ):
                ts = leg[key]
                if not ts:
                    continue
                events.append({
                    "name": stage,
                    "cat": "repl",
                    "ph": "X",
                    "pid": 1,
                    "tid": leg_tid,
                    "ts": round(prev * 1e6, 1),
                    "dur": round(max(0.0, ts - prev) * 1e6, 1),
                    "args": {
                        "trace_id": leg["tid"],
                        "origin": leg["origin"],
                        "index": leg["index"],
                    },
                })
                prev = ts
            events.append({
                "name": f"write-{leg['tid']}",
                "cat": "request",
                "ph": "t",
                "id": leg["tid"],
                "pid": 1,
                "tid": leg_tid,
                "ts": round(t_recv * 1e6, 1),
                # the flow id is the LEADER's trace id — origin lets
                # tools/trace_merge.py remap ids per originating host so
                # two leaders' flows can never collide in a merged file
                "args": {"origin": leg["origin"]},
            })
        if include_recorder and self.recorder is not None:
            dev_tid = tid_of("device-plane")
            for span in self.recorder.spans():
                t0 = span.get("t0")
                if t0 is None:
                    continue
                if self.host and span.get("host") not in (None, self.host):
                    continue  # a co-hosted NodeHost's span (shared ring)
                # the span's own interval, on the stamps' clock
                events.append({
                    "name": span.get("kind", "span"),
                    "cat": "device",
                    "ph": "X",
                    "pid": 1,
                    "tid": dev_tid,
                    "ts": round(self._wall_us(t0), 1),
                    "dur": round(
                        max(span.get("t1", t0) - t0, 1e-6) * 1e6, 1
                    ),
                    "args": {
                        k: v for k, v in span.items()
                        if k not in ("ts", "t0", "t1")
                    },
                })
                if span.get("kind") == "read_ctx" and span.get("tid"):
                    # the leader's half of a sampled read (ISSUE 39) steps
                    # the REQUESTER's flow: its trace id, and its host as
                    # the origin tools/trace_merge.py remaps ids by
                    events.append({
                        "name": f"read-{span['tid']}",
                        "cat": "request",
                        "ph": "t",
                        "id": span["tid"],
                        "pid": 1,
                        "tid": dev_tid,
                        "ts": round(self._wall_us(t0), 1),
                        "args": {"origin": span.get("trace_origin")},
                    })
        ra = self.replattr
        return {
            "displayTimeUnit": "ms",
            "traceEvents": events,
            "metadata": {
                "tracer": {
                    "sample_every": self.sample_every,
                    "requests": self._n,
                    "sampled": self.sampled,
                    "completed": self.completed,
                },
                # multi-host merge keys (ISSUE 14): this dump's host
                # identity plus its leader-side ack-pair clock-offset
                # estimates per peer address (follower − leader seconds)
                "host": self.host,
                "repl_offsets": ra.offsets() if ra is not None else {},
                "repl_legs": len(self._repl_legs),
            },
        }


def compute_stage_stats(traces) -> dict:
    """Per-stage p50/p99 (ms) + share-of-e2e over completed traces —
    ONE implementation serving both ``Tracer.stage_stats`` and the
    bench trace axis's cross-host merge (nearest-rank percentiles, so
    the two surfaces can never disagree on identical data)."""
    per: Dict[str, List[float]] = {}
    e2e: List[float] = []
    for t in traces:
        if not t.done:
            continue
        evs = sorted(t.events, key=lambda e: e[1])
        prev = evs[0][1]
        for stage, ts, _th in evs[1:]:
            per.setdefault(stage, []).append(max(0.0, ts - prev))
            prev = ts
        e2e.append(max(0.0, evs[-1][1] - t.t0))

    def pct(vals, q):
        vals = sorted(vals)
        i = min(len(vals) - 1, int(round(q / 100.0 * (len(vals) - 1))))
        return vals[i]

    total = sum(e2e) or 1.0
    out = {
        "traces": len(e2e),
        "e2e": {
            "p50_ms": round(pct(e2e, 50) * 1e3, 3),
            "p99_ms": round(pct(e2e, 99) * 1e3, 3),
        } if e2e else None,
        "stages": {},
    }
    for stage, vals in sorted(per.items()):
        out["stages"][stage] = {
            "p50_ms": round(pct(vals, 50) * 1e3, 3),
            "p99_ms": round(pct(vals, 99) * 1e3, 3),
            "share_pct": round(sum(vals) / total * 100.0, 1),
            "n": len(vals),
        }
    return out


# ----------------------------------------------------------------------
# completion hook (requests.RequestState.notify)
# ----------------------------------------------------------------------

#: outcome names derived from requests.RequestResultCode (lazily — the
#: requests module imports this one); a hand-copied literal table would
#: silently drift when a code is added
_OUTCOMES: Optional[Dict[int, str]] = None


def _outcome_name(result) -> str:
    global _OUTCOMES
    if _OUTCOMES is None:
        from ..requests import RequestResultCode

        _OUTCOMES = {int(c): c.name.lower() for c in RequestResultCode}
    return _OUTCOMES.get(int(getattr(result, "code", 1)), "completed")


def request_done(token, result) -> None:
    """Called by ``RequestState.notify`` when the future carries a trace
    token.  A ``(tracer, t0, kind)`` tuple is the always-on enqueue
    timestamp of a non-sampled request: observe e2e and count its
    ``(kind, result code)`` into its owning tracer.  A :class:`Trace`
    completes into the tracer that allocated it (which counts it the
    same way)."""
    if token.__class__ is Trace:
        token.tracer.finish(token, _outcome_name(result))
        return
    tracer, t0, kind = token
    tracer.observe_done(t0, kind, _outcome_name(result).upper())


def active() -> Optional[Tracer]:
    """The newest-enabled tracer (None when tracing is off)."""
    return _ACTIVE


def live() -> List[Tracer]:
    """Every tracer of this process that is on (constructed, not yet
    closed), oldest first: co-hosted NodeHosts each own one."""
    return list(_LIVE)
