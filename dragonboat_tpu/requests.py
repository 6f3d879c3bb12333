"""Pending-request tracking: the future/promise layer between user API calls
and the asynchronous engine.

Reference: ``requests.go`` — pooled ``RequestState`` futures with result
channels; ``pendingProposal`` sharded 16 ways on a random 64-bit key
(:446,:943); ``pendingReadIndex`` batching by ``SystemCtx`` (:457);
single-slot ``pendingConfigChange``/``pendingSnapshot``/
``pendingLeaderTransfer`` (:471-486); logical-clock GC of timed-out requests.
"""
from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field
from enum import IntEnum
from typing import Dict, List, Optional, Tuple

from .obs import trace as _trace
from .settings import Soft
from .statemachine import Result
from .wire import Entry, ReadyToRead, ReplTrace, SystemCtx


class RequestError(Exception):
    pass


class ClusterNotFoundError(RequestError):
    pass


class ClusterAlreadyExistError(RequestError):
    pass


class ClusterNotReadyError(RequestError):
    pass


class ClusterClosedError(RequestError):
    pass


class SystemBusyError(RequestError):
    pass


class InvalidSessionError(RequestError):
    pass


class TimeoutError_(RequestError):
    pass


class CanceledError(RequestError):
    pass


class RejectedError(RequestError):
    pass


class InvalidOperationError(RequestError):
    """The request is not valid on this replica type — e.g. any
    proposal/read/config-change/snapshot/transfer on a WITNESS replica
    (reference ``ErrInvalidOperation``, node.go:352-442: witnesses vote
    and persist metadata but never serve user operations)."""


class PayloadTooBigError(RequestError):
    """Entry payload exceeds ``Config.max_in_mem_log_size`` (reference
    ``ErrPayloadTooBig``, node.go:363-367: an entry that cannot fit the
    in-memory log bound can never be appended)."""


class PendingConfigChangeExistError(RequestError):
    pass


class PendingSnapshotExistError(RequestError):
    pass


class PendingLeaderTransferExistError(RequestError):
    pass


class RequestResultCode(IntEnum):
    TIMEOUT = 0
    COMPLETED = 1
    TERMINATED = 2
    REJECTED = 3
    DROPPED = 4
    ABORTED = 5
    COMMITTED = 6


@dataclass
class RequestResult:
    code: RequestResultCode = RequestResultCode.TIMEOUT
    result: Result = field(default_factory=Result)
    snapshot_index: int = 0

    @property
    def completed(self) -> bool:
        return self.code == RequestResultCode.COMPLETED

    @property
    def rejected(self) -> bool:
        return self.code == RequestResultCode.REJECTED

    @property
    def timeout(self) -> bool:
        return self.code == RequestResultCode.TIMEOUT

    @property
    def terminated(self) -> bool:
        return self.code == RequestResultCode.TERMINATED

    @property
    def dropped(self) -> bool:
        return self.code == RequestResultCode.DROPPED


class RequestState:
    """Reference ``requests.go:267`` ``RequestState`` — a one-shot future."""

    __slots__ = (
        "key",
        "client_id",
        "series_id",
        "deadline",
        "_event",
        "_result",
        "read_index",
        "completed_at",
        "trace",
    )

    def __init__(self, key: int = 0, deadline: int = 0):
        self.key = key
        self.client_id = 0
        self.series_id = 0
        self.deadline = deadline
        self._event = threading.Event()
        self._result: Optional[RequestResult] = None
        self.read_index = 0
        #: perf_counter() at notify time — lets a pipelined client report
        #: the request's true completion latency instead of the (later)
        #: moment it got around to observing the result
        self.completed_at: Optional[float] = None
        #: request-trace token (ISSUE 9): None while tracing is off (the
        #: bit-identical default); with tracing on, a (tracer, t0, kind)
        #: enqueue-timestamp token for non-sampled requests or an
        #: obs.trace.Trace for the sampled 1-in-N
        self.trace = None

    def notify(self, result: RequestResult) -> None:
        self.completed_at = time.perf_counter()
        self._result = result
        self._event.set()
        if self.trace is not None:
            _trace.request_done(self.trace, result)

    def wait(self, timeout: Optional[float] = None) -> RequestResult:
        if not self._event.wait(timeout):
            return RequestResult(code=RequestResultCode.TIMEOUT)
        assert self._result is not None
        return self._result

    def done(self) -> bool:
        return self._event.is_set()

    @property
    def result(self) -> Optional[RequestResult]:
        return self._result


class _LogicalClock:
    """Reference ``requests.go:216`` ``logicalClock``."""

    def __init__(self) -> None:
        self.tick = 0

    def advance(self) -> None:
        self.tick += 1


class PendingProposal:
    """Sharded proposal tracker (reference ``requests.go:446,943``)."""

    def __init__(self, shards: int = 0, rng: Optional[random.Random] = None):
        self.nshards = shards or Soft.pending_proposal_shards
        self._shards: List[Dict[int, RequestState]] = [
            {} for _ in range(self.nshards)
        ]
        self._locks = [threading.Lock() for _ in range(self.nshards)]
        self._clock = _LogicalClock()
        self._rng = rng or random.Random()
        self._stopped = False
        # earliest-deadline tracking: tick() skips the full scan until
        # something could actually have expired (it runs once per RTT for
        # EVERY group, so the scan-always version is hot-path cost).
        # Two fields to make the propose/tick race safe: _min_deadline is
        # owned by the scan; _pending_min accumulates deadlines published by
        # propose() since the last scan and is merged (never dropped) there.
        # A proposal inserted into an already-scanned shard mid-scan thus
        # stays visible to the fast-path check either way.
        self._min_deadline = 1 << 62
        self._pending_min = 1 << 62
        self._min_mu = threading.Lock()
        # client-completion egress sink (hostplane.EgressPool): when set,
        # ``applied`` hands the resolved future to the sink instead of
        # running ``rs.notify`` (the client-thread ``Event.set`` wakeup)
        # inline on the apply worker.  None (default) keeps the apply
        # path bit-identical to the pre-compartment build.
        self._egress = None

    def set_egress(self, sink) -> None:
        self._egress = sink

    def _next_key(self) -> int:
        return self._rng.getrandbits(64) or 1

    def propose(
        self, client_id: int, series_id: int, cmd: bytes, timeout_ticks: int
    ) -> Tuple[RequestState, Entry]:
        if self._stopped:
            raise ClusterClosedError()
        key = self._next_key()
        deadline = self._clock.tick + timeout_ticks
        rs = RequestState(key=key, deadline=deadline)
        rs.client_id = client_id
        rs.series_id = series_id
        shard = key % self.nshards
        with self._locks[shard]:
            self._shards[shard][key] = rs
        if deadline < self._pending_min:
            with self._min_mu:
                if deadline < self._pending_min:
                    self._pending_min = deadline
        entry = Entry(
            key=key, client_id=client_id, series_id=series_id, cmd=cmd
        )
        return rs, entry

    def propose_batch(
        self, client_id: int, series_id: int, cmds: List[bytes],
        timeout_ticks: int,
    ) -> Tuple[List[RequestState], List[Entry]]:
        """Track a burst of proposals in one pass.  Semantically identical
        to N ``propose`` calls (one RequestState + one Entry per command);
        amortizes the clock read, the deadline publication and — by
        grouping keys per shard — the tracker lock traffic.  The per-write
        Python cost of the propose path is a first-order term in end-to-end
        throughput once replication itself runs in the native fast lane."""
        if self._stopped:
            raise ClusterClosedError()
        deadline = self._clock.tick + timeout_ticks
        bits = self._rng.getrandbits
        states: List[RequestState] = []
        entries: List[Entry] = []
        by_shard: Dict[int, List[RequestState]] = {}
        for cmd in cmds:
            key = bits(64) or 1
            rs = RequestState(key=key, deadline=deadline)
            rs.client_id = client_id
            rs.series_id = series_id
            states.append(rs)
            entries.append(
                Entry(key=key, client_id=client_id, series_id=series_id, cmd=cmd)
            )
            by_shard.setdefault(key % self.nshards, []).append(rs)
        for shard, group in by_shard.items():
            with self._locks[shard]:
                d = self._shards[shard]
                for rs in group:
                    d[rs.key] = rs
        if deadline < self._pending_min:
            with self._min_mu:
                if deadline < self._pending_min:
                    self._pending_min = deadline
        return states, entries

    def register_batch(self, states: List[RequestState]) -> None:
        """Insert pre-created futures (hostplane ingress batcher): the
        client thread built the RequestStates without touching the tracker
        locks; the batcher registers them here — grouped per shard, one
        lock acquisition each — strictly before staging the entries, so
        completion can never miss the registration."""
        if self._stopped:
            for rs in states:
                rs.notify(RequestResult(code=RequestResultCode.TERMINATED))
            return
        by_shard: Dict[int, List[RequestState]] = {}
        min_deadline = 1 << 62
        for rs in states:
            by_shard.setdefault(rs.key % self.nshards, []).append(rs)
            if rs.deadline < min_deadline:
                min_deadline = rs.deadline
        for shard, group in by_shard.items():
            with self._locks[shard]:
                d = self._shards[shard]
                for rs in group:
                    d[rs.key] = rs
        if min_deadline < self._pending_min:
            with self._min_mu:
                if min_deadline < self._pending_min:
                    self._pending_min = min_deadline

    def applied(
        self,
        key: int,
        client_id: int,
        series_id: int,
        result: Result,
        rejected: bool,
    ) -> None:
        """Completion from the apply path (reference ``requests.go:1155``)."""
        shard = key % self.nshards
        with self._locks[shard]:
            rs = self._shards[shard].get(key)
            if rs is None:
                return
            if rs.client_id != client_id or rs.series_id != series_id:
                return
            del self._shards[shard][key]
        if rs.trace is not None:
            _trace.Tracer.mark(rs, "apply")
        code = (
            RequestResultCode.REJECTED if rejected else RequestResultCode.COMPLETED
        )
        egress = self._egress
        if egress is not None:
            egress(rs, RequestResult(code=code, result=result))
        else:
            rs.notify(RequestResult(code=code, result=result))

    def dropped(self, key: int) -> None:
        shard = key % self.nshards
        with self._locks[shard]:
            rs = self._shards[shard].pop(key, None)
        if rs is not None:
            rs.notify(RequestResult(code=RequestResultCode.DROPPED))

    def close(self) -> None:
        self._stopped = True
        for shard, lock in zip(self._shards, self._locks):
            with lock:
                for rs in shard.values():
                    rs.notify(RequestResult(code=RequestResultCode.TERMINATED))
                shard.clear()

    def has_pending(self) -> bool:
        """Unlocked emptiness probe (tick-lite sweep heuristic)."""
        return any(self._shards)

    def tick(self) -> None:
        self._clock.advance()
        now = self._clock.tick
        if now <= self._min_deadline and now <= self._pending_min:
            return
        new_min = 1 << 62
        timed_out = []
        for shard, lock in zip(self._shards, self._locks):
            with lock:
                for key, rs in list(shard.items()):
                    if rs.deadline < now:
                        timed_out.append(rs)
                        del shard[key]
                    elif rs.deadline < new_min:
                        new_min = rs.deadline
        with self._min_mu:
            # merge the scan result with anything propose() published since;
            # _pending_min is folded in (never discarded), so a proposal the
            # scan raced past cannot lose its timeout
            self._min_deadline = min(new_min, self._pending_min)
            self._pending_min = 1 << 62
        for rs in timed_out:
            rs.notify(RequestResult(code=RequestResultCode.TIMEOUT))


class PendingReadIndex:
    """ReadIndex batching tracker (reference ``requests.go:457,782``)."""

    def __init__(self, rng: Optional[random.Random] = None):
        self._mu = threading.Lock()
        self._rng = rng or random.Random()
        # requests waiting to be batched into the next ReadIndex
        self._pending: List[RequestState] = []
        # ctx → batch already submitted to raft
        self._batches: Dict[SystemCtx, List[RequestState]] = {}
        # confirmed (index known) but waiting for apply to catch up
        self._confirmed: List[Tuple[int, RequestState]] = []
        self._clock = _LogicalClock()
        self._stopped = False
        # completion egress sink (hostplane) — same contract as
        # PendingProposal._egress; None keeps notify inline
        self._egress = None
        # request tracer (ISSUE 9, set by NodeHost wiring): reads carry
        # no entry key, so their stage stamps ride the rs objects this
        # tracker already holds; None keeps every loop below untouched
        self._tracer = None

    def set_egress(self, sink) -> None:
        self._egress = sink

    def read(self, timeout_ticks: int) -> RequestState:
        if self._stopped:
            raise ClusterClosedError()
        rs = RequestState(deadline=self._clock.tick + timeout_ticks)
        with self._mu:
            self._pending.append(rs)
        return rs

    def peep(self) -> bool:
        # GIL-atomic read; polled every step round for every group
        return bool(self._pending)

    def has_pending(self) -> bool:
        """Unlocked emptiness probe (tick-lite sweep heuristic)."""
        return bool(self._pending or self._batches or self._confirmed)

    def next_ctx(self) -> SystemCtx:
        return SystemCtx(
            low=self._rng.getrandbits(64), high=self._rng.getrandbits(64) or 1
        )

    def take_pending(self, ctx: SystemCtx) -> bool:
        """Move queued requests into a submitted batch keyed by ``ctx``."""
        with self._mu:
            if not self._pending:
                return False
            batch = self._pending
            self._batches[ctx] = batch
            self._pending = []
        if self._tracer is not None:
            for rs in batch:
                if rs.trace is not None:
                    self._tracer.mark(rs, "raft_step")
        return True

    def trace_ctx(self, ctx: SystemCtx, origin: str):
        """Tracer on: note on every sampled request of the batch ``ctx``
        covers the context and where it is confirmed from (``local``: this
        replica leads; ``forwarded``), and return the wire context that
        names the first of them (``Message.trace`` of the READ_INDEX), or
        None where the batch holds no sampled request."""
        with self._mu:
            batch = self._batches.get(ctx, ())
        wire = None
        for rs in batch:
            t = rs.trace
            if t.__class__ is _trace.Trace:
                t.read_ctx = (ctx.low, ctx.high)
                t.read_origin = origin
                if wire is None:
                    wire = ReplTrace(tid=t.tid, origin=t.tracer.host)
        return wire

    def trace_woke(self, ctx: SystemCtx, role: str) -> None:
        """Tracer on: the batch ``ctx`` covers found its group asleep and
        woke this replica (``role``); noted on its sampled requests
        (``Trace.woke``: a ``quiesce_wake`` span at their end)."""
        with self._mu:
            batch = self._batches.get(ctx, ())
        now = time.perf_counter()
        for rs in batch:
            t = rs.trace
            if t.__class__ is _trace.Trace and t.woke is None:
                t.woke = (now, role)

    def pending_ctxs(self) -> List[SystemCtx]:
        """Contexts taken for confirmation but not yet ready — after a
        fast-lane eject these must be re-driven through the scalar
        protocol or their reads strand until timeout."""
        with self._mu:
            return list(self._batches.keys())

    def add_ready(self, readies: List[ReadyToRead]) -> None:
        """Raft confirmed these contexts at an index
        (reference ``requests.go:821``)."""
        if not readies:
            return
        tracer = self._tracer
        with self._mu:
            for r in readies:
                batch = self._batches.pop(r.system_ctx, None)
                if batch is None:
                    continue
                # lease-served readies (ISSUE 10) skipped the echo-quorum
                # round entirely; the trace shows the short path
                stage = "lease_read" if r.lease else "read_confirm"
                for rs in batch:
                    rs.read_index = r.index
                    self._confirmed.append((r.index, rs))
                    if tracer is not None and rs.trace is not None:
                        tracer.mark(rs, stage)

    def applied(self, applied_index: int) -> None:
        """Apply watermark moved; complete reads whose index is covered
        (reference ``requests.go:868``)."""
        done: List[RequestState] = []
        with self._mu:
            if not self._confirmed:
                return
            keep = []
            for idx, rs in self._confirmed:
                if idx <= applied_index:
                    done.append(rs)
                else:
                    keep.append((idx, rs))
            self._confirmed = keep
        egress = self._egress
        tracer = self._tracer
        for rs in done:
            if tracer is not None and rs.trace is not None:
                tracer.mark(rs, "apply")
            if egress is not None:
                egress(rs, RequestResult(code=RequestResultCode.COMPLETED))
            else:
                rs.notify(RequestResult(code=RequestResultCode.COMPLETED))

    def dropped(self, ctxs: List[SystemCtx]) -> None:
        with self._mu:
            batches = [self._batches.pop(c, None) for c in ctxs]
        for batch in batches:
            if batch:
                for rs in batch:
                    rs.notify(RequestResult(code=RequestResultCode.DROPPED))

    def close(self) -> None:
        self._stopped = True
        with self._mu:
            all_rs = list(self._pending)
            self._pending = []
            for batch in self._batches.values():
                all_rs.extend(batch)
            self._batches.clear()
            all_rs.extend(rs for _, rs in self._confirmed)
            self._confirmed = []
        for rs in all_rs:
            rs.notify(RequestResult(code=RequestResultCode.TERMINATED))

    def tick(self) -> None:
        self._clock.advance()
        now = self._clock.tick
        # fast path: nothing tracked (idle groups tick every RTT)
        if not (self._pending or self._batches or self._confirmed):
            return
        timed_out: List[RequestState] = []
        with self._mu:
            self._pending, expired = (
                [rs for rs in self._pending if rs.deadline >= now],
                [rs for rs in self._pending if rs.deadline < now],
            )
            timed_out.extend(expired)
            for ctx in list(self._batches):
                batch = self._batches[ctx]
                live = [rs for rs in batch if rs.deadline >= now]
                dead = [rs for rs in batch if rs.deadline < now]
                timed_out.extend(dead)
                if live:
                    self._batches[ctx] = live
                else:
                    del self._batches[ctx]
            keep = []
            for idx, rs in self._confirmed:
                if rs.deadline < now:
                    timed_out.append(rs)
                else:
                    keep.append((idx, rs))
            self._confirmed = keep
        for rs in timed_out:
            rs.notify(RequestResult(code=RequestResultCode.TIMEOUT))


class _SingleSlot:
    """Single-in-flight request trackers (reference ``requests.go:471-486``)."""

    exist_error = RequestError

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._pending: Optional[RequestState] = None
        self._payload: Optional[object] = None
        self._clock = _LogicalClock()
        self._stopped = False

    def request(self, payload, timeout_ticks: int) -> RequestState:
        if self._stopped:
            raise ClusterClosedError()
        with self._mu:
            if self._pending is not None:
                raise self.exist_error()
            rs = RequestState(
                key=random.getrandbits(64),
                deadline=self._clock.tick + timeout_ticks,
            )
            self._pending = rs
            self._payload = payload
            return rs

    def take(self):
        # lock-free empty check: this runs in every step round for every
        # group (node._handle_events) and is almost always empty; a plain
        # read is GIL-atomic and a racing request() just gets picked up on
        # the next round
        if self._payload is None:
            return None
        with self._mu:
            p, self._payload = self._payload, None
            return p

    def pending(self) -> Optional[RequestState]:
        if self._pending is None:
            return None
        with self._mu:
            return self._pending

    def notify(self, result: RequestResult) -> None:
        with self._mu:
            rs, self._pending = self._pending, None
            self._payload = None
        if rs is not None:
            rs.notify(result)

    def close(self) -> None:
        self._stopped = True
        self.notify(RequestResult(code=RequestResultCode.TERMINATED))

    def tick(self) -> None:
        self._clock.advance()
        if self._pending is None:
            return
        with self._mu:
            rs = self._pending
            if rs is not None and rs.deadline < self._clock.tick:
                self._pending = None
                self._payload = None
            else:
                rs = None
        if rs is not None:
            rs.notify(RequestResult(code=RequestResultCode.TIMEOUT))


class PendingConfigChange(_SingleSlot):
    exist_error = PendingConfigChangeExistError


class PendingSnapshot(_SingleSlot):
    exist_error = PendingSnapshotExistError


class PendingLeaderTransfer(_SingleSlot):
    exist_error = PendingLeaderTransferExistError
