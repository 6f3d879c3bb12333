"""The one traffic generator: reads a ``traffic/*.json`` and drives a cluster.

An *operation* is what a Dragonboat client does under the retry contract
upstream documents for its Sync* APIs.  A write is a put of a seeded key
and value (the configuration's ``key_bytes`` + ``value_bytes``) to one
group, submitted to the host that leads the group at that moment.  The key
draw is the traffic file's: without ``keys_per_group`` every write puts a
fresh key; with it the group's small key set is overwritten in turn, and
the value carries the write's serial, so what a read returns names the
write it saw.  A read is a linearizable ReadIndex read followed by the
local lookup, on the host ``read_host`` names (``leader``, or ``any``: a
Dragonboat client reads at whichever NodeHost it is on), of a
key whose write was acknowledged; ``read_newest_share`` of the reads ask
for the key whose acknowledgement their poller saw last (a user reading
back what was just written: the read a stale replica gets wrong).  An
attempt that ends in anything but ``COMPLETED`` (``DROPPED``, ``TIMEOUT``,
a busy queue, ...) waits one ``rtt_millisecond`` and is submitted again;
re-putting the same key and value is idempotent.  The operation fails only
if no acknowledgement arrives by its deadline.  Its latency runs from when
it was due (open loop) or first submitted (closed loop) to
``completed_at`` of the attempt that succeeded, retries included.

The window's end abandons nothing: once it closes the generator stops
offering and drains every operation to an outcome.  ``attempted`` counts
operations due (first submitted) inside the window; a rate counts
acknowledgements that arrived inside it.

At most one poller thread per host, no thread per client: each thread owns
the groups its host led at the start and polls their futures.
"""
from __future__ import annotations

import threading
import time

import numpy as np

WRITE, READ = 0, 1
OK = "COMPLETED"
#: fresh-key traffic: a read asks for one of the group's newest this many
KNOWN_KEYS = 16


class Op:
    __slots__ = ("kind", "cid", "key", "val", "due", "first", "attempt",
                 "fut", "host", "sent", "retry_at", "slot", "u")


class Outcome:
    """What one window produced; lists are per operation, window ops only
    unless named otherwise."""

    def __init__(self):
        self.t0 = self.t_end = 0.0
        self.attempted = 0
        self.failed = 0
        self.acks_in_window = 0       # acknowledgements that arrived inside
        self.ack_at = []              # perf_counter of every acknowledgement
        self.retries = 0              # attempts beyond the first, window ops
        self.lat = {WRITE: [], READ: []}   # seconds; failed = the deadline
        self.start = {WRITE: [], READ: []}  # seconds into the window, same order
        self.late = []                # seconds the generator ran behind
        self.inflight_at_end = 0
        self.events = []              # non-COMPLETED attempts, every phase
        # for the comparison (warm-up operations included); times are
        # perf_counter readings
        self.acked_writes = []    # (cid, key, val, apply_seq, first, acked_at)
        self.unacked_writes = []  # (cid, key, val, first): may or may not apply
        self.reads = []           # (cid, key, got, host, submitted, looked_up)
        self.attempts_by_group = {}   # cid -> write attempts submitted

    def merge(self, o: "Outcome") -> None:
        self.attempted += o.attempted
        self.failed += o.failed
        self.acks_in_window += o.acks_in_window
        self.retries += o.retries
        self.ack_at += o.ack_at
        for k in self.lat:
            self.lat[k] += o.lat[k]
            self.start[k] += o.start[k]
        self.late += o.late
        self.inflight_at_end += o.inflight_at_end
        self.events += o.events
        self.acked_writes += o.acked_writes
        self.unacked_writes += o.unacked_writes
        self.reads += o.reads
        for cid, n in o.attempts_by_group.items():
            self.attempts_by_group[cid] = self.attempts_by_group.get(cid, 0) + n


def open_schedule(traffic: dict, cids: list, seed: int, seconds: float,
                  warmup_s: float) -> list:
    """(due, kind, cid) for the whole run, due relative to the window's
    start (warm-up arrivals are negative).  Every seed gets the same number
    of arrivals, the same number of writes and the same number of operations
    per group, in another order and with other gaps, so the seed changes the
    order of the work and never its amount."""
    rng = np.random.default_rng([seed, 0x5EED])
    rate = float(traffic["rate_ops_per_s"])
    out = []
    for span, origin in ((warmup_s, -warmup_s), (seconds, 0.0)):
        n = int(round(rate * span))
        if n == 0:
            continue
        gaps = rng.exponential(1.0, n + 1)
        due = origin + np.cumsum(gaps)[:n] * (span / gaps.sum())
        n_reads = int(round(n * float(traffic["read_share"])))
        kinds = np.array([READ] * n_reads + [WRITE] * (n - n_reads))
        groups = np.resize(np.array(cids), n)
        rng.shuffle(kinds)
        rng.shuffle(groups)
        out += list(zip(due.tolist(), kinds.tolist(), groups.tolist()))
    return out


class _Worker:
    """One poller thread: the groups it owns, their in-flight operations."""

    def __init__(self, gen: "Generator", idx: int, cids: list, schedule):
        self.gen = gen
        self.cids = cids
        self.rng = np.random.default_rng([gen.seed, idx + 1])
        self.pool = b""
        self.out = Outcome()
        # cid -> distinct keys with an acknowledged write, newest last
        self.have = {cid: list(gen.known.get(cid, ())) for cid in cids}
        # (cid, key) of the acknowledgement this poller saw last
        self.newest = next(((cid, self.have[cid][-1]) for cid in
                            reversed(cids) if self.have[cid]), None)
        self.schedule = schedule  # open loop: sorted [(due, kind, cid)]
        self.error = None

    def _bytes(self, n: int) -> bytes:
        if len(self.pool) < n:
            self.pool = self.rng.bytes(1 << 16)
        b, self.pool = self.pool[:n], self.pool[n:]
        return b

    def _new_op(self, kind, cid, due, slot=None) -> Op:
        g = self.gen
        op = Op()
        op.kind, op.cid, op.due, op.slot = kind, cid, due, slot
        op.attempt, op.fut, op.retry_at, op.first = 0, None, None, None
        if kind == WRITE:
            serial = g.next_serial(cid)
            stamp = serial.to_bytes(4, "little")
            if g.keys_per_group:
                op.key = g.group_key(cid, serial % g.keys_per_group)
            else:
                op.key = stamp + self._bytes(g.key_bytes - 4)
            op.val = stamp + self._bytes(g.value_bytes - 4)
        else:
            op.key = op.val = None
            op.u = self.rng.random(3)
        return op

    def _submit(self, op: Op, now: float) -> None:
        """One attempt: a write to the host that leads the group right now,
        a read to the host the traffic names."""
        g, cl = self.gen, self.gen.cluster
        if op.first is None:
            op.first = now
            if op.kind == READ:
                if op.u[0] < g.read_newest_share and self.newest is not None:
                    op.cid, op.key = self.newest
                else:
                    have = self.have[op.cid]
                    if not have:
                        raise RuntimeError(
                            f"group {op.cid}: a read before any "
                            "acknowledged write (no prefill?)")
                    op.key = have[int(op.u[1] * len(have))]
        op.attempt += 1
        op.retry_at = None
        op.host = cl.leader_host(op.cid)
        try:
            if op.kind == WRITE:
                op.sent = now
                a = self.out.attempts_by_group
                a[op.cid] = a.get(op.cid, 0) + 1
                op.fut = cl.submit_write(op.host, op.cid, op.key + op.val,
                                         g.attempt_timeout_s)
            else:
                if g.read_host == "any":
                    op.host = int(op.u[2] * cl.replicas)
                # what was acknowledged before this instant the read must
                # see: read the clock as late as the call allows
                op.sent = time.perf_counter()
                op.fut = cl.submit_read(op.host, op.cid, g.attempt_timeout_s)
        except cl.busy_errors as e:
            op.fut = None
            self._attempt_failed(op, type(e).__name__, now)

    def _attempt_failed(self, op: Op, code: str, now: float) -> None:
        g = self.gen
        self.out.events.append(
            (round(now - g.t0, 4), code, "write" if op.kind == WRITE else "read",
             op.cid, op.host + 1, op.attempt)
        )
        g.cluster.refresh_leader(op.cid)
        op.fut = None
        op.retry_at = now + g.rtt_s

    def _finish(self, op: Op, done_at, got=None, apply_seq=0) -> None:
        """The operation reached its outcome: acknowledged (``done_at``) or
        failed at its deadline (``done_at`` None)."""
        g, out = self.gen, self.out
        start = op.due if g.open_loop else op.first
        in_window = g.t0 <= start < g.t_end
        acked = done_at is not None
        if op.kind == WRITE:
            if acked:
                out.acked_writes.append((op.cid, op.key, op.val, apply_seq,
                                         op.first, done_at))
                have = self.have[op.cid]
                if not g.keys_per_group:
                    have.append(op.key)
                    del have[:-KNOWN_KEYS]
                elif op.key not in have:
                    have.append(op.key)
                self.newest = (op.cid, op.key)
            else:
                out.unacked_writes.append((op.cid, op.key, op.val, op.first))
        elif acked:
            out.reads.append((op.cid, op.key, got[0], op.host, op.sent,
                              got[1]))
        if acked:
            out.ack_at.append(done_at)
            if g.t0 <= done_at < g.t_end:
                out.acks_in_window += 1
        if in_window:
            out.attempted += 1
            out.retries += op.attempt - 1
            out.lat[op.kind].append(
                done_at - start if acked else g.deadline_s
            )
            out.start[op.kind].append(start - g.t0)
            if not acked:
                out.failed += 1

    def _poll(self, op: Op, now: float) -> bool:
        """True once the operation has reached its outcome."""
        g = self.gen
        start = op.due if g.open_loop else op.first
        if op.fut is not None:
            if op.fut.done():
                r = op.fut.result
                code = r.code.name
                if code == OK:
                    if op.kind == READ:
                        got = g.cluster.lookup(op.host, op.cid, op.key)
                        self._finish(op, op.fut.completed_at,
                                     got=(got, time.perf_counter()))
                    else:
                        self._finish(op, op.fut.completed_at,
                                     apply_seq=r.result.value)
                    return True
                self._attempt_failed(op, code, now)
            elif now - op.sent > g.attempt_timeout_s + g.timeout_grace_s:
                # the program's own tick-driven timeout did not fire
                self._attempt_failed(op, "TIMEOUT_BY_CLIENT_CLOCK", now)
        if op.fut is None:
            if now - start >= g.deadline_s:
                self._finish(op, None)
                return True
            if op.retry_at is not None and now >= op.retry_at:
                self._submit(op, now)
        return False

    def run(self) -> None:
        try:
            self._run()
        except BaseException as e:  # surfaces in Generator.run
            self.error = e

    def _run(self) -> None:
        g = self.gen
        inflight = []
        sched, nxt = self.schedule, 0
        noted_end = False
        if not g.open_loop:
            # closed loop: ``inflight_per_group`` callers per group, each
            # submitting its next write when the previous one is answered
            free = [(cid, s) for s in range(g.inflight_per_group)
                    for cid in self.cids]
            freed_at = {}
        while True:
            now = time.perf_counter()
            offering = now < g.t_end
            # outcomes first: what is submitted below knows of every
            # acknowledgement that has arrived
            still = []
            progress = False
            for op in inflight:
                if self._poll(op, now):
                    progress = True
                    if not g.open_loop:
                        free.append((op.cid, op.slot))
                        freed_at[(op.cid, op.slot)] = (
                            op.fut.completed_at if op.fut is not None else now
                        )
                else:
                    still.append(op)
            inflight = still
            if g.open_loop:
                while nxt < len(sched) and g.t0 + sched[nxt][0] <= now:
                    rel, kind, cid = sched[nxt]
                    nxt += 1
                    op = self._new_op(kind, cid, g.t0 + rel)
                    self.out.late.append(now - op.due)
                    self._submit(op, now)
                    inflight.append(op)
                more = nxt < len(sched)
            else:
                if offering:
                    for cid, s in free:
                        op = self._new_op(WRITE, cid, now, slot=s)
                        t_free = freed_at.get((cid, s))
                        if t_free is not None and now >= g.t0:
                            self.out.late.append(max(now - t_free, 0.0))
                        self._submit(op, now)
                        inflight.append(op)
                    free = []
                more = False
            if not offering and not noted_end:
                self.out.inflight_at_end, noted_end = len(inflight), True
            if not inflight and not more and not offering:
                return
            if progress:
                continue
            # nothing finished: block on the oldest future (it wakes this
            # thread the moment it completes), bounded by the next due time
            wait = g.poll_s
            if g.open_loop and more:
                wait = min(wait, max(g.t0 + sched[nxt][0] - now, 0.0))
            oldest = next((o.fut for o in inflight if o.fut is not None), None)
            if oldest is not None and wait > 0:
                oldest.wait(wait)
            elif wait > 0:
                time.sleep(wait)


class Generator:
    def __init__(self, cluster, traffic: dict, seed: int, seconds: float,
                 serials: dict, known: dict):
        self.cluster = cluster
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.open_loop = traffic["loop"] == "open"
        if traffic["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop kind {traffic['loop']!r}")
        self.traffic = traffic
        self.warmup_s = float(traffic["warmup_s"])
        self.attempt_timeout_s = float(traffic["attempt_timeout_s"])
        self.deadline_s = float(traffic["deadline_s"])
        self.inflight_per_group = int(traffic.get("inflight_per_group", 0))
        self.keys_per_group = int(traffic.get("keys_per_group", 0))
        self.read_host = traffic.get("read_host", "leader")
        if self.read_host not in ("leader", "any"):
            raise ValueError(f"unknown read_host {self.read_host!r}")
        self.read_newest_share = float(traffic.get("read_newest_share", 0.0))
        self.key_bytes, self.value_bytes = cluster.key_bytes, cluster.value_bytes
        # the seeded part of a group's overwritten keys
        salt = np.random.default_rng([self.seed, 0x4B]).bytes(
            (self.key_bytes - 4) * (max(cluster.cids) + 1))
        self._salt = [salt[i:i + self.key_bytes - 4]
                      for i in range(0, len(salt), self.key_bytes - 4)]
        self.rtt_s = cluster.rtt_s
        self.timeout_grace_s = 1.0   # beyond the program's own timeout
        self.poll_s = 0.002
        self.serials = serials   # cid -> writes ever proposed
        self.known = known       # cid -> [key] acknowledged earlier
        self.t0 = self.t_end = 0.0

    def next_serial(self, cid: int) -> int:
        n = self.serials.get(cid, 0) + 1  # one owner thread per group
        self.serials[cid] = n
        return n

    def group_key(self, cid: int, i: int) -> bytes:
        return i.to_bytes(4, "little") + self._salt[cid]

    def prefill(self) -> Outcome:
        """Outside any window: one acknowledged write per key of every
        group's key set (one per group where keys are fresh), the keys the
        reads will ask for.  Where the traffic reads, then one read per
        group, a quiet half second and one more write per group: with the
        read plane in use the engine runs other programs for a round
        without reads, and a lone write or an idle tick after the first
        read is their first use, which belongs to set-up."""
        self.open_loop, self.seconds, self.warmup_s = True, 0.0, 0.0
        cids = self.cluster.cids
        out = self.run(schedule=[(0.0, WRITE, c) for c in cids]
                       * max(self.keys_per_group, 1))
        if float(self.traffic["read_share"]) > 0:
            out.merge(self.run(schedule=[(0.0, READ, c) for c in cids
                                         if self.known.get(c)]))
            time.sleep(0.5)
            out.merge(self.run(schedule=[(0.0, WRITE, c) for c in cids]))
        return out

    def run(self, on_window=None, schedule=None) -> Outcome:
        cl = self.cluster
        n_threads = min(3, len(cl.cids))
        owned = [[] for _ in range(n_threads)]
        for cid in cl.cids:
            owned[cl.leader_host(cid) % n_threads].append(cid)
        owned = [o for o in owned if o]
        sched = [None] * len(owned)
        if self.open_loop:
            owner = {cid: i for i, o in enumerate(owned) for cid in o}
            sched = [[] for _ in owned]
            if schedule is None:
                schedule = open_schedule(self.traffic, cl.cids, self.seed,
                                         self.seconds, self.warmup_s)
            for item in schedule:
                sched[owner[item[2]]].append(item)
        start = time.perf_counter() + 0.05
        self.t0 = start + self.warmup_s
        self.t_end = self.t0 + self.seconds
        workers = [_Worker(self, i, o, sched[i]) for i, o in enumerate(owned)]
        threads = [threading.Thread(target=w.run, name=f"bench-gen-{i}")
                   for i, w in enumerate(workers)]
        for t in threads:
            t.start()
        if on_window is not None:
            on_window(self.t0, self.t_end)
        for t in threads:
            t.join()
        out = Outcome()
        out.t0, out.t_end = self.t0, self.t_end
        for w in workers:
            if w.error is not None:
                raise w.error
            out.merge(w.out)
            self.known.update(w.have)
        out.events.sort()
        return out
