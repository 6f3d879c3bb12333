"""The plain reference: a replicated key-value log in straightforward Python.

It imports nothing of the program and takes nothing the program made.  It
states the semantics every configuration here promises — a write is
acknowledged once a quorum holds it and the acknowledging host has applied
it, every replica applies the same log in the same order, a read returns
the newest acknowledged value — in the fewest lines that do so.

Two uses.  ``expected_state`` replays the operations a run acknowledged and
gives what every replica of the program must then hold.  ``ReferenceCluster``
is the same semantics behind the surface the generator drives, so it can be
put in the program's place: sound, it is the harness's own self-check;
with ``broken`` naming one guarantee to give up, it is the control that the
comparison has to fail.
"""
from __future__ import annotations

import bisect
import random
import time

#: the guarantees a control may give up, one at a time
BROKEN = ("ack_before_quorum", "stale_read")


def expected_state(acked_writes) -> dict:
    """cid -> {key: value} after the acknowledged puts, applied in the
    order the state machine numbered them."""
    state = {}
    for cid, key, val, *_ in sorted(acked_writes, key=lambda w: (w[0], w[3])):
        state.setdefault(cid, {})[key] = val
    return state


def wrong_reads(acked_writes, unacked_writes, reads) -> int:
    """Reads that a linearizable key-value store could not have answered so.

    A write is ``(cid, key, val, apply_seq, first_submitted, acked_at)``
    (an unacknowledged one stops after ``first_submitted``), a read ``(cid,
    key, got, host, submitted, looked_up)``; every value is unique to its
    write.  A read is wrong if it got nothing (it only asks for keys with an
    acknowledged write), a value no write of that key carried, the value of a
    write first submitted after the lookup, or the value of an acknowledged
    write that the state machine applied before another write of the key
    whose acknowledgement had arrived when the read was submitted: every
    application of a retried write precedes the one that was acknowledged,
    so that read saw a state older than the read was owed."""
    by_val, by_key = {}, {}
    for cid, key, val, seq, first, acked_at in acked_writes:
        by_val[(cid, key, val)] = (seq, first)
        by_key.setdefault((cid, key), []).append((acked_at, seq))
    for cid, key, val, first in unacked_writes:
        by_val[(cid, key, val)] = (None, first)
    owed = {}  # (cid, key) -> acknowledgement times, newest apply_seq by then
    for k, hist in by_key.items():
        hist.sort()
        newest, top = [], 0
        for _at, seq in hist:
            top = max(top, seq)
            newest.append(top)
        owed[k] = ([at for at, _seq in hist], newest)
    wrong = 0
    for cid, key, got, _host, submitted, looked_up in reads:
        seen = by_val.get((cid, key, got))
        if seen is None or seen[1] > looked_up:
            wrong += 1
            continue
        times, newest = owed.get((cid, key), ((), ()))
        n = bisect.bisect_left(times, submitted)  # acknowledged before it
        if n and seen[0] is not None and seen[0] < newest[n - 1]:
            wrong += 1
    return wrong


def commit_range(replicas: int, n_acked: int, attempts: int, terms: int):
    """Where a group's commit index may lie.  The log opens with one
    membership entry per initial replica (Dragonboat's bootstrap), every
    leader term adds one no-op and every proposal attempt at most one entry;
    every acknowledged write took an entry of its own."""
    return replicas + 1 + n_acked, replicas + terms + attempts


class _Code:
    def __init__(self, name):
        self.name = name


class _Applied:
    def __init__(self, value):
        self.value = value


class _Result:
    def __init__(self, code, value):
        self.code = _Code(code)
        self.result = _Applied(value)


class Done:
    """A future that is already complete (the reference answers at once)."""

    def __init__(self, code="COMPLETED", value=0):
        self.result = _Result(code, value)
        self.completed_at = time.perf_counter()

    def done(self):
        return True

    def wait(self, timeout=None):
        return self.result


class Busy(Exception):
    pass


class ReferenceCluster:
    busy_errors = (Busy,)

    def __init__(self, groups: int, replicas: int, rtt_s: float, seed: int = 0,
                 broken: str = None, lag: int = 4, loss: float = 1 / 64,
                 key_bytes: int = 8, value_bytes: int = 8):
        if broken is not None and broken not in BROKEN:
            raise ValueError(f"unknown broken guarantee {broken!r}")
        self.groups, self.replicas, self.rtt_s = groups, replicas, rtt_s
        self.key_bytes, self.value_bytes = key_bytes, value_bytes
        self.cids = list(range(1, groups + 1))
        self.broken, self.lag, self.loss = broken, lag, loss
        self.rng = random.Random(seed)
        self.log = {cid: [] for cid in self.cids}
        self.kv = {cid: [{} for _ in range(replicas)] for cid in self.cids}
        self.leader = {cid: cid % replicas for cid in self.cids}
        self.phases = {}

    # ---- the surface the generator drives --------------------------------

    def leader_host(self, cid):
        return self.leader[cid]

    def refresh_leader(self, cid):
        pass

    def submit_write(self, host, cid, cmd, timeout_s):
        key, val = bytes(cmd[:self.key_bytes]), bytes(cmd[self.key_bytes:])
        self.log[cid].append((key, val))
        for r, kv in enumerate(self.kv[cid]):
            if (self.broken == "ack_before_quorum" and r != host
                    and self.rng.random() < self.loss):
                continue  # acknowledged although this replica never got it
            kv[key] = val
        return Done(value=len(self.log[cid]))

    def submit_read(self, host, cid, timeout_s):
        return Done()

    def lookup(self, host, cid, key):
        if self.broken == "stale_read":
            # a replica that has not applied the newest ``lag`` entries
            behind = dict(self.log[cid][: max(len(self.log[cid]) - self.lag, 0)])
            return behind.get(key)
        return self.kv[cid][host].get(key)

    # ---- what the comparison reads ----------------------------------------

    def wait_converged(self, timeout_s):
        return 0.0

    def replica_contents(self, cid):
        return self.kv[cid]

    def device_commit(self):
        return {cid: self.replicas + 1 + len(self.log[cid])
                for cid in self.cids}

    def terms_seen(self, cid):
        return 1

    def leader_changes(self, t0, t1):
        return []

    def sampled_traces(self):
        return []

    def state_leaves(self):
        return []

    def stop(self):
        pass
