"""The generator against a fake host that drops, times out and refuses."""
import time

import pytest

from benchmark.generator import READ, WRITE, Generator
from benchmark.reference.kv import Busy, Done, ReferenceCluster


class Later:
    """A future that completes ``delay`` seconds after it was made, or never."""

    def __init__(self, code, delay, value=0):
        self._done = Done(code, value)
        self._at = None if delay is None else time.perf_counter() + delay

    def done(self):
        return self._at is not None and time.perf_counter() >= self._at

    @property
    def result(self):
        return self._done.result

    @property
    def completed_at(self):
        return self._at

    def wait(self, timeout=None):
        time.sleep(min(timeout or 0.001, 0.001))
        return self.result


class FlakyCluster(ReferenceCluster):
    """Answers after 2 ms; every 5th attempt is DROPPED, every 7th is
    refused (busy), every 11th never answers (the client's clock times it
    out); group 3 never acknowledges a write at all."""

    def __init__(self, groups=6):
        super().__init__(groups, 3, rtt_s=0.002)
        self.n = 0
        self.submitted = {WRITE: 0, READ: 0}

    def _flaky(self, kind, ok):
        self.n += 1
        self.submitted[kind] += 1
        if self.n % 7 == 0:
            raise Busy()
        if self.n % 5 == 0:
            return Later("DROPPED", 0.002)
        if self.n % 11 == 0:
            return Later("TIMEOUT", None)
        return ok()

    def submit_write(self, host, cid, cmd, timeout_s):
        if cid == 3:
            self.submitted[WRITE] += 1
            return Later("DROPPED", 0.001)

        def ok():
            done = super(FlakyCluster, self).submit_write(
                host, cid, cmd, timeout_s)
            return Later("COMPLETED", 0.002, done.result.result.value)
        return self._flaky(WRITE, ok)

    def submit_read(self, host, cid, timeout_s):
        return self._flaky(READ, lambda: Later("COMPLETED", 0.002))


TRAFFIC = {
    "closed": {"loop": "closed", "read_share": 0.0, "inflight_per_group": 2,
               "attempt_timeout_s": 0.03, "deadline_s": 0.4, "warmup_s": 0.1},
    "open": {"loop": "open", "read_share": 0.9, "rate_ops_per_s": 600.0,
             "keys_per_group": 4, "read_host": "any",
             "read_newest_share": 0.5, "attempt_timeout_s": 0.03,
             "deadline_s": 0.4, "warmup_s": 0.1},
}


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_retries_inside_a_deadline_and_abandons_nothing(loop):
    cluster = FlakyCluster()
    serials, known = {}, {}
    pre = Generator(cluster, TRAFFIC[loop], 1, 0, serials, known)
    pre.deadline_s, pre.timeout_grace_s = 0.3, 0.01
    pre = pre.prefill()
    # group 3 acknowledges nothing: its prefill write fails at the deadline
    assert {w[0] for w in pre.unacked_writes} == {3}
    # every key of every other group's key set has an acknowledged write
    keys = TRAFFIC[loop].get("keys_per_group", 1)
    assert all(len(known[c]) == keys for c in cluster.cids if c != 3)
    known[3] = [b"k" * 8]  # so reads of group 3 have a key to ask for
    gen = Generator(cluster, TRAFFIC[loop], 7, 0.6, serials, known)
    gen.timeout_grace_s = 0.01
    out = gen.run()

    n_ops = len(out.lat[WRITE]) + len(out.lat[READ])
    assert out.attempted == n_ops > 50
    if loop == "open":
        assert out.attempted == 360  # rate x seconds, whatever the seed
        assert len(out.lat[WRITE]) == 36
    # every operation of the window reached an outcome: nothing abandoned
    acked_in_window = sum(1 for lat in out.lat[WRITE] + out.lat[READ]
                          if lat < gen.deadline_s)
    assert acked_in_window + out.failed == out.attempted
    assert out.inflight_at_end > 0  # the drain had work to do
    # only group 3's writes fail, and they count as the deadline
    assert out.failed > 0
    assert out.failed == sum(1 for lat in out.lat[WRITE]
                             if lat == gen.deadline_s)
    assert all(w[0] == 3 for w in out.unacked_writes)
    assert all(w[0] != 3 for w in out.acked_writes)
    # drops, refusals and timeouts were retried, and each is on the record
    codes = {ev[1] for ev in out.events}
    assert {"DROPPED", "Busy", "TIMEOUT_BY_CLIENT_CLOCK"} <= codes
    assert out.retries > 0
    ok_ops = [lat for lat in out.lat[READ] + out.lat[WRITE]
              if lat < gen.deadline_s]
    # latency includes the retries: a retried operation took at least one
    # rtt more than the 2 ms an attempt takes
    assert ok_ops and 0.004 < max(ok_ops) < gen.deadline_s
    # a rate counts acknowledgements that arrived inside the window
    assert 0 < out.acks_in_window <= len(out.ack_at)
    assert out.acks_in_window == sum(
        1 for t in out.ack_at if out.t0 <= t < out.t_end)
    if loop == "open":
        # reads go to every host, and every one names the host it asked, the
        # instant it was submitted and the instant of its lookup
        assert {r[3] for r in out.reads} == {0, 1, 2}
        assert all(r[4] <= r[5] for r in out.reads)
        # a group's small key set is overwritten, the value naming the write
        per_key = {}
        for cid, key, val, *_ in out.acked_writes:
            per_key.setdefault((cid, key), set()).add(val)
        assert max(len(v) for v in per_key.values()) > 1
        assert len({k for c, k in per_key if c == 1}) <= 4


def test_same_seed_same_schedule_and_every_seed_the_same_work():
    from benchmark.generator import open_schedule

    cids = list(range(1, 49))
    a = open_schedule(TRAFFIC["open"], cids, 2**31 + 7, 2.0, 0.5)
    b = open_schedule(TRAFFIC["open"], cids, 2**31 + 7, 2.0, 0.5)
    c = open_schedule(TRAFFIC["open"], cids, 5, 2.0, 0.5)
    assert a == b and a != c
    for s in (a, c):
        window = [x for x in s if x[0] >= 0]
        assert len(window) == 1200
        assert sum(1 for x in window if x[1] == WRITE) == 120
        per_group = {}
        for _due, _kind, cid in window:
            per_group[cid] = per_group.get(cid, 0) + 1
        assert set(per_group.values()) == {25}
        assert 0 <= window[0][0] and window[-1][0] <= 2.0


def test_wrong_reads_is_a_linearizability_check():
    from benchmark.reference.kv import wrong_reads

    k = b"key00000"
    # (cid, key, val, apply_seq, first_submitted, acked_at)
    w1 = (1, k, b"v1", 1, 0.0, 1.0)
    w2 = (1, k, b"v2", 2, 2.0, 3.0)
    retried = (1, k, b"v0", 3, 0.5, 5.0)  # applied early unseen, then again
    acked = [w1, w2, retried]
    unacked = [(1, k, b"vx", 4.0)]

    def wrong(got, submitted, looked_up=9.0):
        return wrong_reads(acked, unacked,
                           [(1, k, got, 2, submitted, looked_up)])

    assert wrong(b"v1", 1.5) == 0          # the newest acknowledged by then
    assert wrong(b"v2", 2.5) == 0          # concurrent with w2: either is fine
    assert wrong(b"v1", 2.5) == 0
    assert wrong(b"v1", 3.5) == 1          # w2 was acknowledged: stale
    assert wrong(None, 1.5) == 1           # nothing at all
    assert wrong(b"zz", 1.5) == 1          # a value nobody wrote
    assert wrong(b"v2", 1.5, 1.8) == 1     # from the future
    assert wrong(b"v0", 3.5) == 0          # a retried write, seen early
    assert wrong(b"v2", 5.5) == 1          # ... and owed once acknowledged
    assert wrong(b"vx", 4.5) == 0          # never acknowledged: may apply
    assert wrong(b"vx", 3.5, 3.9) == 1     # but not before it was proposed
