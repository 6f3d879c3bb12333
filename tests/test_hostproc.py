"""Multi-process host plane (hostproc/, ISSUE 12) differential suite.

Contracts under test:

- SPSC shared-memory rings: record integrity across wraparound, the
  record-size guard, and sustained-full backpressure surfacing as
  :class:`SystemBusyError`;
- worker round trips: the encode worker matches the inline
  ``get_encoded_payload`` oracle byte-for-byte; worker-reported errors
  surface as :class:`WorkerError`;
- the apply tier: ``ProcStateMachine`` ≡ the in-process machine on
  update results, lookup, snapshot round trips and the self-rebase
  bound; kill -9 mid-stream falls back in-process with every command
  applied EXACTLY once;
- the WAL worker: appends land the same bytes the in-process journal
  writes, an (injected) fsync failure fails the flush cycle — nothing
  acked — and heals on retry; a dead worker degrades to the in-process
  append+fsync; an ErrorFS host keeps the sink DETACHED so fault
  injection still reaches the in-process durability point;
- workers-off structural identity: ``host_workers=0`` constructs none
  of it — the compartmentalized plane is bit-identical to the
  pre-hostproc build;
- live stack: workers-on ≡ workers-off on completion values and apply
  order, and kill -9 under load loses no acks and duplicates none.
"""
import io
import os
import signal
import threading
import time

import pytest

from dragonboat_tpu import Config, NodeHostConfig, Result
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.hostproc import spawnable_spec
from dragonboat_tpu.hostproc import workers as wp
from dragonboat_tpu.hostproc.control import (
    HostProcPlane,
    RingClient,
    WalSink,
    WorkerError,
    WorkerGone,
)
from dragonboat_tpu.hostproc.rings import ShmRing
from dragonboat_tpu.hostproc.sm import ProcStateMachine
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.requests import SystemBusyError
from dragonboat_tpu.testing import CounterSM
from dragonboat_tpu.transport import ChanRouter, ChanTransport

RTT_MS = 5
CID = 910


class WorkerKVSM:
    """Module-level spawnable SM with observable apply ORDER: value is
    the running count, data echoes the command reversed — any reorder,
    loss or duplication anywhere in the pipeline shows up in either."""

    __hostproc_spawnable__ = True

    def __init__(self, cluster_id, node_id):
        self.log = []

    def update(self, cmd):
        self.log.append(bytes(cmd))
        return Result(value=len(self.log), data=bytes(cmd)[::-1])

    def lookup(self, query):
        return list(self.log)

    def save_snapshot(self, w, files, done):
        blob = b"\x00".join(self.log)
        w.write(len(blob).to_bytes(8, "little") + blob)

    def recover_from_snapshot(self, r, files, done):
        n = int.from_bytes(r.read(8), "little")
        blob = r.read(n)
        self.log = blob.split(b"\x00") if blob else []

    def close(self):
        pass


# ----------------------------------------------------------------------
# rings: wraparound integrity + sustained-full backpressure
# ----------------------------------------------------------------------


def test_ring_wraparound_integrity():
    import random

    rng = random.Random(7)
    r = ShmRing(capacity=256)
    try:
        sent = []
        for i in range(4000):
            blob = bytes([i % 251]) * rng.randint(0, 60)
            while not r.push(blob):
                assert r.pop() == sent.pop(0)
            sent.append(blob)
            if rng.random() < 0.5:
                got = r.pop()
                if got is not None:
                    assert got == sent.pop(0)
        while sent:
            assert r.pop() == sent.pop(0)
        assert r.pop() is None
        assert r.depth() == 0
    finally:
        r.close()


def _flip_tail(name, a, b, secs):
    ring = ShmRing(name=name, create=False)
    deadline = time.time() + secs
    while time.time() < deadline:
        for _ in range(500):
            ring._store(0, a)
            ring._store(0, b)
    ring.close()


def test_ring_cursor_is_never_read_torn():
    """A cursor is published in one store: while another process writes
    ``tail`` over and over, this one reads only values that were written
    (a store made of a zero-fill and a write reads as 0 in between, and
    a consumer then pops a record that was never pushed)."""
    import multiprocessing as mp

    a, b = 0x00FFFFFF, 0x01000000
    ring = ShmRing(capacity=4096)
    try:
        ring._store(0, a)
        w = mp.get_context("spawn").Process(
            target=_flip_tail, args=(ring.name, a, b, 2.0), daemon=True
        )
        w.start()
        seen = set()
        while w.is_alive():
            seen.update(ring._load(0) for _ in range(2000))
        w.join()
        assert w.exitcode == 0
        assert seen == {a, b}, [hex(v) for v in sorted(seen)][:6]
    finally:
        ring.close()


def test_ring_rejects_oversized_record():
    r = ShmRing(capacity=4096)
    try:
        with pytest.raises(ValueError):
            r.push(b"x" * (r.cap + 1))
    finally:
        r.close()


class _FakePlane:
    def __init__(self):
        self._obs = None
        self.busy = 0
        self.fallbacks = 0

    def _count_busy(self, role):
        self.busy += 1

    def _count_fallback(self, role):
        self.fallbacks += 1


def test_ring_sustained_full_raises_system_busy():
    """A request ring nobody drains stays full past the busy window —
    the client surfaces SystemBusy, the ingress backpressure contract."""
    plane = _FakePlane()
    c = RingClient(
        plane, "encode", ShmRing(capacity=4096), ShmRing(capacity=4096), 0
    )
    c.alive = True
    try:
        while c.req.push(b"z" * 1500):  # no consumer: fill the ring
            pass
        with pytest.raises(SystemBusyError):
            c.call(wp.OP_PING, b"z" * 1500, busy_timeout=0.05)
        assert plane.busy == 1
    finally:
        c.req.close()
        c.resp.close()


def test_spawnable_spec_rules():
    assert spawnable_spec(WorkerKVSM) == "test_hostproc:WorkerKVSM"
    assert spawnable_spec(CounterSM) == "dragonboat_tpu.testing:CounterSM"

    class Local:
        __hostproc_spawnable__ = True

    assert spawnable_spec(Local) is None  # <locals> qualname
    assert spawnable_spec(lambda c, n: None) is None  # not opted in


# ----------------------------------------------------------------------
# one shared plane for the worker round-trip suites (spawn amortized)
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def plane():
    p = HostProcPlane(workers=2, encode_lanes=2)
    yield p
    p.stop()


def test_encode_worker_matches_inline_oracle(plane):
    from dragonboat_tpu.rsm.encoded import get_encoded_payload

    lane = plane.encode_lane(0)
    cmds = [b"a", b"hello world", b"x" * 3000, b"\x00\xff" * 17]
    for ct in (0, 1):  # no-compression, snappy
        encs = lane.encode(ct, cmds)
        assert encs == [get_encoded_payload(ct, c) for c in cmds]


def test_worker_error_surfaces(plane):
    c = plane.apply_lanes[0]
    with pytest.raises(WorkerError):
        c.call(wp.OP_SM_UPDATE, (0).to_bytes(8, "little") * 2 + b"x")


def test_proc_sm_differential_and_rebase(plane):
    spec = spawnable_spec(WorkerKVSM)
    sm = ProcStateMachine(plane, spec, 42, 1, WorkerKVSM)
    oracle = WorkerKVSM(42, 1)
    assert sm.device_bound
    # force frequent self-rebase so the redo buffer's snapshot path runs
    sm.REBASE_CMDS = 4
    for i in range(25):
        cmd = b"cmd-%d" % i
        r, ro = sm.update(cmd), oracle.update(cmd)
        assert (r.value, r.data) == (ro.value, ro.data), i
    assert sm.lookup(None) == oracle.lookup(None)
    assert len(sm._redo) < 25  # rebase kept the buffer bounded
    # snapshot stream is byte-identical to the plain machine's
    w1, w2 = io.BytesIO(), io.BytesIO()
    sm.save_snapshot(w1, [], None)
    oracle.save_snapshot(w2, [], None)
    assert w1.getvalue() == w2.getvalue()
    # recover round trip into a fresh proxy
    sm2 = ProcStateMachine(plane, spec, 43, 1, WorkerKVSM)
    sm2.recover_from_snapshot(io.BytesIO(w1.getvalue()), [], None)
    assert sm2.lookup(None) == oracle.lookup(None)
    r, ro = sm2.update(b"after"), oracle.update(b"after")
    assert (r.value, r.data) == (ro.value, ro.data)
    sm.close()
    sm2.close()


def test_wal_sink_append_bytes_and_injected_fsync_failure(
    plane, tmp_path
):
    path = str(tmp_path / "j" / "host-journal.wal")
    sink = WalSink(plane.wal_lane)
    assert sink.append(path, b"REC-1|") is True
    assert sink.append(path, b"REC-2|") is True
    with open(path, "rb") as f:
        assert f.read() == b"REC-1|REC-2|"
    # injected fsync failure: the op RAN and FAILED — WorkerError (an
    # OSError) propagates so the flush cycle fails and nothing is acked
    plane.inject(plane.wal_lane.worker_id, {"wal_fail_fsyncs": 1})
    with pytest.raises(OSError):
        sink.append(path, b"REC-3|")
    # healed: the retry lands durably
    assert sink.append(path, b"REC-4|") is True
    # size-guarded truncate: a STALE expected size (an abandoned
    # truncate executing after further appends) is REFUSED — the
    # journal's caller falls back to its own in-process truncate —
    # while the correct size truncates durably
    assert sink.truncate(path, 1) is False
    with open(path, "rb") as f:
        assert f.read() != b""
    assert sink.truncate(path, os.path.getsize(path)) is True
    with open(path, "rb") as f:
        assert f.read() == b""


# ----------------------------------------------------------------------
# kill -9: fallback, exactly-once, bounded respawn
# ----------------------------------------------------------------------


def test_kill9_proc_sm_fallback_exactly_once():
    p = HostProcPlane(workers=1, encode_lanes=1)
    try:
        spec = spawnable_spec(WorkerKVSM)
        sm = ProcStateMachine(p, spec, 7, 1, WorkerKVSM)
        sent = []
        for i in range(10):
            cmd = b"pre-%d" % i
            sent.append(cmd)
            assert sm.update(cmd).value == i + 1
        os.kill(p.worker_pid(0), signal.SIGKILL)
        deadline = time.time() + 10
        while p.alive_count() and time.time() < deadline:
            time.sleep(0.02)
        # mid-flight command applies exactly once in the rebuilt state
        sent.append(b"during")
        r = sm.update(b"during")
        assert r.value == 11 and r.data == b"gnirud"
        assert not sm.device_bound
        assert sm.lookup(None) == sent  # nothing lost, nothing doubled
        # the monitor respawns the worker (bounded), the fallen-back
        # proxy stays in-process, and a FRESH proxy can bind remotely
        deadline = time.time() + 15
        while p.alive_count() == 0 and time.time() < deadline:
            time.sleep(0.05)
        assert p.alive_count() == 1
        assert p.restarts_total == 1
        sm2 = ProcStateMachine(p, spec, 8, 1, WorkerKVSM)
        assert sm2.device_bound
        assert sm2.update(b"fresh").value == 1
        st = p.stats()
        assert st["fallbacks"].get("apply", 0) >= 1
    finally:
        p.stop()


def test_kill9_wal_sink_falls_back_in_process(tmp_path):
    p = HostProcPlane(workers=1, encode_lanes=1)
    try:
        path = str(tmp_path / "host-journal.wal")
        sink = WalSink(p.wal_lane)
        assert sink.append(path, b"A|") is True
        os.kill(p.worker_pid(0), signal.SIGKILL)
        deadline = time.time() + 10
        while p.wal_lane.alive and time.time() < deadline:
            time.sleep(0.02)
        # dead worker: the sink reports unavailable — the journal's
        # caller falls back to its own in-process write+fsync
        assert sink.append(path, b"B|") is False
    finally:
        p.stop()


# ----------------------------------------------------------------------
# GroupCommitWAL through the WAL worker: nothing acked before fsync
# ----------------------------------------------------------------------


def test_wal_worker_flush_failure_reaches_riders(tmp_path, monkeypatch):
    """The journaled flush cycle rides the WAL worker; an injected
    worker-side fsync failure fails the WHOLE cycle (every rider sees
    the error — nothing acked), and the healed retry lands durably with
    a journal a fresh open replays consistently."""
    from dragonboat_tpu.hostplane import GroupCommitWAL
    from dragonboat_tpu.logdb import open_logdb
    from dragonboat_tpu.wire import Entry as WEntry, State, Update

    monkeypatch.setenv("DBTPU_HOSTPROC_OFFLOAD", "1")
    p = HostProcPlane(workers=1, encode_lanes=1)
    ldb = open_logdb(str(tmp_path), shards=2)
    try:
        wal = GroupCommitWAL(
            ldb, journal_mode="force", hostproc=p
        )
        assert wal.status()["worker_sink"] is True
        # two shards in one cycle => the cycle rides the journal (the
        # single-batch/empty-journal rule would take the classic path)
        ud = Update(
            cluster_id=5, node_id=1,
            state=State(term=3, vote=1, commit=7),
            entries_to_save=[WEntry(index=7, term=3, key=1, cmd=b"v")],
        )
        ud2 = Update(
            cluster_id=4, node_id=1,
            state=State(term=2, vote=1, commit=1),
            entries_to_save=[WEntry(index=1, term=2, key=2, cmd=b"w")],
        )
        p.inject(0, {"wal_fail_fsyncs": 1})
        with pytest.raises(OSError):
            wal.flush([ud, ud2])
        # heal: the caller's retry path re-flushes and is acked
        wal.flush([ud, ud2])
        assert ldb.journal.appends >= 1
        assert p.stats()["lanes"]["wal"]["calls"] > 0
    finally:
        ldb.close()
        p.stop()
    # both the failed and the healed append may sit in the journal —
    # replay is idempotent and must land exactly the acked state
    ldb2 = open_logdb(str(tmp_path), shards=2)
    try:
        st = ldb2.read_raft_state(5, 1, 0)
        assert st is not None and st.state.commit == 7
        ents, _ = ldb2.iterate_entries([], 0, 5, 1, 7, 8, 1 << 30)
        assert [e.index for e in ents] == [7]
    finally:
        ldb2.close()


def test_error_fs_keeps_wal_sink_detached(tmp_path, monkeypatch):
    """An ErrorFS host must keep fault injection wired to the ACTUAL
    durability point: the vfs cannot cross the process boundary, so the
    sink stays detached and the in-process journal path (the existing
    test_hostplane nothing-acked-before-fsync suite) keeps covering it."""
    from dragonboat_tpu import vfs
    from dragonboat_tpu.hostplane import GroupCommitWAL
    from dragonboat_tpu.logdb import open_logdb

    monkeypatch.setenv("DBTPU_HOSTPROC_OFFLOAD", "1")
    inj = vfs.Injector(lambda op, path: False)
    efs = vfs.ErrorFS(vfs.OSFS(), inj)
    p = HostProcPlane(workers=1, encode_lanes=1)
    ldb = open_logdb(str(tmp_path), shards=2)
    try:
        wal = GroupCommitWAL(
            ldb, journal_mode="force", hostproc=p, fs=efs
        )
        assert wal.status()["worker_sink"] is False
    finally:
        ldb.close()
        p.stop()


# ----------------------------------------------------------------------
# WAL probe strategy (ISSUE 12 satellite): modes, reprobe, status
# ----------------------------------------------------------------------


def test_wal_journal_modes_and_reprobe(tmp_path):
    from dragonboat_tpu.hostplane import GroupCommitWAL
    from dragonboat_tpu.logdb import open_logdb

    ldb = open_logdb(str(tmp_path), shards=2)
    try:
        off = GroupCommitWAL(ldb, journal_mode="off")
        assert off.status()["mode"] == "off"
        assert off.status()["journal"] is False
        assert off.status()["engaged"] is False
    finally:
        ldb.close()
    ldb = open_logdb(str(tmp_path / "b"), shards=2)
    try:
        forced = GroupCommitWAL(ldb, journal_mode="force")
        st = forced.status()
        assert st["mode"] == "force" and st["engaged"] is True
        # forced mode RE-probes at construction (the satellite fix: one
        # polluted startup sample must not pin the pacing window)
        assert st["probes"] >= 2
        p1 = st["probe_ms"]
        p2 = forced.reprobe() * 1e3
        assert forced.status()["probes"] >= 3
        assert p2 >= 0.0 and p1 >= 0.0
        # this box's disk fsyncs sub-ms: auto mode keeps classic saves
        auto = GroupCommitWAL(ldb, journal_mode="auto")
        if auto.status()["probe_ms"] < 0.5:
            assert auto.status()["engaged"] is False
    finally:
        ldb.close()


# ----------------------------------------------------------------------
# live stack
# ----------------------------------------------------------------------


def _mk_host(addr, router, tmpdir, host_workers=0, trace=0, **expert_kw):
    return NodeHost(
        NodeHostConfig(
            node_host_dir=tmpdir,
            rtt_millisecond=RTT_MS,
            raft_address=addr,
            trace_sample_every=trace,
            raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                s, rh, ch, router=router
            ),
            expert=ExpertConfig(
                host_compartments=True, host_workers=host_workers,
                **expert_kw,
            ),
        )
    )


def _wait_leader(nh, cid, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        lid, ok = nh.get_leader_id(cid)
        if ok:
            return lid
        time.sleep(0.02)
    raise AssertionError("no leader")


def _drive(nh, cid, n):
    s = nh.get_noop_session(cid)
    states = [nh.propose(s, b"s%d" % i, timeout=10.0) for i in range(n)]
    states += nh.propose_batch(s, [b"b%d" % i for i in range(n)], timeout=10.0)
    out = []
    for rs in states:
        r = rs.wait(10.0)
        assert r.completed, r.code
        out.append((r.result.value, bytes(r.result.data)))
    return out


def test_workers_off_structural_identity(tmp_path):
    """host_workers=0: no hostproc plane, no encode lanes, no journal
    sink, the user SM unwrapped — the compartmentalized plane is the
    pre-hostproc build exactly."""
    router = ChanRouter()
    nh = _mk_host("hw:1", router, str(tmp_path / "nh"))
    try:
        assert nh.hostproc is None
        assert nh.hostplane.hostproc is None
        assert nh.hostplane.ingress._encoders is None
        assert nh.hostplane.wal._journal.sink is None
        nh.start_cluster(
            {1: "hw:1"}, False, WorkerKVSM,
            Config(cluster_id=CID, node_id=1, election_rtt=10,
                   heartbeat_rtt=1),
        )
        _wait_leader(nh, CID)
        assert type(nh.get_node(CID).sm.managed.sm) is WorkerKVSM
        ws = nh.wal_status()
        assert ws is not None and ws["worker_sink"] is False
    finally:
        nh.stop()


def test_live_differential_workers_on_vs_off(tmp_path, monkeypatch):
    """Workers-on ≡ workers-off on completion values, apply order and
    payload echoes — with the apply tier REALLY remote (proxy bound,
    worker round trips observed)."""
    monkeypatch.setenv("DBTPU_HOSTPROC_OFFLOAD", "1")
    results = {}
    for mode, workers in (("off", 0), ("on", 2)):
        router = ChanRouter()
        nh = _mk_host(
            f"hw{mode}:1", router, str(tmp_path / f"nh-{mode}"),
            host_workers=workers, host_wal_journal="force",
            trace=1 if workers else 0,
        )
        try:
            nh.start_cluster(
                {1: f"hw{mode}:1"}, False, WorkerKVSM,
                Config(cluster_id=CID, node_id=1, election_rtt=10,
                       heartbeat_rtt=1),
            )
            _wait_leader(nh, CID)
            if workers:
                usm = nh.get_node(CID).sm.managed.sm
                assert isinstance(usm, ProcStateMachine)
                assert usm.device_bound
                assert nh.wal_status()["worker_sink"] is True
            results[mode] = _drive(nh, CID, 20)
            if workers:
                st = nh.hostproc.stats()
                assert st["lanes"]["apply"]["calls"] >= 40
                assert st["restarts"] == 0
                # ipc trace stage (ISSUE 12 satellite): a ring-staged
                # burst rode the encode worker, so its sampled traces
                # stamp the shared-memory handoff BEFORE ingress
                s2 = nh.get_noop_session(CID)
                brs = nh.propose_batch(
                    s2, [b"t%d" % i for i in range(8)], timeout=10.0
                )
                for rs in brs:
                    assert rs.wait(10.0).completed
                stamped = [
                    [e[0] for e in rs.trace.events]
                    for rs in brs if rs.trace is not None
                ]
                assert stamped and any("ipc" in ev for ev in stamped)
                for ev in stamped:
                    if "ipc" in ev:
                        assert ev.index("ipc") < ev.index("ingress")
                assert st["lanes"]["encode"]["calls"] >= 1 or (
                    nh.hostproc.stats()["lanes"]["encode"]["calls"] >= 1
                )
        finally:
            nh.stop()
    assert results["on"] == results["off"]


def test_live_kill9_under_load_no_lost_or_duplicate_acks(
    tmp_path, monkeypatch
):
    """kill -9 the (single) worker mid-load: every acked proposal is
    applied exactly once — the proxy's snapshot+redo rebuild — and the
    plane keeps serving (fallen back) afterwards."""
    monkeypatch.setenv("DBTPU_HOSTPROC_OFFLOAD", "1")
    router = ChanRouter()
    nh = _mk_host(
        "hwk:1", router, str(tmp_path / "nh"), host_workers=1,
        host_wal_journal="force",
    )
    try:
        nh.start_cluster(
            {1: "hwk:1"}, False, WorkerKVSM,
            Config(cluster_id=CID, node_id=1, election_rtt=10,
                   heartbeat_rtt=1),
        )
        _wait_leader(nh, CID)
        usm = nh.get_node(CID).sm.managed.sm
        assert isinstance(usm, ProcStateMachine) and usm.device_bound
        s = nh.get_noop_session(CID)
        acked = []
        stop = threading.Event()
        errs = []

        def loader():
            i = 0
            while not stop.is_set():
                try:
                    r = nh.sync_propose(s, b"k%d" % i, timeout=10.0)
                    acked.append((i, r.value))
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
                    return
                i += 1

        t = threading.Thread(target=loader)
        t.start()
        deadline = time.time() + 5
        while len(acked) < 10 and time.time() < deadline:
            time.sleep(0.02)
        os.kill(nh.hostproc.worker_pid(0), signal.SIGKILL)
        deadline = time.time() + 5
        while usm.device_bound and time.time() < deadline:
            time.sleep(0.02)
        # keep loading through the fallback window, then stop
        time.sleep(0.5)
        stop.set()
        t.join(15)
        assert not errs, errs
        assert len(acked) >= 10
        # exactly-once: result values are the strictly increasing apply
        # counter with no gaps and no repeats, and the surviving state
        # holds exactly the acked commands in order
        assert [v for _, v in acked] == list(range(1, len(acked) + 1))
        log = nh.sync_read(CID, None, timeout=10.0)
        assert log[: len(acked)] == [b"k%d" % i for i, _ in acked]
        assert not usm.device_bound
        st = nh.hostproc.stats()
        assert st["fallbacks"].get("apply", 0) >= 1
        # still serving after the fallback
        r = nh.sync_propose(s, b"post", timeout=10.0)
        assert r.value == len(log) + 1
    finally:
        nh.stop()
