"""ISSUE 38: a periodic save, cut at every fs operation and LogDB commit.

One save task of the real ``Node._save_snapshot_task`` runs over a ``MemFS``
under ``ErrorFS``: the image, the flag file, the directories AND the LogDB's
WAL (``WalKV`` over the same fs) fail from operation ``k`` on, for every
``k`` of the save, which is the disk a crash at ``k`` leaves.  Then the
replica "restarts" from that disk (``process_orphans``, ``LogReader.load``,
``sm.recover`` from the newest recorded snapshot, the log behind it
replayed) and must hold what it held: the newest recorded snapshot
validates, every entry above ``snapshot.index - compaction_overhead`` is
still in the LogDB, no record points at a missing directory, no temp or
unrecorded directory is left, and the recovered state machine equals the
one that saved.  The orders the save's two LogDB batches have to keep
(the record and the stale records' deletes; then the log's range delete)
each have a case that fails when the order is swapped, and at every
operation of a save the LogReader's marker stays at or behind its
snapshot: a follower behind the marker can be sent an image.
"""
import os
import threading

import pytest

from dragonboat_tpu import vfs
from dragonboat_tpu.logdb.kv import WalKV
from dragonboat_tpu.logdb.logreader import LogReader
from dragonboat_tpu.logdb.rdb import RDB
from dragonboat_tpu.logdb.sharded import ShardedDB
from dragonboat_tpu.node import Node
from dragonboat_tpu.raft.log import CompactedError, SnapshotOutOfDateError
from dragonboat_tpu.rsm import StateMachine, Task, from_regular_sm
from dragonboat_tpu.rsm.snapshotio import BLOCK_SIZE, validate_snapshot_file
from dragonboat_tpu.rsm.statemachine import SSReqType, SSRequest
from dragonboat_tpu.server.snapshotenv import (
    is_final_snapshot_dir,
    is_temp_snapshot_dir,
    snapshot_index_from_dir,
)
from dragonboat_tpu.snapshotter import Snapshotter
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.wire import Entry, Membership, State, Update

CID, NID = 1, 1
OVERHEAD = 3
PER_SAVE = 10  # entries applied before each save
ROOT = "/snaps"


class KV(IStateMachine):
    """cmd = b"k v"; ``pad`` bytes ride every image (a spilled one: over a
    block)."""

    def __init__(self, pad=0):
        self.kv = {}
        self.pad = pad

    def update(self, cmd):
        k, v = cmd.decode().split(" ")
        self.kv[k] = v
        return Result(value=len(self.kv))

    def lookup(self, query):
        return self.kv.get(query)

    def save_snapshot(self, w, files, done):
        body = repr(sorted(self.kv.items())).encode()
        w.write(len(body).to_bytes(8, "little") + body)
        for i in range(0, self.pad, 400_000):
            w.write(b"\xa5" * min(400_000, self.pad - i))

    def recover_from_snapshot(self, r, files, done):
        import ast

        n = int.from_bytes(r.read(8), "little")
        self.kv = dict(ast.literal_eval(r.read(n).decode()))
        assert len(r.read(-1)) == self.pad


class Proxy:
    def node_ready(self):
        pass

    def apply_update(self, *a):
        pass

    def apply_config_change(self, *a):
        pass

    def restore_remotes(self, ss):
        pass

    def should_stop(self):
        return False


class Crash:
    """Counts every injectable operation once armed and fails each one
    past ``limit``: the process died at operation ``limit`` + 1."""

    def __init__(self):
        self.armed = False
        self.n = 0
        self.limit = None
        self.ops = []
        self.probe = None  # called at every operation, in the save's thread

    def policy(self, op, path):
        if not self.armed:
            return False
        if self.probe is not None:
            self.probe()
        self.n += 1
        self.ops.append((op, os.path.basename(path)))
        return self.limit is not None and self.n > self.limit


class _Events:
    def publish(self, ev):
        pass


class _NH:
    sys_events = _Events()


class _Pending:
    def notify(self, r):
        pass


class _Config:
    compaction_overhead = OVERHEAD
    snapshot_entries = PER_SAVE


class World:
    """One replica's disk (``base``) and what runs over it."""

    def __init__(self, base, pad=0, crash=None):
        self.base = base
        self.crash = crash
        fs = vfs.ErrorFS(base, vfs.Injector(crash.policy)) if crash else base
        self.fs = fs
        base.makedirs("/db/shard-00", exist_ok=True)
        base.makedirs(ROOT, exist_ok=True)
        self.db = ShardedDB([RDB(WalKV("/db/shard-00", fs=fs))])
        self.snap = Snapshotter(ROOT, CID, NID, self.db, fs=fs)
        self.snap.process_orphans()
        self.logreader = LogReader.load(CID, NID, self.db)
        self.user = KV(pad)
        self.sm = StateMachine(
            from_regular_sm(self.user), self.snap, Proxy(), CID, NID)
        self.last = 0
        self.node = self._bare_node()

    def _bare_node(self):
        n = Node.__new__(Node)
        n.cluster_id, n.node_id = CID, NID
        n.nh = _NH()
        n.config = _Config()
        n.sm, n.snapshotter = self.sm, self.snap
        n.logdb, n.logreader = self.db, self.logreader
        n.fastlane, n.fast_lane, n._natsm_attached = None, False, False
        n.replica_obs = None
        n.pending_snapshot = _Pending()
        n._snapshotting = threading.Semaphore(0)
        n._compacted_to, n._compacted_to_mu = 0, threading.Lock()
        return n

    def apply(self, count):
        ents = [
            Entry(term=1, index=i, cmd=b"k%d v%d" % (i % 7, i))
            for i in range(self.last + 1, self.last + count + 1)
        ]
        self.last += count
        self.db.save_raft_state([Update(
            cluster_id=CID, node_id=NID, entries_to_save=ents,
            state=State(term=1, vote=NID, commit=self.last))])
        self.logreader.append(ents)
        self.sm.handle([Task(cluster_id=CID, node_id=NID, entries=ents)])

    def save(self):
        t = Task(cluster_id=CID, node_id=NID, save=True,
                 ss_request=SSRequest(type=SSReqType.PERIODIC))
        self.node._save_snapshot_task(t, None)

    def close(self):
        self.db.close()


def world_before_the_save(pad, prior, crash=None):
    """``prior`` clean saves, then ten more entries applied: the save
    under test is the next call."""
    w = World(vfs.MemFS(), pad, crash)
    for _ in range(prior):
        w.apply(PER_SAVE)
        w.save()
    w.apply(PER_SAVE)
    return w


def crashed_save(w, limit):
    """The save under test, failing from operation ``limit`` + 1 on."""
    w.crash.limit = limit
    w.crash.n = 0
    w.crash.armed = True
    try:
        w.save()
    except OSError:
        pass  # the LogDB batch failed: nothing catches that, as in a crash
    finally:
        w.crash.armed = False


def check_restart(w):
    """Restart from ``w``'s disk and hold it to what it acknowledged."""
    saved_kv, last = dict(w.user.kv), w.last
    reader_knew = w.logreader.snapshot().index
    w.close()
    r = World(w.base, w.user.pad)
    try:
        records = r.db.list_snapshots(CID, NID)
        recorded = {s.index for s in records}
        # the reader never knew of a snapshot whose record is not durable
        assert reader_knew == 0 or reader_knew in recorded, (
            reader_knew, recorded)
        # no record points at a missing directory
        for s in records:
            assert r.base.exists(s.filepath), s.filepath
        names = r.base.listdir(ROOT)
        assert not [n for n in names if is_temp_snapshot_dir(n)], names
        assert {snapshot_index_from_dir(n) for n in names
                if is_final_snapshot_dir(n)} == recorded
        newest = records[-1] if records else None
        lo = 1
        if newest is not None:
            assert validate_snapshot_file(newest.filepath, r.base)
            assert newest == r.logreader.snapshot()
            r.sm.recover(Task(recover=True, ss=newest))
            # the log kept behind it: every entry above index - overhead
            lo = max(1, newest.index - OVERHEAD + 1)
        ents, _ = r.db.iterate_entries([], 0, CID, NID, lo, last + 1, 1 << 62)
        assert [e.index for e in ents] == list(range(lo, last + 1))
        replay = [e for e in ents
                  if e.index > (newest.index if newest else 0)]
        r.sm.handle([Task(cluster_id=CID, node_id=NID, entries=replay)])
        assert r.sm.get_last_applied() == last
        assert r.user.kv == saved_kv
        return recorded
    finally:
        r.close()


#: injectable operations (fs calls and the WAL's write + fsync of each of
#: the two batches) of the save under test.
#: ``test_the_saves_operations_are_these`` holds the numbers to the code: a
#: PR that adds a call to the save moves them.
SCENARIOS = {
    # name: (pad, prior saves, operations)
    "buffered-first": (0, 0, 14),
    "buffered-fourth": (0, 3, 17),
    "spilled-first": (BLOCK_SIZE + 4096, 0, 19),
    "spilled-fourth": (BLOCK_SIZE + 4096, 3, 22),
}


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_the_saves_operations_are_these(name):
    pad, prior, ops = SCENARIOS[name]
    crash = Crash()
    w = world_before_the_save(pad, prior, crash)
    crashed_save(w, None)  # never fails: it counts
    assert crash.n == ops, crash.ops
    recorded = check_restart(w)
    assert max(recorded) == w.last
    assert len(recorded) == min(prior + 1, 3)


@pytest.mark.parametrize("name,k", [
    (name, k) for name, (_, _, ops) in SCENARIOS.items() for k in range(ops)
])
def test_a_save_cut_at_any_operation_restarts_whole(name, k):
    pad, prior, _ = SCENARIOS[name]
    crash = Crash()
    w = world_before_the_save(pad, prior, crash)
    before = {s.index for s in w.db.list_snapshots(CID, NID)}
    crashed_save(w, k)
    assert crash.n > k  # it was cut
    recorded = check_restart(w)
    # the save either happened or did not: never half a retention
    assert recorded in (before, (before | {w.last}) - (
        {min(before)} if len(before) == 3 else set())), (before, recorded)


# ---- the orders, each with the swap that its case catches ----------------


def _before_the_commit(w, first):
    """``first(ss)`` runs ahead of the snapshotter's commit: what the save
    does after its record is durable, done before."""
    real = w.snap.commit

    def commit(ss, env):
        first(ss)
        return real(ss, env)

    w.snap.commit = commit


def _swap_entries_before_record(w):
    _before_the_commit(
        w, lambda ss: w.db.remove_entries_to(CID, NID, ss.index - OVERHEAD))


def _swap_reader_learns_before_record(w):
    _before_the_commit(w, w.logreader.create_snapshot)
    w.logreader.create_snapshot = lambda ss: None  # the node's, after


def _swap_directory_before_record(w):
    def first(ss):
        have = w.db.list_snapshots(CID, NID)
        if len(have) >= 3:
            w.snap.remove_dirs(have[:1])

    _before_the_commit(w, first)


SWAPS = {
    "entries_leave_after_the_record": _swap_entries_before_record,
    "reader_learns_after_the_record": _swap_reader_learns_before_record,
    "record_goes_before_its_directory": _swap_directory_before_record,
}


@pytest.mark.parametrize("order", list(SWAPS))
def test_a_swapped_order_is_caught_at_some_cut(order):
    """The crash-point check has teeth: with the order swapped, some cut
    of the fourth save leaves a disk the restart check refuses; with the
    order as it is, none does (the test above)."""
    pad, prior, ops = SCENARIOS["buffered-fourth"]
    refused = []
    for k in range(ops + 4):
        crash = Crash()
        w = world_before_the_save(pad, prior, crash)
        SWAPS[order](w)
        crashed_save(w, k)
        try:
            check_restart(w)
        except AssertionError:
            refused.append(k)
    assert refused, order


@pytest.mark.parametrize("how", ["as_it_is", "refusal_ignored"])
def test_a_reader_that_refuses_to_compact_keeps_every_entry(how):
    """``logreader.compact`` runs before the LogDB drops the entries, and
    its refusal drops none; the save itself still commits."""
    w = world_before_the_save(0, 3)

    def refuse(index):
        raise CompactedError()

    w.logreader.compact = refuse
    if how == "refusal_ignored":  # the swap: drop whatever the reader said
        w.node._compact_log = lambda ss, req, scope: w.db.remove_entries_to(
            CID, NID, ss.index - OVERHEAD)
    w.save()
    assert w.db.list_snapshots(CID, NID)[-1].index == w.last
    lo = (w.last - PER_SAVE) - OVERHEAD + 1  # the save before's range
    ents, _ = w.db.iterate_entries([], 0, CID, NID, lo, w.last + 1, 1 << 62)
    kept = [e.index for e in ents] == list(range(lo, w.last + 1))
    assert kept == (how == "as_it_is")
    w.close()


# ---- a follower behind the marker, at every operation of a save ----------


def serve_a_lagging_follower(lr):
    """What a leader's raft does for an active follower whose ``next`` is
    at or below the reader's marker: ``entries`` refuses (the marker alone
    says so, no LogDB read), so the follower is sent ``snapshot()``, which
    has to be there (``make_install_snapshot_message`` raises 'got an
    empty snapshot' otherwise, inside the engine's step) and has to reach
    the marker, or the follower needs a second one."""
    first, _ = lr.get_range()
    ss = lr.snapshot()
    for nxt in range(1, first):
        with pytest.raises(CompactedError):
            lr.entries(nxt, nxt + 1, 1 << 62)
        assert not ss.is_empty() and ss.index >= first - 1, (
            nxt, first - 1, ss.index)


@pytest.mark.parametrize("name,how", [
    (name, how) for name in SCENARIOS
    for how in ("as_it_is", "marker_moves_first")
])
def test_the_marker_never_passes_the_readers_snapshot(name, how):
    """On a group's FIRST save the reader holds no snapshot at all, and on
    a later one the one before: a marker moved ahead of ``create_snapshot``
    (the swap) is caught at the operations between the two."""
    pad, prior, _ = SCENARIOS[name]
    crash = Crash()
    w = world_before_the_save(pad, prior, crash)
    if how == "marker_moves_first":
        _before_the_commit(
            w, lambda ss: w.logreader.compact(ss.index - OVERHEAD))
    unserved = []

    def probe():
        try:
            serve_a_lagging_follower(w.logreader)
        except AssertionError:
            unserved.append(crash.n)

    crash.probe = probe
    crashed_save(w, None)
    probe()  # and with the save done
    assert w.logreader.get_range()[0] == w.last - OVERHEAD + 1  # it moved
    assert bool(unserved) == (how == "marker_moves_first"), unserved
    w.close()


def test_a_save_an_install_overtook_still_removes_what_it_unrecorded():
    """``create_snapshot`` refuses a snapshot older than the reader's (an
    InstallSnapshot raced in): the save's record and the oldest record's
    delete are durable by then, so the oldest directory goes too, and no
    entry is dropped."""
    w = world_before_the_save(0, 3)

    def overtaken(ss):
        raise SnapshotOutOfDateError()

    w.logreader.create_snapshot = overtaken
    marker = w.logreader.get_range()[0]
    w.save()
    recorded = {s.index for s in w.db.list_snapshots(CID, NID)}
    assert recorded == {20, 30, 40}
    assert {snapshot_index_from_dir(n) for n in w.base.listdir(ROOT)} == recorded
    assert w.logreader.get_range()[0] == marker
    ents, _ = w.db.iterate_entries([], 0, CID, NID, marker, 41, 1 << 62)
    assert [e.index for e in ents] == list(range(marker, 41))
    check_restart(w)
