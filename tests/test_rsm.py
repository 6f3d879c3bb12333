"""RSM layer tests (reference model: ``internal/rsm/*_test.go``)."""
import io

import pytest

from dragonboat_tpu.rsm import (
    MembershipState,
    SessionManager,
    StateMachine,
    Task,
    TaskQueue,
    from_concurrent_sm,
    from_regular_sm,
)
from dragonboat_tpu.rsm.session import Session
from dragonboat_tpu.rsm.snapshotio import (
    SnapshotFormatError,
    SnapshotReader,
    SnapshotWriter,
    shrink_snapshot,
    validate_snapshot_file,
)
from dragonboat_tpu.statemachine import (
    IStateMachine,
    Result,
    SMEntry,
    IConcurrentStateMachine,
)
from dragonboat_tpu.wire import (
    ConfigChange,
    ConfigChangeType,
    Entry,
    EntryType,
    SERIES_ID_FOR_REGISTER,
    SERIES_ID_FOR_UNREGISTER,
)
from dragonboat_tpu.wire.codec import encode_config_change


# ---------- sessions ----------


def test_session_response_cache_and_clear():
    s = Session(7)
    s.add_response(1, Result(value=11))
    s.add_response(2, Result(value=22))
    s.add_response(3, Result(value=33))
    r, ok = s.get_response(2)
    assert ok and r.value == 22
    s.clear_to(2)
    assert s.has_responded(2)
    assert not s.has_responded(3)
    _, ok = s.get_response(1)
    assert not ok
    _, ok = s.get_response(2)
    assert not ok
    r, ok = s.get_response(3)
    assert ok and r.value == 33


def test_session_duplicate_response_rejected():
    s = Session(7)
    s.add_response(1, Result(value=1))
    with pytest.raises(RuntimeError):
        s.add_response(1, Result(value=2))


def test_session_manager_lru_eviction():
    sm = SessionManager(max_sessions=3)
    for cid in (1, 2, 3):
        sm.register_client_id(cid)
    sm.client_registered(1)  # touch 1 → 2 is now LRU
    sm.register_client_id(4)
    assert sm.client_registered(2) is None
    assert sm.client_registered(1) is not None
    assert len(sm) == 3


def test_session_manager_serialization_roundtrip_and_hash():
    sm = SessionManager(max_sessions=10)
    sm.register_client_id(100)
    s = sm.client_registered(100)
    s.add_response(1, Result(value=7, data=b"seven"))
    sm.register_client_id(200)
    data = sm.save()
    sm2 = SessionManager.load(data, max_sessions=10)
    assert len(sm2) == 2
    assert sm.hash() == sm2.hash()  # hash before any divergent touches
    s2 = sm2.client_registered(100)
    r, ok = s2.get_response(1)
    assert ok and r.data == b"seven"
    # client_registered touches LRU order on sm2 only → hashes now diverge,
    # mirroring why every replica must apply the same lookup sequence
    assert sm.hash() != sm2.hash()
    # identical further ops on identically-ordered stores stay identical
    sm3 = SessionManager.load(data, max_sessions=10)
    sm4 = SessionManager.load(data, max_sessions=10)
    for m in (sm3, sm4):
        m.client_registered(100)
        m.register_client_id(300)
    assert sm3.hash() == sm4.hash()


# ---------- membership ----------


def cc(t, node_id, addr="a:1", ccid=0, initialize=False):
    return ConfigChange(
        type=t, node_id=node_id, address=addr, config_change_id=ccid,
        initialize=initialize,
    )


def test_membership_add_remove():
    m = MembershipState(1, 1, ordered=False)
    assert m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 1, "a:1"), 1)
    assert m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 2, "b:1"), 2)
    assert m.members.addresses == {1: "a:1", 2: "b:1"}
    assert m.handle_config_change(cc(ConfigChangeType.REMOVE_NODE, 2), 3)
    assert 2 in m.members.removed
    # adding a removed node back is rejected
    assert not m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 2, "b:1"), 4)


def test_membership_rejects_removing_only_node():
    m = MembershipState(1, 1, ordered=False)
    m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 1, "a:1"), 1)
    assert not m.handle_config_change(cc(ConfigChangeType.REMOVE_NODE, 1), 2)


def test_membership_ordered_config_change():
    m = MembershipState(1, 1, ordered=True)
    assert m.handle_config_change(
        cc(ConfigChangeType.ADD_NODE, 1, "a:1", initialize=True), 1
    )
    # stale config change id rejected
    assert not m.handle_config_change(
        cc(ConfigChangeType.ADD_NODE, 2, "b:1", ccid=0), 5
    )
    # correct id (== last applied index) accepted
    assert m.handle_config_change(
        cc(ConfigChangeType.ADD_NODE, 2, "b:1", ccid=1), 6
    )


def test_membership_observer_promotion():
    m = MembershipState(1, 1, ordered=False)
    m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 1, "a:1"), 1)
    m.handle_config_change(cc(ConfigChangeType.ADD_OBSERVER, 2, "b:1"), 2)
    assert 2 in m.members.observers
    # promotion with same address ok
    assert m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 2, "b:1"), 3)
    assert 2 in m.members.addresses and 2 not in m.members.observers
    # observer promotion with different address rejected
    m.handle_config_change(cc(ConfigChangeType.ADD_OBSERVER, 3, "c:1"), 4)
    assert not m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 3, "x:9"), 5)


def test_membership_add_existing_member_different_address_rejected():
    m = MembershipState(1, 1, ordered=False)
    m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 1, "a:1"), 1)
    assert not m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 1, "z:9"), 2)
    # same address re-add is a no-op accept (dedup)
    assert m.handle_config_change(cc(ConfigChangeType.ADD_NODE, 1, "a:1"), 3)


# ---------- snapshot io ----------


def test_snapshot_writer_reader_roundtrip(tmp_path):
    p = str(tmp_path / "snap.ss")
    w = SnapshotWriter(p)
    w.write_session(b"SESSIONDATA")
    w.write(b"A" * (3 * 1024 * 1024 + 17))  # multi-block payload
    w.finalize()
    assert validate_snapshot_file(p)
    r = SnapshotReader(p)
    assert r.read_session() == b"SESSIONDATA"
    body = r.read(-1)
    assert body == b"A" * (3 * 1024 * 1024 + 17)
    r.close()


def test_snapshot_corruption_detected(tmp_path):
    p = str(tmp_path / "snap.ss")
    w = SnapshotWriter(p)
    w.write_session(b"s")
    w.write(b"B" * 100_000)
    w.finalize()
    with open(p, "r+b") as f:
        f.seek(2048)
        f.write(b"\xff\xfe")
    assert not validate_snapshot_file(p)
    r = SnapshotReader(p)
    with pytest.raises(SnapshotFormatError):
        r.read_session()
        r.read(-1)
    r.close()


def test_snapshot_header_corruption_detected(tmp_path):
    p = str(tmp_path / "snap.ss")
    w = SnapshotWriter(p)
    w.write_session(b"s")
    w.finalize()
    with open(p, "r+b") as f:
        f.seek(10)
        f.write(b"\xff")
    with pytest.raises(SnapshotFormatError):
        SnapshotReader(p)


def test_shrink_snapshot(tmp_path):
    src, dst = str(tmp_path / "a.ss"), str(tmp_path / "b.ss")
    w = SnapshotWriter(src)
    w.write_session(b"sess")
    w.write(b"C" * 500_000)
    w.finalize()
    shrink_snapshot(src, dst)
    assert validate_snapshot_file(dst)
    r = SnapshotReader(dst)
    assert r.read_session() == b""
    assert r.read(-1) == b""
    r.close()


# ---------- StateMachine manager ----------


class KVSM(IStateMachine):
    """Tiny in-memory KV: cmd = b"set k v"."""

    def __init__(self):
        self.kv = {}
        self.update_count = 0

    def update(self, cmd):
        self.update_count += 1
        _, k, v = cmd.decode().split(" ")
        self.kv[k] = v
        return Result(value=len(self.kv))

    def lookup(self, query):
        return self.kv.get(query)

    def save_snapshot(self, w, files, done):
        data = repr(sorted(self.kv.items())).encode()
        w.write(data)

    def recover_from_snapshot(self, r, files, done):
        import ast

        self.kv = dict(ast.literal_eval(r.read(-1).decode()))


class RecordingProxy:
    def __init__(self):
        self.updates = []
        self.config_changes = []
        self.restored = []

    def node_ready(self):
        pass

    def apply_update(self, entry, result, rejected, ignored, notify_read):
        self.updates.append((entry.index, result, rejected, ignored))

    def apply_config_change(self, ccv, key, rejected):
        self.config_changes.append((ccv, key, rejected))

    def restore_remotes(self, ss):
        self.restored.append(ss)

    def should_stop(self):
        return False


def make_sm():
    proxy = RecordingProxy()
    kvsm = KVSM()
    sm = StateMachine(
        from_regular_sm(kvsm), None, proxy, cluster_id=1, node_id=1
    )
    return sm, kvsm, proxy


def entry(index, cmd=b"", client_id=0, series_id=0, responded_to=0, term=1):
    return Entry(
        term=term,
        index=index,
        cmd=cmd,
        client_id=client_id,
        series_id=series_id,
        responded_to=responded_to,
    )


def test_sm_applies_noop_session_entries():
    sm, kvsm, proxy = make_sm()
    t = Task(cluster_id=1, node_id=1, entries=[
        entry(1, b"set a 1"), entry(2, b"set b 2")])
    assert sm.handle([t]) is None
    assert kvsm.kv == {"a": "1", "b": "2"}
    assert sm.get_last_applied() == 2
    assert [u[0] for u in proxy.updates] == [1, 2]


def test_sm_out_of_order_entry_panics():
    sm, _, _ = make_sm()
    with pytest.raises(RuntimeError):
        sm.handle([Task(cluster_id=1, node_id=1, entries=[entry(5, b"set a 1")])])


def test_sm_session_lifecycle_and_dedup():
    sm, kvsm, proxy = make_sm()
    client = 42
    ents = [
        entry(1, client_id=client, series_id=SERIES_ID_FOR_REGISTER),
        entry(2, b"set a 1", client_id=client, series_id=1),
        entry(3, b"set a 2", client_id=client, series_id=1),  # dup retry
        entry(4, b"set b 3", client_id=client, series_id=2, responded_to=1),
        entry(5, client_id=client, series_id=SERIES_ID_FOR_UNREGISTER),
    ]
    sm.handle([Task(cluster_id=1, node_id=1, entries=ents)])
    # dup must not re-execute: 'a' stays '1', update ran twice total
    assert kvsm.kv == {"a": "1", "b": "3"}
    assert kvsm.update_count == 2
    # the dup got the cached result back
    assert proxy.updates[2][1] == proxy.updates[1][1]
    assert sm.get_last_applied() == 5


def test_sm_unregistered_session_rejected():
    sm, kvsm, proxy = make_sm()
    sm.handle([Task(cluster_id=1, node_id=1, entries=[
        entry(1, b"set a 1", client_id=99, series_id=1)])])
    assert kvsm.kv == {}
    assert proxy.updates[0][2] is True  # rejected


def test_sm_config_change_application():
    sm, _, proxy = make_sm()
    c = ConfigChange(type=ConfigChangeType.ADD_NODE, node_id=2, address="b:1")
    e = Entry(
        term=1, index=1, type=EntryType.CONFIG_CHANGE,
        cmd=encode_config_change(c), key=77,
    )
    sm.handle([Task(cluster_id=1, node_id=1, entries=[e])])
    assert 2 in sm.get_membership().addresses
    assert proxy.config_changes[0][2] is False
    assert proxy.config_changes[0][1] == 77
    assert sm.get_last_applied() == 1


def test_sm_handle_returns_snapshot_task():
    sm, _, _ = make_sm()
    t1 = Task(cluster_id=1, node_id=1, entries=[entry(1, b"set a 1")])
    t2 = Task(cluster_id=1, node_id=1, save=True)
    got = sm.handle([t1, t2])
    assert got is t2
    assert sm.get_last_applied() == 1


def test_sm_hash_deterministic_across_replicas():
    sm1, _, _ = make_sm()
    sm2, _, _ = make_sm()
    ents = [
        entry(1, client_id=5, series_id=SERIES_ID_FOR_REGISTER),
        entry(2, b"set x 9", client_id=5, series_id=1),
    ]
    sm1.handle([Task(cluster_id=1, node_id=1, entries=list(ents))])
    sm2.handle([Task(cluster_id=1, node_id=1, entries=list(ents))])
    assert sm1.get_hash() == sm2.get_hash()
    assert sm1.get_session_hash() == sm2.get_session_hash()


class ConcKVSM(IConcurrentStateMachine):
    def __init__(self):
        self.kv = {}

    def update(self, entries):
        for e in entries:
            _, k, v = e.cmd.decode().split(" ")
            self.kv[k] = v
            e.result = Result(value=len(self.kv))
        return entries

    def lookup(self, query):
        return self.kv.get(query)

    def prepare_snapshot(self):
        return dict(self.kv)  # point-in-time copy

    def save_snapshot(self, ctx, w, files, done):
        w.write(repr(sorted(ctx.items())).encode())

    def recover_from_snapshot(self, r, files, done):
        import ast

        self.kv = dict(ast.literal_eval(r.read(-1).decode()))


def test_sm_concurrent_batches_updates():
    proxy = RecordingProxy()
    csm = ConcKVSM()
    sm = StateMachine(from_concurrent_sm(csm), None, proxy, 1, 1)
    ents = [entry(i, b"set k%d v" % i) for i in range(1, 6)]
    sm.handle([Task(cluster_id=1, node_id=1, entries=ents)])
    assert len(csm.kv) == 5
    assert [u[0] for u in proxy.updates] == [1, 2, 3, 4, 5]
    # prepare_snapshot captures a point-in-time ctx
    meta = sm.prepare_snapshot(__import__(
        "dragonboat_tpu.rsm.statemachine", fromlist=["SSRequest"]
    ).SSRequest())
    assert meta.index == 5
    assert len(meta.ctx) == 5


# ---------- TaskQueue ----------


def test_task_queue_fifo_and_backpressure():
    q = TaskQueue()
    for i in range(5):
        q.enqueue(Task(index=i))
    assert q.get().index == 0
    rest = q.get_all()
    assert [t.index for t in rest] == [1, 2, 3, 4]
    assert q.get() is None
    assert q.more_entries_to_apply()


# ---------- on-disk SM recover/shrink corner tables ----------
#
# First slice of the reference's ``internal/rsm/statemachine_test.go``
# recover/shrink corner families (VERDICT r5 item 7), with vfs.ErrorFS
# fault injection on the snapshot path: on-disk init-index skipping,
# metadata-only recovery, recover/save under injected I/O errors (state
# must stay at the pre-fault watermarks), and shrink fault atomicity.

from dragonboat_tpu import vfs
from dragonboat_tpu.rsm import from_on_disk_sm
from dragonboat_tpu.rsm.statemachine import SSReqType, SSRequest, Task as SMTask
from dragonboat_tpu.snapshotter import Snapshotter
from dragonboat_tpu.statemachine import IOnDiskStateMachine


class DiskKVSM(IOnDiskStateMachine):
    """On-disk KV whose durable store is a plain dict + an applied index
    it persists conceptually (the tests inject the 'persisted' index via
    ``init_index``, the reference tests' OnDiskInitIndex knob)."""

    def __init__(self, init_index: int = 0):
        self.kv = {}
        self.init_index = init_index
        self.update_count = 0
        self.recovered = 0

    def open(self, stopc) -> int:
        return self.init_index

    def update(self, entries):
        for e in entries:
            self.update_count += 1
            _, k, v = e.cmd.decode().split(" ")
            self.kv[k] = v
            e.result = Result(value=len(self.kv))
        return entries

    def lookup(self, query):
        return self.kv.get(query)

    def sync(self):
        pass

    def prepare_snapshot(self):
        return dict(self.kv)

    def save_snapshot(self, ctx, w, done):
        w.write(repr(sorted(ctx.items())).encode())

    def recover_from_snapshot(self, r, done):
        import ast

        self.recovered += 1
        self.kv = dict(ast.literal_eval(r.read(-1).decode()))


class _FakeLogDB:
    def __init__(self):
        self.snapshots = []

    def save_snapshot(self, cluster_id, node_id, ss):
        self.snapshots.append(ss)

    def commit_snapshot(self, cluster_id, node_id, ss, stale):
        self.snapshots = [s for s in self.snapshots if s.index not in stale]
        self.snapshots.append(ss)

    def list_snapshots(self, cluster_id, node_id):
        return list(self.snapshots)


def make_disk_sm(tmp_path, fs=vfs.DEFAULT, init_index=0, sub="snaps"):
    proxy = RecordingProxy()
    dsm = DiskKVSM(init_index)
    snap = Snapshotter(
        str(tmp_path / sub), cluster_id=1, node_id=1, logdb=_FakeLogDB(),
        fs=fs,
    )
    sm = StateMachine(from_on_disk_sm(dsm), snap, proxy, 1, 1)
    sm.open()
    return sm, dsm, proxy, snap


def _apply(sm, lo, hi):
    ents = [entry(i, b"set k%d v%d" % (i, i)) for i in range(lo, hi + 1)]
    sm.handle([Task(cluster_id=1, node_id=1, entries=ents)])


def test_ondisk_entries_below_init_index_skipped(tmp_path):
    """shouldApplyEntry/onDiskInitIndex: entries the SM's own store
    already covers advance the watermark WITHOUT re-applying (reference
    statemachine_test.go on-disk init-index table)."""
    sm, dsm, proxy, _ = make_disk_sm(tmp_path, init_index=3)
    sm.set_batched_last_applied(3)
    sm.last_applied = 3
    _apply(sm, 4, 6)
    # only 4..6 executed; nothing from the covered prefix
    assert dsm.update_count == 3
    assert sm.get_last_applied() == 6
    assert sm.on_disk_index == 6
    # the skipped-prefix contract also holds when replay starts below:
    sm2, dsm2, proxy2, _ = make_disk_sm(tmp_path, init_index=2, sub="s2")
    _apply(sm2, 1, 3)
    assert dsm2.update_count == 1  # only index 3 executed
    assert sm2.get_last_applied() == 3
    # skipped entries still produced (ignored) apply notifications
    assert [u[3] for u in proxy2.updates] == [True, True, False]


def test_ondisk_recover_covered_snapshot_adopts_metadata_only(tmp_path):
    """Recover with ``ss.on_disk_index <= on_disk_init_index``: the SM's
    own store already covers the image — watermarks/membership adopt,
    recover_from_snapshot must NOT run (reference Recover :228-341)."""
    sm, dsm, _, snap = make_disk_sm(tmp_path, init_index=0)
    _apply(sm, 1, 5)
    ss, env = sm.save(SSRequest())
    snap.commit(ss, env)
    assert ss.on_disk_index == 5
    # second replica whose own store is AHEAD of the snapshot
    sm2, dsm2, _, _ = make_disk_sm(tmp_path, init_index=9, sub="s2")
    got = sm2.recover(SMTask(cluster_id=1, node_id=1, recover=True, ss=ss))
    assert got is ss
    assert dsm2.recovered == 0            # metadata-only
    assert sm2.get_last_applied() == ss.index
    assert sm2.on_disk_index == 9         # own store stays authoritative


def test_ondisk_recover_newer_snapshot_restores_image(tmp_path):
    sm, dsm, _, snap = make_disk_sm(tmp_path, init_index=0)
    _apply(sm, 1, 5)
    ss, env = sm.save(SSRequest())
    snap.commit(ss, env)
    sm2, dsm2, _, _ = make_disk_sm(tmp_path, init_index=2, sub="s2")
    sm2.recover(SMTask(cluster_id=1, node_id=1, recover=True, ss=ss))
    assert dsm2.recovered == 1
    assert dsm2.kv == dsm.kv
    assert sm2.get_last_applied() == 5
    assert sm2.on_disk_index == 5


def test_ondisk_recover_read_fault_leaves_state_unchanged(tmp_path):
    """ErrorFS read fault mid-recover: the exception propagates and the
    SM keeps its pre-fault watermarks and image (the reference's
    fault-injected recover corners)."""
    base = vfs.MemFS()
    sm, dsm, _, snap = make_disk_sm(tmp_path, fs=base, init_index=0)
    _apply(sm, 1, 5)
    ss, env = sm.save(SSRequest())
    snap.commit(ss, env)
    # reader SM on an ErrorFS that fails the 2nd read of the image file
    efs = vfs.ErrorFS(base, vfs.Injector.after_n(1, ops={"read"}))
    sm2, dsm2, _, _ = make_disk_sm(tmp_path, fs=efs, init_index=0, sub="s2")
    _apply(sm2, 1, 2)
    with pytest.raises(OSError):
        sm2.recover(SMTask(cluster_id=1, node_id=1, recover=True, ss=ss))
    assert sm2.get_last_applied() == 2      # pre-fault watermark
    assert sm2.snapshot_index == 0
    assert dsm2.kv == {"k1": "v1", "k2": "v2"}
    # the fs healed (injector only counts reads): recovery then succeeds
    sm3, dsm3, _, _ = make_disk_sm(tmp_path, fs=base, init_index=0, sub="s3")
    sm3.recover(SMTask(cluster_id=1, node_id=1, recover=True, ss=ss))
    assert dsm3.kv == dsm.kv


def test_ondisk_save_write_fault_cleans_tmp_and_keeps_index(tmp_path):
    """ErrorFS write fault mid-save: Snapshotter.save aborts, removes the
    temp dir, and snapshot_index does not advance — a later healthy save
    from the same SM succeeds at the same index."""
    base = vfs.MemFS()
    efs = vfs.ErrorFS(base, vfs.Injector.after_n(0, ops={"write"}))
    sm, dsm, _, snap = make_disk_sm(tmp_path, fs=efs, init_index=0)
    _apply(sm, 1, 4)
    with pytest.raises(OSError):
        sm.save(SSRequest())
    assert sm.snapshot_index == 0
    root = str(tmp_path / "snaps")
    leftovers = [d for d in base.listdir(root) if "generating" in d]
    assert leftovers == [], leftovers
    # heal the fs: same snapshotter, save succeeds and the index moves
    snap.fs = base
    sm.snapshotter.fs = base
    healthy = Snapshotter(root, 1, 1, logdb=_FakeLogDB(), fs=base)
    sm.snapshotter = healthy
    ss, env = sm.save(SSRequest())
    healthy.commit(ss, env)
    assert ss.index == 4 and sm.snapshot_index == 4


def test_shrink_snapshot_fault_atomicity(tmp_path):
    """shrink under a dst-write fault: the destination is not a valid
    snapshot, the source stays intact, and a healthy retry produces a
    valid shrunken image (reference shrink corner family)."""
    base = vfs.MemFS()
    src, dst = "/a.ss", "/b.ss"
    w = SnapshotWriter(src, fs=base)
    w.write_session(b"sess")
    w.write(b"D" * 300_000)
    w.finalize()
    efs = vfs.ErrorFS(base, vfs.Injector.on_path("b.ss", ops={"write"}))
    with pytest.raises(OSError):
        shrink_snapshot(src, dst, fs=efs)
    assert not validate_snapshot_file(dst, fs=base)
    assert validate_snapshot_file(src, fs=base)   # source untouched
    shrink_snapshot(src, dst, fs=base)            # healthy retry
    assert validate_snapshot_file(dst, fs=base)
    r = SnapshotReader(dst, fs=base)
    assert r.read_session() == b"" and r.read(-1) == b""
    r.close()


def test_ondisk_witness_snapshot_recover_is_metadata_only(tmp_path):
    """A witness/dummy snapshot adopts watermarks without touching the
    SM image (reference witness snapshot corners)."""
    from dragonboat_tpu.wire import Snapshot as WireSnapshot

    sm, dsm, _, _ = make_disk_sm(tmp_path, init_index=0)
    _apply(sm, 1, 2)
    ss = WireSnapshot(index=7, term=3, witness=True, cluster_id=1)
    got = sm.recover(SMTask(cluster_id=1, node_id=1, recover=True, ss=ss))
    assert got is ss
    assert dsm.recovered == 0
    assert sm.get_last_applied() == 7
    assert dsm.kv == {"k1": "v1", "k2": "v2"}  # image untouched
