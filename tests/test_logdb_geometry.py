"""The LogDB's shard geometry (ISSUE 45): a NodeHost opens a new LogDB with
as many shards as it has step workers, so a worker's groups live in one
shard and a committer cycle is ONE durable write batch; a directory that
exists keeps the shards it was written with; nothing is acknowledged before
its sync whatever the two counts are; and the committers count their durable
batches beside their cycles (``Engine.stats()``, ``Tracer.wal_cycles()``).
"""
from __future__ import annotations

import os
import time

import pytest

from dragonboat_tpu import Config, NodeHostConfig
from dragonboat_tpu import vfs
from dragonboat_tpu.config import ExpertConfig, LogDBConfig
from dragonboat_tpu.engine import Engine
from dragonboat_tpu.logdb import LogReader, WalKV, open_logdb
from dragonboat_tpu.logdb.sharded import shards_on_disk
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.obs import trace as trace_mod
from dragonboat_tpu.obs.trace import Tracer
from dragonboat_tpu.testing import CounterSM
from dragonboat_tpu.transport import ChanRouter, ChanTransport
from dragonboat_tpu.wire import Bootstrap, Entry, State, Update

RTT_MS = 5


def mk_host(addr, dirname, router=None, logdb_config=None,
            logdb_factory=None, trace_sample_every=0, **expert_kw):
    router = router or ChanRouter()
    return NodeHost(NodeHostConfig(
        node_host_dir=dirname,
        rtt_millisecond=RTT_MS,
        raft_address=addr,
        raft_rpc_factory=lambda s, rh, ch: ChanTransport(
            s, rh, ch, router=router),
        logdb_factory=logdb_factory,
        logdb_config=logdb_config or LogDBConfig(),
        trace_sample_every=trace_sample_every,
        expert=ExpertConfig(**expert_kw),
    ))


def start_groups(nh, addr, cids):
    for cid in cids:
        nh.start_cluster(
            {1: addr}, False, CounterSM,
            Config(cluster_id=cid, node_id=1, election_rtt=10,
                   heartbeat_rtt=1),
        )
    deadline = time.time() + 20.0
    while not all(nh.get_leader_id(cid)[1] for cid in cids):
        assert time.time() < deadline, "no leader"
        time.sleep(0.01)


def update(cid, index, term=1):
    return Update(
        cluster_id=cid, node_id=1,
        state=State(term=term, vote=1, commit=index),
        entries_to_save=[Entry(index=index, term=term, cmd=b"v%d" % index)],
    )


def fsyncs(db):
    return sum(s.kv.fsyncs for s in db._shards)


# ---------------------------------------------------------------------------
# (a) a new LogDB has a shard per step worker; a cycle is one durable batch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["durable", "memory"])
@pytest.mark.parametrize("workers", [0, 2, 3])
def test_a_new_logdb_has_as_many_shards_as_step_workers(
        tmp_path, where, workers):
    nh = mk_host("geo:1", str(tmp_path) if where == "durable" else ":memory:",
                 step_worker_count=workers)
    try:
        want = workers or 4
        assert nh.engine.step_ready.count == want
        assert len(nh.logdb._shards) == want
        if where == "durable":
            assert shards_on_disk(nh.logdb._dir) == want
    finally:
        nh.stop()


@pytest.mark.parametrize("field", ["expert", "logdb_config"])
def test_a_count_the_user_sets_is_kept(tmp_path, field):
    if field == "expert":
        nh = mk_host("geo:1", str(tmp_path), logdb_shards=8,
                     logdb_config=LogDBConfig(shards=2))
    else:
        nh = mk_host("geo:1", str(tmp_path),
                     logdb_config=LogDBConfig(shards=8))
    try:
        assert len(nh.logdb._shards) == 8
    finally:
        nh.stop()


@pytest.mark.parametrize("shards_per_worker", [1, 4])
def test_one_committer_cycle_over_many_groups_of_its_worker(
        tmp_path, shards_per_worker):
    """The default geometry makes the cycle one durable batch; with more
    shards than workers the per-bucket loop stays and pays one a shard."""
    workers = 4
    nh = mk_host("geo:1", str(tmp_path),
                 logdb_shards=0 if shards_per_worker == 1 else 16)
    try:
        db = nh.logdb
        assert len(db._shards) == workers * shards_per_worker
        for w in range(workers):
            cids = [w + workers * k for k in range(1, 13)]  # its 12 groups
            assert {nh.engine.step_ready.partitioner.get_partition_id(c)
                    for c in cids} == {w}
            before = fsyncs(db)
            c = nh.engine._committers[w]
            cycles = c.cycles
            c._commit([([], [update(cid, 1) for cid in cids[:6]]),
                       ([], [update(cid, 1) for cid in cids[6:]])])
            assert fsyncs(db) - before == shards_per_worker
            assert c.cycles == cycles + 1
        for st in nh.engine.stats()["committers"]:
            assert st["cycles"] == 1 and st["merged_updates"] == 12
            assert st["sync_batches"] == st["cycles"] * shards_per_worker
        # and every group reads back from the shard it was put in
        for cid in range(workers, workers * 13):
            rs = db.read_raft_state(cid, 1, 0)
            assert rs.state.commit == 1 and rs.entry_count == 1
    finally:
        nh.stop()


def test_a_cycle_whose_records_are_all_suppressed_syncs_nothing():
    db = open_logdb("", shards=2)
    try:
        ud = Update(cluster_id=3, node_id=1,
                    state=State(term=1, vote=1, commit=1))
        assert db.save_raft_state([ud]) == 1
        assert db.save_raft_state([ud]) == 0  # the same hard state again
        assert db.save_raft_state([]) == 0
    finally:
        db.close()


# ---------------------------------------------------------------------------
# (b) a directory that exists is opened with the shards it has
# ---------------------------------------------------------------------------

GROUPS = list(range(1, 41))


def fill(db):
    for cid in GROUPS:
        db.save_bootstrap_info(
            cid, 1, Bootstrap(addresses={1: f"a{cid}:1"}, join=False))
    db.save_raft_state([update(cid, i, term=2)
                        for cid in GROUPS for i in (1, 2, 3)])


def check_filled(db):
    for cid in GROUPS:
        assert db.get_bootstrap_info(cid, 1).addresses == {1: f"a{cid}:1"}
        rs = db.read_raft_state(cid, 1, 0)
        assert (rs.state.term, rs.state.commit) == (2, 3)
        assert (rs.first_index, rs.entry_count) == (1, 3)
        ents, _ = db.iterate_entries([], 0, cid, 1, 1, 4, 1 << 30)
        assert [(e.index, e.cmd) for e in ents] == [
            (i, b"v%d" % i) for i in (1, 2, 3)]
        lr = LogReader.load(cid, 1, db)
        assert lr.get_range() == (1, 3)
    assert len(db.list_node_info()) == len(GROUPS)


def test_a_16_shard_directory_reopens_with_all_16(tmp_path):
    d = str(tmp_path / "logdb")
    db = open_logdb(d, shards=16, fsync=False)
    fill(db)
    db.close()
    assert shards_on_disk(d) == 16
    # the new default (a NodeHost's: its step workers) follows the disk
    for kw in ({"default_shards": 4}, {}, {"shards": 16}):
        db = open_logdb(d, fsync=False, **kw)
        try:
            assert len(db._shards) == 16
            check_filled(db)
        finally:
            db.close()
    assert shards_on_disk(d) == 16  # no shard-NN made or lost on the way


@pytest.mark.parametrize("asked", [4, 17])
def test_an_explicit_count_that_disagrees_raises_naming_both(tmp_path, asked):
    d = str(tmp_path / "logdb")
    open_logdb(d, shards=16, fsync=False).close()
    with pytest.raises(RuntimeError) as e:
        open_logdb(d, shards=asked, fsync=False)
    assert "16 shards" in str(e.value) and f"{asked} were" in str(e.value)
    assert shards_on_disk(d) == 16


def test_shard_directories_that_are_not_a_run_from_0_raise(tmp_path):
    d = str(tmp_path / "logdb")
    open_logdb(d, shards=3, fsync=False).close()
    os.rename(os.path.join(d, "shard-01"), os.path.join(d, "shard-07"))
    with pytest.raises(RuntimeError, match=r"\[0, 2, 7\]"):
        open_logdb(d, fsync=False)


def test_no_directory_no_shards(tmp_path):
    assert shards_on_disk("") == 0
    assert shards_on_disk(str(tmp_path / "absent")) == 0
    (tmp_path / "host-journal.wal").write_bytes(b"")
    (tmp_path / "shard-1").mkdir()  # not a shard's name
    assert shards_on_disk(str(tmp_path)) == 0


def test_a_host_restarted_on_its_16_shard_directory_keeps_every_group(
        tmp_path):
    """What every upgraded host does: written by the parent's default (16
    shards), reopened under the new one."""
    cids = [1, 2, 3, 5, 6, 7, 21]  # cid % 16 and cid % 4 differ for most
    nh = mk_host("geo:1", str(tmp_path), logdb_config=LogDBConfig(shards=16))
    try:
        start_groups(nh, "geo:1", cids)
        for cid in cids:
            s = nh.get_noop_session(cid)
            for _ in range(cid):
                nh.sync_propose(s, b"x", timeout=10.0)
    finally:
        nh.stop()
    with pytest.raises(RuntimeError, match="16 shards.*4 were"):
        mk_host("geo:1", str(tmp_path), logdb_shards=4)
    nh = mk_host("geo:1", str(tmp_path))
    try:
        assert len(nh.logdb._shards) == 16
        assert nh.engine.step_ready.count == 4
        assert sorted(i.cluster_id for i in nh.logdb.list_node_info()) == cids
        start_groups(nh, "geo:1", cids)
        for cid in cids:
            assert nh.sync_read(cid, None, timeout=10.0) == cid
            s = nh.get_noop_session(cid)
            assert nh.sync_propose(s, b"x", timeout=10.0).value == cid + 1
    finally:
        nh.stop()


@pytest.mark.parametrize("existing", [16, 0])
def test_import_snapshot_lands_on_the_hosts_own_layout(tmp_path, existing):
    from dragonboat_tpu.server.snapshotenv import snapshot_dir_name
    from dragonboat_tpu.tools import import_snapshot
    from dragonboat_tpu.tools.importsnap import _host_dir

    cid = 7  # 7 % 16 = 7, 7 % 4 = 3
    router = ChanRouter()
    export = tmp_path / "export"
    export.mkdir()
    nh = mk_host("orig:1", str(tmp_path / "orig"), router=router)
    try:
        start_groups(nh, "orig:1", [cid])
        s = nh.get_noop_session(cid)
        for _ in range(5):
            nh.sync_propose(s, b"x", timeout=10.0)
        idx = nh.request_snapshot(
            cid, export_path=str(export), timeout=10.0
        ).wait(10.0).snapshot_index
        assert idx > 0
    finally:
        nh.stop()
    nhc = NodeHostConfig(
        node_host_dir=str(tmp_path / "repair"), rtt_millisecond=RTT_MS,
        raft_address="repair:1",
        raft_rpc_factory=lambda s_, rh, ch: ChanTransport(
            s_, rh, ch, router=router),
    )
    logdb_dir = os.path.join(_host_dir(nhc), "logdb")
    if existing:
        # the host that is repaired was written by the parent's default
        open_logdb(logdb_dir, shards=existing).close()
    import_snapshot(nhc, str(export / snapshot_dir_name(idx)),
                    {1: "repair:1"}, 1)
    want = existing or 4  # a new directory: the host's step workers
    assert shards_on_disk(logdb_dir) == want
    db = open_logdb(logdb_dir)
    try:
        assert len(db._shards) == want
        # in the shard the host will look in, not in cid % another count
        assert [s.index for s in
                db._shards[cid % want].list_snapshots(cid, 1)] == [idx]
    finally:
        db.close()
    nh2 = NodeHost(nhc)
    try:
        assert len(nh2.logdb._shards) == want
        start_groups(nh2, "repair:1", [cid])
        assert nh2.sync_read(cid, None, timeout=10.0) == 5
    finally:
        nh2.stop()


# ---------------------------------------------------------------------------
# (c) nothing acknowledges before its fsync: the classic committer path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shards_per_worker", [1, 4])
def test_nothing_acked_before_fsync_on_the_committer_path(
        tmp_path, shards_per_worker):
    """While a shard's fsync fails nothing of that cycle is acknowledged
    (``process_raft_update`` runs only after ``save_raft_state`` returned);
    the committer's retry lands it once the disk heals.  One committer
    cycle over four groups: one shard batch, or four in a row."""
    failing = [False]
    inj = vfs.Injector(lambda op, path: failing[0] and op == "fsync")
    efs = vfs.ErrorFS(vfs.OSFS(), inj)
    workers = 1
    ldb_dir = str(tmp_path / "wal")

    def logdb_factory(nhc):
        args = nhc.open_logdb_args()
        assert args["default_shards"] == workers and args["fsync"]
        return open_logdb(
            ldb_dir, shards=workers * shards_per_worker,
            kv_factory=lambda d: WalKV(d, fsync=True, fs=efs),
        )

    cids = [1, 2, 3, 4]
    nh = mk_host("ack:1", str(tmp_path / "nh"), logdb_factory=logdb_factory,
                 step_worker_count=workers)
    try:
        assert nh.hostplane is None  # the classic committer, no journal
        assert len(nh.logdb._shards) == shards_per_worker
        start_groups(nh, "ack:1", cids)
        sess = {cid: nh.get_noop_session(cid) for cid in cids}
        for cid in cids:
            assert nh.sync_propose(sess[cid], b"pre", timeout=10.0).value == 1
        before = nh.logdb.fsync_count()
        synced = nh.engine.stats()["committers"][0]["sync_batches"]
        assert before > 0 and synced > 0
        failing[0] = True
        pending = [nh.propose(sess[cid], b"during", timeout=30.0)
                   for cid in cids]
        assert not any(rs.wait(0.25).completed for rs in pending)
        assert not any(rs.done() for rs in pending)
        assert inj.injected > 0
        # a failed batch counts no sync: nothing became durable
        assert nh.logdb.fsync_count() == before
        assert nh.engine.stats()["committers"][0]["sync_batches"] == synced
        failing[0] = False
        for rs in pending:
            assert rs.wait(10.0).completed
        assert nh.logdb.fsync_count() > before
        for cid in cids:
            assert nh.sync_read(cid, None, timeout=10.0) == 2
    finally:
        nh.stop()


# ---------------------------------------------------------------------------
# (d) the cycles by the second while a tracer is on; nothing when it is off
# ---------------------------------------------------------------------------


def test_the_tracer_keeps_the_cycles_by_the_second():
    db = open_logdb("", shards=4)
    tr = Tracer(sample_every=1)
    eng = Engine(lambda: (1, {}), db, step_workers=2, apply_workers=1)
    eng.tracer = tr
    try:
        t0 = int(time.perf_counter())
        # worker 0's groups lie in shards 0 and 2 of the four, worker 1's
        # in 1 and 3: a cycle over both of a worker's shards syncs twice
        eng._committers[0]._commit([([], [update(2, 1), update(4, 1)])])
        eng._committers[0]._commit([([], [update(8, 1)])])
        eng._committers[1]._commit(
            [([], [update(1, 1), update(3, 1), update(5, 1)])])
        secs = tr.wal_cycles()
        assert set(secs) <= set(range(t0, t0 + 31))
        cycles, batches, updates = (
            sum(c[i] for c in secs.values()) for i in (0, 1, 2))
        assert (cycles, batches, updates) == (3, 5, 6)
        st = eng.stats()["committers"]
        assert [c["sync_batches"] for c in st] == [3, 2]
        commit_s = sum(c[3] for c in secs.values())
        assert 0.0 < commit_s < 30.0
        assert commit_s == pytest.approx(
            sum(c.commit_s for c in eng._committers))
        # folded once: a second reading adds nothing
        assert tr.wal_cycles() == secs
    finally:
        eng.stop()
        tr.close()
        db.close()


def test_with_no_tracer_no_cycle_is_counted_for_one(tmp_path, monkeypatch):
    def boom(*a, **k):
        raise AssertionError("a tracer's counter was reached with it off")

    monkeypatch.setattr(Tracer, "count_wal_cycle", boom)
    live = len(trace_mod.live())
    nh = mk_host("off:1", str(tmp_path))
    try:
        assert nh.tracer is None and nh.engine.tracer is None
        assert len(trace_mod.live()) == live  # none was built
        start_groups(nh, "off:1", [1])
        s = nh.get_noop_session(1)
        assert nh.sync_propose(s, b"x", timeout=10.0).value == 1
        st = nh.engine.stats()["committers"]
        assert sum(c["cycles"] for c in st) > 0
        assert all(c["sync_batches"] <= c["cycles"] for c in st)
    finally:
        nh.stop()


def test_a_traced_host_reports_its_cycles(tmp_path):
    nh = mk_host("on:1", str(tmp_path), trace_sample_every=1)
    try:
        start_groups(nh, "on:1", [1, 2, 5])
        for cid in (1, 2, 5):
            s = nh.get_noop_session(cid)
            for _ in range(3):
                nh.sync_propose(s, b"x", timeout=10.0)
        st = nh.engine.stats()["committers"]
        secs = nh.tracer.wal_cycles()
        for i, key in enumerate(("cycles", "sync_batches", "merged_updates")):
            assert sum(c[i] for c in secs.values()) == \
                sum(c[key] for c in st)
        # a shard a worker: never more than one durable batch a cycle
        assert all(c[1] <= c[0] for c in secs.values())
    finally:
        nh.stop()
