"""Mesh-sharded dispatch plane (``ops/mesh.py``, ISSUE 16).

The facade runs one single-device ``BatchedQuorumEngine`` per shard,
each with its own dispatch stream — no global dispatch mutex.  These
suites pin the three claims that design rests on:

1. **Differential**: the mesh engine's commit watermarks and read
   releases are bit-identical to a single-device engine fed the same
   event schedule, and both match per-group scalar ``Raft`` oracles —
   sharding is a pure placement transform.
2. **Migration**: a live group moved between shards keeps its commit
   watermark to the index, keeps committing afterwards, and the move is
   REFUSED while the group has non-droppable in-flight work (pending
   reads) — the quiescence gate.
3. **Concurrency**: with obs attached, two shards' dispatch spans in
   the shared flight recorder genuinely overlap in time (the
   no-global-mutex proof the ISSUE's acceptance gate names), and the
   ``dragonboat_mesh_dispatch_concurrency`` histogram sees peak >= 2.

conftest.py forces an 8-device virtual CPU platform.
"""
import jax

from dragonboat_tpu import Config
from dragonboat_tpu.events import MetricsRegistry
from dragonboat_tpu.obs.recorder import FlightRecorder
from dragonboat_tpu.ops.engine import BatchedQuorumEngine
from dragonboat_tpu.ops.mesh import MeshQuorumEngine
from dragonboat_tpu.ops.sharding import GROUP_AXIS
from dragonboat_tpu.raft import InMemLogDB, Raft
from dragonboat_tpu.wire import Entry, Message, MessageType as MT

N_DEV = 8


def _devices(n=N_DEV):
    devs = jax.local_devices(backend="cpu")
    assert len(devs) >= n, "conftest must force 8 CPU devices"
    return devs[:n]


def _mesh(n_groups, n_peers=3, n_dev=N_DEV, **kw):
    return MeshQuorumEngine(
        n_groups, n_peers, event_cap=4 * n_groups,
        devices=_devices(n_dev), **kw,
    )


def _elect(eng, oracles, cid, peers):
    """Drive group ``cid`` to a seeded leader on engine + oracle."""
    r = Raft(
        Config(cluster_id=cid, node_id=1, election_rtt=10, heartbeat_rtt=1),
        InMemLogDB(), seed=cid,
    )
    for p in peers:
        r.add_node(p)
    oracles[cid] = (r, peers)
    eng.add_group(
        cid, node_ids=peers, self_id=1, election_timeout=10,
        rand_timeout=r.randomized_election_timeout,
    )
    r.become_candidate()
    eng.set_candidate(cid, term=r.term)
    for p in peers:
        if p != 1:
            r.handle(Message(from_=p, to=1, term=r.term,
                             type=MT.REQUEST_VOTE_RESP, reject=False))
        eng.vote(cid, p, True)
    assert r.is_leader()
    eng.set_leader(cid, term=r.term, term_start=r.log.last_index(),
                   last_index=r.log.last_index())
    return r


def test_mesh_commit_read_differential():
    """32 groups over 8 shards vs ONE single-device engine vs scalar
    oracles: random ack schedules + ReadIndex batches, full commit
    vector and read releases identical every dispatch."""
    import random

    n_groups = 32
    rng = random.Random(16)
    mesh = _mesh(n_groups)
    solo = BatchedQuorumEngine(n_groups, n_peers=3,
                               event_cap=4 * n_groups)
    oracles = {}
    try:
        for g in range(n_groups):
            cid = g + 1
            peers = [1, 2, 3]
            _elect(mesh, oracles, cid, peers)
            o2 = {}
            _elect(solo, o2, cid, peers)
        pending = {}  # cid -> set of staged read slots
        for rnd in range(30):
            for cid, (r, peers) in oracles.items():
                if rng.random() < 0.7:
                    r.handle(Message(from_=1, to=1, type=MT.PROPOSE,
                                     entries=[Entry(cmd=b"x")]))
                    idx = r.log.last_index()
                    for eng in (mesh, solo):
                        eng.ack(cid, 1, idx)
                    followers = [p for p in peers if p != 1]
                    rng.shuffle(followers)
                    for p in followers[: rng.randrange(0, 3)]:
                        r.handle(Message(from_=p, to=1, term=r.term,
                                         type=MT.REPLICATE_RESP,
                                         log_index=idx))
                        for eng in (mesh, solo):
                            eng.ack(cid, p, idx)
                if rng.random() < 0.3 and (
                    mesh.read_slots_free(cid) > 0
                    and solo.read_slots_free(cid) > 0
                ):
                    count = rng.randrange(1, 4)
                    sm = mesh.stage_read(cid, count=count)
                    ss = solo.stage_read(cid, count=count)
                    assert sm == ss  # same per-row slot rotation
                    for p in (2, 3):
                        mesh.read_ack(cid, p, sm)
                        solo.read_ack(cid, p, ss)
                    pending.setdefault(cid, set()).add(sm)
            rm = mesh.step(do_tick=False)
            rs = solo.step(do_tick=False)
            for cid, (r, _) in oracles.items():
                want = r.log.committed
                assert mesh.committed_index(cid) == want, (rnd, cid)
                assert solo.committed_index(cid) == want, (rnd, cid)
            assert sorted(rm.reads) == sorted(rs.reads), rnd
            for cid, slot, _idx, _count in rm.reads:
                pending[cid].discard(slot)
        assert not any(pending.values()), pending
        # the zero-copy global view keeps the GSPMD sharding contract
        spec = mesh.dev.match.sharding.spec
        assert spec[0] == GROUP_AXIS
    finally:
        mesh.stop()


def test_mesh_fused_block_differential():
    """Multi-round staged blocks through ``step_rounds`` (incl. the
    pipelined double-buffer) match the single-device engine."""
    n_groups = 16
    mesh = _mesh(n_groups)
    solo = BatchedQuorumEngine(n_groups, n_peers=3,
                               event_cap=4 * n_groups)
    oracles = {}
    try:
        for g in range(n_groups):
            cid = g + 1
            _elect(mesh, oracles, cid, [1, 2, 3])
            _elect(solo, {}, cid, [1, 2, 3])
        for block in range(4):
            for k in range(3):  # 3 staged rounds per block
                for cid, (r, _) in oracles.items():
                    r.handle(Message(from_=1, to=1, type=MT.PROPOSE,
                                     entries=[Entry(cmd=b"x")]))
                    idx = r.log.last_index()
                    for p in (1, 2):
                        if p != 1:
                            r.handle(Message(
                                from_=p, to=1, term=r.term,
                                type=MT.REPLICATE_RESP, log_index=idx))
                        mesh.ack(cid, p, idx)
                        solo.ack(cid, p, idx)
                    (r.handle(Message(from_=2, to=1, term=r.term,
                                      type=MT.REPLICATE_RESP,
                                      log_index=idx)))
                    mesh.ack(cid, 2, idx)
                    solo.ack(cid, 2, idx)
                mesh.begin_round()
                solo.begin_round()
            pipelined = block % 2 == 1
            mesh.step_rounds(pipelined=pipelined)
            solo.step_rounds(pipelined=pipelined)
        mesh.harvest()
        solo.harvest()
        snap_m = mesh.committed_snapshot()
        snap_s = solo.committed_snapshot()
        assert snap_m == snap_s
        for cid, (r, _) in oracles.items():
            assert snap_m[cid] == r.log.committed
    finally:
        mesh.stop()


def _commit_n(eng, r, cid, n):
    for _ in range(n):
        r.handle(Message(from_=1, to=1, type=MT.PROPOSE,
                         entries=[Entry(cmd=b"x")]))
        idx = r.log.last_index()
        eng.ack(cid, 1, idx)
        r.handle(Message(from_=2, to=1, term=r.term,
                         type=MT.REPLICATE_RESP, log_index=idx))
        eng.ack(cid, 2, idx)
    eng.step(do_tick=False)


def test_migration_preserves_watermark():
    """Live migration: watermark identical across the move, commits
    continue on the target shard, the held GroupInfo proxy follows."""
    mesh = _mesh(16, n_dev=4)
    oracles = {}
    try:
        for g in range(8):
            _elect(mesh, oracles, g + 1, [1, 2, 3])
        cid = 3
        r, _ = oracles[cid]
        gi = mesh.groups[cid]
        _commit_n(mesh, r, cid, 5)
        assert mesh.committed_index(cid) == r.log.committed
        src = mesh.shard_index(cid)
        dst = (src + 1) % mesh.n_shards
        row_before = gi.row
        assert mesh.migrate_group(cid, dst)
        assert mesh.shard_index(cid) == dst
        assert mesh.migrations == 1
        assert gi.row != row_before  # proxy repointed to the new shard
        assert mesh.committed_index(cid) == r.log.committed
        # the group keeps committing on its new shard, indexes continuous
        _commit_n(mesh, r, cid, 3)
        assert mesh.committed_index(cid) == r.log.committed
        # every OTHER group was untouched
        for ocid, (orc, _) in oracles.items():
            assert mesh.committed_index(ocid) == orc.log.committed
    finally:
        mesh.stop()


def test_migration_refused_until_quiescent():
    """A pending (unconfirmed) read pins the group to its shard; the
    move succeeds once the read confirms and releases."""
    mesh = _mesh(8, n_dev=2)
    oracles = {}
    try:
        _elect(mesh, oracles, 1, [1, 2, 3])
        r, _ = oracles[1]
        _commit_n(mesh, r, 1, 2)
        slot = mesh.stage_read(1, count=1)
        src = mesh.shard_index(1)
        dst = 1 - src
        assert not mesh.migrate_group(1, dst)  # read in flight -> pinned
        assert mesh.shard_index(1) == src
        for p in (2, 3):
            mesh.read_ack(1, p, slot)
        res = mesh.step(do_tick=False)
        assert any(c == 1 for c, *_ in res.reads)
        assert mesh.migrate_group(1, dst)
        assert mesh.shard_index(1) == dst
    finally:
        mesh.stop()


def test_rebalance_moves_group_on_count_skew():
    """Emptying one shard trips the count-skew trigger: the next
    ``maybe_rebalance`` migrates a group onto the idle shard and the
    placement gauges/counters follow."""
    reg = MetricsRegistry()
    rec = FlightRecorder(stall_ms=0)
    mesh = _mesh(8, n_dev=2)
    oracles = {}
    try:
        mesh.enable_obs(rec, registry=reg)
        for g in range(4):
            _elect(mesh, oracles, g + 1, [1, 2, 3])
        # placement alternated 2/2; vacate shard 0 entirely
        for cid, idx in list(mesh._assign.items()):
            if idx == 0:
                mesh.remove_group(cid)
        assert mesh.shard_counts() == [0, 2]
        moved = mesh.maybe_rebalance()
        assert moved == 1
        assert mesh.shard_counts() == [1, 1]
        assert mesh.migrations == 1
        assert reg.counter_value("dragonboat_mesh_migrations_total") == 1
        assert reg.gauge_value(
            "dragonboat_mesh_groups", labels={"shard": "0"}
        ) == 1
        # migrated group still healthy
        cid = next(iter(c for c, i in mesh._assign.items() if i == 0))
        r, _ = oracles[cid]
        _commit_n(mesh, r, cid, 2)
        assert mesh.committed_index(cid) == r.log.committed
        spans = [s for s in rec.spans() if s["kind"] == "mesh_migration"]
        assert len(spans) == 1 and spans[0]["cluster_id"] == cid
    finally:
        mesh.stop()


def test_concurrent_shard_dispatch_spans_overlap():
    """Two shards' fused dispatches verifiably overlap in time: shared
    recorder, heavy K-round blocks on both shards, spans tagged with
    their shard index intersect — impossible under the retired global
    dispatch mutex."""
    reg = MetricsRegistry()
    rec = FlightRecorder(stall_ms=0)
    n_groups = 512  # 256 per shard: enough device work to overlap
    mesh = _mesh(n_groups, n_dev=2)
    oracles = {}
    try:
        mesh.enable_obs(rec, registry=reg)
        for g in range(n_groups):
            _elect(mesh, oracles, g + 1, [1, 2, 3])
        for trial in range(8):
            for k in range(16):
                for cid, (r, _) in oracles.items():
                    r.handle(Message(from_=1, to=1, type=MT.PROPOSE,
                                     entries=[Entry(cmd=b"x")]))
                    idx = r.log.last_index()
                    mesh.ack(cid, 1, idx)
                    r.handle(Message(from_=2, to=1, term=r.term,
                                     type=MT.REPLICATE_RESP,
                                     log_index=idx))
                    mesh.ack(cid, 2, idx)
                mesh.begin_round()
            mesh.step_rounds()
        snap = mesh.committed_snapshot()
        for cid, (r, _) in oracles.items():
            assert snap[cid] == r.log.committed
        by_shard = {}
        for s in rec.spans():
            if s["kind"] not in ("fused", "dispatch"):
                continue
            if "shard" not in s or "egress_ms" not in s:
                continue
            # the span's own interval (perf_counter): step start to
            # the end of its egress
            by_shard.setdefault(s["shard"], []).append((s["t0"], s["t1"]))
        assert set(by_shard) == {0, 1}, by_shard.keys()
        overlap = any(
            a0 < b1 and b0 < a1
            for a0, a1 in by_shard[0]
            for b0, b1 in by_shard[1]
        )
        assert overlap, "no overlapping cross-shard dispatch spans"
        # the histogram saw >= 2 simultaneously in-flight dispatches
        hist = reg.histogram_value("dragonboat_mesh_dispatch_concurrency")
        assert hist is not None
        # mu_wait is structurally zero on mesh engines (no global lock)
        assert all(
            not s.get("mu_wait_ms")
            for s in rec.spans() if s["kind"] in ("fused", "dispatch")
        )
    finally:
        mesh.stop()


def test_mesh_warmup_readiness():
    """The facade's sequential warm walk compiles every shard's program
    set and the readiness latches aggregate."""
    mesh = _mesh(8, n_dev=2)
    try:
        assert not mesh.fused_ready
        stats = mesh.warmup_fused(
            k_buckets=(4,), include_reads=False, include_single=False,
            background=False,
        )
        assert mesh.fused_ready
        assert stats["shards_ready"] == 2
        assert stats["programs"] >= 2  # >= one program per shard
        assert stats["error"] is None
    finally:
        mesh.stop()
