"""AOT warm-compile + persistent XLA compilation cache gate (ISSUE 7).

The fast cpu gate behind ``make test-warmup``: the warmup pass runs
against a TEMP compilation-cache directory and the suite asserts the two
contracts the tentpole rests on:

(a) a second enable is CACHE-HOT — after ``jax.clear_caches()`` (the
    in-process twin of a restart) re-warming a fresh engine deserializes
    every program from the persistent cache (hits > 0, misses == 0)
    instead of recompiling;
(b) proposals issued DURING warmup never block on compilation — the
    round thread stays on the already-compiled single-round path until
    the readiness latch flips (``fused_dispatches == 0`` while warming,
    ``fuse_skip="warmup"`` on the round spans), and commits keep landing
    the whole time.
"""
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from dragonboat_tpu.ops.engine import (  # noqa: E402
    WARM_K_BUCKETS,
    BatchedQuorumEngine,
    compilation_cache_stats,
    enable_persistent_compilation_cache,
    k_bucket,
    kernel_source_hash,
)


def test_k_bucket_covers_the_adaptive_range():
    assert WARM_K_BUCKETS == tuple(sorted(WARM_K_BUCKETS))
    assert k_bucket(1) == WARM_K_BUCKETS[0]
    for k in range(1, max(WARM_K_BUCKETS) + 1):
        b = k_bucket(k)
        assert b >= k and b in WARM_K_BUCKETS
    # beyond the largest bucket clamps (callers cap K there)
    assert k_bucket(10 * max(WARM_K_BUCKETS)) == max(WARM_K_BUCKETS)


def test_kernel_source_hash_is_stable():
    assert kernel_source_hash() == kernel_source_hash()
    assert len(kernel_source_hash()) == 64


def test_second_enable_is_cache_hot(tmp_path):
    """(a): cold warmup populates the persistent cache; after clearing
    the in-memory jit caches, a fresh engine's warmup is served entirely
    from disk."""
    versioned = enable_persistent_compilation_cache(str(tmp_path / "cc"))
    assert kernel_source_hash()[:16] in versioned

    # earlier tests in the same process may already hold these programs
    # in the in-memory jit cache (no compile → no cache-miss events);
    # drop them so the cold warmup genuinely compiles into the temp dir
    jax.clear_caches()
    eng = BatchedQuorumEngine(16, 4, event_cap=64)
    s0 = compilation_cache_stats()
    stats = eng.warmup_fused(k_buckets=(4,), background=False)
    assert stats["error"] is None
    assert eng.fused_ready
    # 2 fused (reads on/off) + 2 sparse + 2 sparse-votes (tick on/off)
    # + 2 dense + 2 dense-votes + 2 dense read + 2 dense-votes read
    assert stats["programs"] == 14
    s1 = compilation_cache_stats()
    assert s1["misses"] > s0["misses"], "cold warmup must populate the cache"

    # the in-process twin of a restart: drop every in-memory executable
    jax.clear_caches()
    eng2 = BatchedQuorumEngine(16, 4, event_cap=64)
    st2 = eng2.warmup_fused(k_buckets=(4,), background=False)
    assert st2["error"] is None
    assert eng2.fused_ready
    assert st2["cache_hits"] > 0, "second enable must hit the persistent cache"
    assert st2["cache_misses"] == 0, (
        f"second enable recompiled {st2['cache_misses']} programs"
    )


def test_warmup_failure_leaves_single_round_path(monkeypatch):
    """A warmup that dies must leave the latch unset (the coordinator
    simply stays on the single-round path) — never a crashed engine."""
    eng = BatchedQuorumEngine(8, 3, event_cap=32)
    monkeypatch.setattr(
        eng, "_warm_one",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")),
    )
    stats = eng.warmup_fused(k_buckets=(4,), background=False)
    assert stats["error"] is not None
    assert not eng.fused_ready


class FakeNode:
    """Minimal node shim (the test_device_ticks pattern): commit effects
    re-checked under raftMu with the scalar guards intact."""

    def __init__(self, cid, raft):
        self.cluster_id = cid
        self.raft_mu = threading.RLock()

        class _P:
            pass

        self.peer = _P()
        self.peer.raft = raft
        self.commits = []
        self.confirms = []

    def offload_commit(self, q):
        r = self.peer.raft
        with self.raft_mu:
            if r.is_leader() and r.log.try_commit(q, r.term):
                self.commits.append(q)

    def offload_election(self, won, term):
        pass

    def offload_read_confirm(self, low, high, term):
        self.confirms.append((low, high, term))

    def offload_read_echo(self, node_id, low, high):
        pass

    def offload_tick_elect(self):
        pass

    def offload_tick_heartbeat(self):
        pass

    def offload_tick_demote(self):
        pass


def _mk_coord_cluster(n_groups=4, warm=False):
    from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator

    coord = TpuQuorumCoordinator(
        capacity=64, n_peers=4, drive_ticks=True, interval_s=60.0,
        warm_fused=warm,
    )
    return coord, _register_leaders(coord, n_groups)


def _register_leaders(coord, n_groups):
    from dragonboat_tpu.raft import InMemLogDB
    from tests.raft_harness import new_test_raft

    nodes = {}
    for g in range(n_groups):
        cid = 1 + g
        r = new_test_raft(1, [1, 2, 3], 10, 1, InMemLogDB())
        r.cluster_id = cid
        r.become_candidate()
        r.become_leader()
        n = FakeNode(cid, r)
        r.offload = coord
        nodes[cid] = n
        coord._nodes[cid] = n
        with coord._mu:
            coord._sync_row_locked(n)
    coord.flush()
    return nodes


def _drive_round(coord, nodes, ticks=4):
    """One write per group + a tick burst, flushed synchronously."""
    from dragonboat_tpu.wire import Entry

    for cid, n in nodes.items():
        r = n.peer.raft
        with n.raft_mu:
            r.append_entries([Entry(cmd=b"w")])
            idx = r.log.last_index()
        coord.ack(cid, 2, idx)
        coord.ack(cid, 3, idx)
    for _ in range(ticks):
        coord.request_tick()
    coord.flush()


def test_proposals_never_block_on_warmup():
    """(b): while the warmup thread compiles, rounds keep completing on
    the single-round path — zero fused dispatches before the latch, the
    skip reason on record, commits landing throughout; after the latch, a
    tick backlog fuses; and from the warm-up's start to the end nothing
    compiles on the thread that dispatches."""
    from dragonboat_tpu.ops.engine import compilation_log

    coord, nodes = _mk_coord_cluster(warm=False)
    try:
        obs = coord.enable_obs()
        # a cold coordinator's first ticking round compiles its
        # single-round program on first use: before the window, so that
        # "already compiled" below is true of every round inside it
        _drive_round(coord, nodes, ticks=4)
        t = coord.start_warmup()
        assert t is not None
        window = len(compilation_log())
        rounds_during_warm = 0
        while not coord.eng.fused_ready and rounds_during_warm < 2000:
            _drive_round(coord, nodes, ticks=4)
            rounds_during_warm += 1
            if not coord.eng.fused_ready:
                # every round that ran before the latch stayed on the
                # already-compiled single-round programs
                assert coord.fused_dispatches == 0
        t.join(timeout=300)
        assert coord.eng.fused_ready, coord.warmup_stats
        assert coord.warmup_stats["error"] is None
        # commits landed the whole time (proposals were never stalled
        # behind the compile thread)
        for cid, n in nodes.items():
            r = n.peer.raft
            assert r.log.committed == r.log.last_index(), (
                cid, r.log.committed, r.log.last_index(),
            )
        spans = obs.recorder.spans()
        if rounds_during_warm:
            assert any(
                s.get("fuse_skip") == "warmup" for s in spans
                if s["kind"] == "coord_round"
            ), "deficit rounds during warmup must record the skip reason"
        assert any(s["kind"] == "warmup" for s in spans)

        # after the latch: a tick backlog replays as ONE fused dispatch
        before = coord.fused_dispatches
        _drive_round(coord, nodes, ticks=6)
        assert coord.fused_dispatches == before + 1
        fused_spans = [
            s for s in obs.recorder.spans() if s["kind"] == "fused"
        ]
        assert any(s.get("k_rounds", 0) > 1 for s in fused_spans)
        # the tentpole's headline contract: every compile of the window
        # ran on the warm-up thread, none where a round dispatches (a
        # first-use compile would name this thread)
        assert [
            (e[2], e[3]) for e in compilation_log()[window:]
            if e[3] != t.name
        ] == []
        for cid, n in nodes.items():
            r = n.peer.raft
            assert r.log.committed == r.log.last_index()
    finally:
        coord.stop()


def test_warmup_metrics_published():
    """The ``dragonboat_device_warmup_seconds`` family lands in the
    registry the moment obs is enabled, and accumulates once warmup
    runs."""
    from dragonboat_tpu.events import MetricsRegistry
    from dragonboat_tpu.obs import FlightRecorder

    reg = MetricsRegistry()
    eng = BatchedQuorumEngine(8, 3, event_cap=32)
    eng.enable_obs(recorder=FlightRecorder(), registry=reg)
    import io

    buf = io.StringIO()
    reg.write_health_metrics(buf)
    assert "dragonboat_device_warmup_seconds" in buf.getvalue()
    stats = eng.warmup_fused(
        k_buckets=(4,), include_single=False, background=False
    )
    assert stats["error"] is None
    buf = io.StringIO()
    reg.write_health_metrics(buf)
    text = buf.getvalue()
    assert "dragonboat_device_warmup_programs_total 2" in text
    # warmup spans carry the variant + compile wall, and never trip the
    # stall watchdog (compile_ms is not a watchdog field)
    spans = [s for s in eng._obs.recorder.spans() if s["kind"] == "warmup"]
    assert len(spans) == 2
    assert all("compile_ms" in s and not s.get("stalled") for s in spans)


def test_warm_plan_is_every_program_a_live_coordinator_dispatches():
    """The plan, spelled out: the fused block per K bucket and the three
    single-round kinds with and without votes, each with and without the
    read plane where the kernel carries it — every one a packed program
    (``ops/packed.py``: blocks in, one ingress block, one egress block)."""
    from dragonboat_tpu.ops import packed

    eng = BatchedQuorumEngine(16, 4, event_cap=64)
    plan = eng.warm_plan()
    assert len(plan) == len(set(plan))
    assert set(plan) == (
        {("fused", k, hr, False) for k in WARM_K_BUCKETS
         for hr in (False, True)}
        | {(kind, dt, False, False) for kind in ("sparse", "sparse_votes")
           for dt in (False, True)}
        | {(kind, dt, hr, False) for kind in ("dense", "dense_votes")
           for dt in (False, True) for hr in (False, True)}
    )
    programs = {
        "fused": packed.quorum_multiround, "sparse": packed.quorum_step,
        "dense": packed.quorum_step_dense,
    }
    for kind, arg, hr, kv in plan + eng.warm_plan(include_kv=True)[-8:]:
        fn, ing, statics = eng._variant_args(kind, arg, hr, kv)
        assert fn is programs[kind.split("_")[0]], kind
        # ONE ingress block, laid out by the rule the live path stages by
        assert ing.ndim == 1 and ing.dtype == "int32"
        assert statics["dims"] == eng._dims


def test_warm_coordinator_compiles_nothing_on_first_use():
    """A cold coordinator that has finished warming compiles nothing on
    the round thread: not at registration, its first write, its first
    tick backlog, its first read, its first election, nor at an election
    while reads are pending (``compilation_log`` names what would)."""
    from dragonboat_tpu.ops.engine import compilation_log
    from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator
    from dragonboat_tpu.wire import Entry

    jax.clear_caches()  # the in-process twin of a cold start
    coord = TpuQuorumCoordinator(
        capacity=64, n_peers=4, drive_ticks=True, interval_s=60.0,
    )
    try:
        t = coord.start_warmup()
        t.join(timeout=600)
        assert coord.eng.fused_ready, coord.warmup_stats
        warmed = len(compilation_log())
        assert warmed > 0

        def fresh():
            return [(e[2], e[3]) for e in compilation_log()[warmed:]]

        nodes = _register_leaders(coord, 4)
        assert fresh() == [], "registration"
        _drive_round(coord, nodes, ticks=0)     # sparse, no tick
        _drive_round(coord, nodes, ticks=1)     # sparse, tick
        assert fresh() == [], "first write"
        before = coord.fused_dispatches
        _drive_round(coord, nodes, ticks=3)     # fused K=4
        _drive_round(coord, nodes, ticks=9)     # fused K=16
        assert coord.fused_dispatches == before + 2
        assert fresh() == [], "first tick backlog"

        def read(cid, ctx, ticks):
            r = nodes[cid].peer.raft
            coord.read_stage(cid, r.log.committed, ctx, ctx, r.term)
            coord.read_ack_hint(cid, 2, ctx, ctx)
            coord.read_ack_hint(cid, 3, ctx, ctx)
            for _ in range(ticks):
                coord.request_tick()
            coord.flush()

        read(1, 101, ticks=0)                   # dense reads, no tick
        read(1, 102, ticks=1)                   # dense reads, tick
        read(2, 103, ticks=5)                   # fused reads
        assert len(nodes[1].confirms) == 2 and nodes[2].confirms
        assert fresh() == [], "first read"

        def campaign(cid, ticks):
            r = nodes[cid].peer.raft
            coord.set_candidate(cid, r.term + 1)
            coord.vote(cid, 2, True)
            coord.vote(cid, 3, True)
            for _ in range(ticks):
                coord.request_tick()
            coord.flush()
            coord.set_leader(cid, r.term + 1, r.log.last_index(),
                             r.log.last_index())
            coord.flush()

        campaign(3, ticks=0)                    # sparse votes, no tick
        campaign(4, ticks=1)                    # sparse votes, tick
        assert fresh() == [], "first election"
        # a campaign while another group's read is pending: dense + votes
        r = nodes[1].peer.raft
        coord.read_stage(1, r.log.committed, 104, 104, r.term)
        coord.read_ack_hint(1, 2, 104, 104)
        campaign(3, ticks=0)
        coord.read_stage(1, r.log.committed, 105, 105, r.term)
        coord.read_ack_hint(1, 2, 105, 105)
        campaign(4, ticks=1)
        assert fresh() == [], "election with reads pending"
        for n in nodes.values():
            with n.raft_mu:
                n.peer.raft.append_entries([Entry(cmd=b"w")])
        _drive_round(coord, nodes, ticks=2)
        assert fresh() == [], "after it all"
        # ... and staged every one of them into a buffer the plan keeps
        assert set(coord.eng._ingress) <= coord.eng._ingress_keep
        assert len(coord.eng._ingress) >= 6
    finally:
        coord.stop()


def test_ingress_buffers_held_are_the_warm_plan_and_one_a_kind():
    """The host buffers an engine keeps for staging are bounded: one per
    shape the warm plan holds (what a live coordinator dispatches), and
    for every other shape — a bench's churn widths, a K of its own — the
    last-used one of its kind."""
    eng = BatchedQuorumEngine(64, 4, event_cap=64)
    fused = dict(has_votes=False, do_tick=True, has_reads=False,
                 has_kv=False)
    for c in (1, 2, 4, 8):
        for k in (4, 16):
            eng._ingress_for("fused", k=k, c=c, has_churn=True, **fused)
    assert set(eng._ingress) == {"fused"}
    a = eng._ingress_for("sparse", cap=64, has_votes=False)
    a.views["n"][0] = 5
    b = eng._ingress_for("sparse", cap=64, has_votes=False)
    assert b is a and b.views["n"][0] == 0  # restaged in place
    assert eng._ingress_for("sparse", cap=64, has_votes=True) is not a
    assert set(eng._ingress) == {"fused", "sparse"}

    eng.warmup_fused(k_buckets=(4,), include_reads=False,
                     include_single=False, background=False)
    assert len(eng._ingress_keep) == 1
    x = eng._ingress_for("fused", k=4, c=0, has_churn=False, **fused)
    eng._ingress_for("fused", k=4, c=8, has_churn=True, **fused)
    assert eng._ingress_for("fused", k=4, c=0, has_churn=False,
                            **fused) is x  # the plan's own is kept
    assert len(eng._ingress) == 3


def test_warm_coordinator_of_a_quiesce_cluster_compiles_nothing():
    """The first quiesce group flips the engine's latch and starts the
    warm-up over (``has_quiesce`` programs); once that is through, the
    round thread compiles nothing: not for activity marks, a peer's
    QUIESCE, the tick that puts rows to sleep, a wake, a tick backlog, a
    read, nor an election of a group that sleeps."""
    from dragonboat_tpu.ops.engine import compilation_log
    from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator

    class QuiesceNode(FakeNode):
        dev_quiesce = True
        _asleep = False

        class quiesce_mgr:
            threshold = 6

        def __init__(self, cid, raft):
            super().__init__(cid, raft)
            self.slept = 0

        def offload_quiesce_enter(self):
            self.slept += 1

    jax.clear_caches()
    coord = TpuQuorumCoordinator(
        capacity=64, n_peers=4, drive_ticks=True, interval_s=60.0,
    )
    try:
        first = coord.start_warmup()
        from dragonboat_tpu.raft import InMemLogDB
        from tests.raft_harness import new_test_raft

        nodes = {}
        for cid in (1, 2, 3, 4):
            r = new_test_raft(1, [1, 2, 3], 10, 1, InMemLogDB())
            r.cluster_id = cid
            r.become_candidate()
            r.become_leader()
            r.offload = coord
            nodes[cid] = QuiesceNode(cid, r)
            coord.register(nodes[cid])
        assert coord.eng.quiesce_enabled
        first.join(timeout=600)  # gave up: the latch flipped under it
        deadline = time.time() + 600
        while not coord.eng.fused_ready and time.time() < deadline:
            time.sleep(0.05)
        assert coord.eng.fused_ready, coord.warmup_stats
        coord.flush()
        warmed = len(compilation_log())

        def fresh():
            return [(e[2], e[3]) for e in compilation_log()[warmed:]]

        _drive_round(coord, nodes, ticks=1)
        coord.quiesce_activity(1)
        coord.quiesce_slept(2, own=False)       # a peer's QUIESCE
        _drive_round(coord, nodes, ticks=0)     # marks, sparse, no tick
        assert fresh() == [], "marks"
        for _ in range(8):                      # past the threshold
            coord.request_tick()
            coord.flush()
        assert all(n.slept == 1 for c, n in nodes.items() if c != 2)
        assert nodes[2].slept == 0              # slept on its peer's word
        q = coord.eng.read_rows(
            "quiesced", [coord.eng.groups[c].row for c in nodes])
        assert q.all()
        assert fresh() == [], "rows went to sleep"
        coord.quiesce_woke(3)
        _drive_round(coord, nodes, ticks=5)     # fused, marks riding
        r = nodes[1].peer.raft
        coord.read_stage(1, r.log.committed, 201, 201, r.term)
        coord.read_ack_hint(1, 2, 201, 201)
        coord.read_ack_hint(1, 3, 201, 201)
        coord.request_tick()
        coord.flush()
        assert nodes[1].confirms
        r4 = nodes[4].peer.raft
        coord.set_candidate(4, r4.term + 1)
        coord.vote(4, 2, True)
        coord.vote(4, 3, True)
        coord.request_tick()
        coord.flush()
        assert fresh() == [], "after it all"
    finally:
        coord.stop()
