"""Chip smoke: the NodeHost -> device-quorum main path, once, on the TPU.

One process, public entry points, two phases on one chip:

* ``live``   — three ``NodeHost``s with ``quorum_engine="tpu"`` running the
  deployment of upstream's published benchmark (BASELINE.md: 48 groups x 3
  replicas, 16-byte proposals, fsync honored) under a 9:1 read:write mix.
  Every acknowledged write is read back linearizably, replicas agree, the
  engine state lives on the accelerator and the fused K-round path fired.
* ``engine`` — one ``BatchedQuorumEngine`` at the BASELINE.json ladder size
  (65,536 groups x 5 peers) fed a seeded ack/vote stream through the
  single-round ``step`` and the fused ``step_rounds``; the whole commit
  vector must equal a pure-numpy kth-largest model, and a seeded sample of
  groups must equal scalar ``Raft`` oracles fed the same messages.

``--chips 4`` runs ONLY the mesh path and what it is compared with: a
``MeshQuorumEngine`` over four devices against one single-device engine on
the same stream, then one live NodeHost set over the mesh.

Each phase prints one JSON line of observations (not metrics).  The last
line of stdout is ``{"ok": true, "device": {...}}`` with the device as jax
reports it.  Any failed check raises: exit code non-zero, no result line.
Without a TPU the script exits non-zero before doing anything, unless
``--rehearse-cpu`` asks for the tiny-size CPU rehearsal (whose lines all
say ``"rehearsal": true`` and ``"platform": "cpu"``).
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: upstream's published benchmark deployment (BASELINE.md:13)
LIVE_GROUPS, LIVE_REPLICAS, PAYLOAD = 48, 3, 16
#: one raft tick.  Upstream's examples run 200ms; 50ms keeps the election
#: wait around a second while leaving leadership stable on a shared host
#: (5ms x election_rtt=10 flaps leaders with three hosts in one process)
RTT_MS = 50
#: BASELINE.json ladder size
ENGINE_GROUPS, ENGINE_PEERS, ENGINE_SAMPLE = 65536, 5, 256
#: tiny sizes of the CPU rehearsal
REHEARSAL = dict(live_groups=6, engine_groups=512, sample=32)


def emit(phase: str, ctx: dict, **obs) -> None:
    line = {"phase": phase, "platform": ctx["platform"], **obs}
    if ctx["rehearsal"]:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)


def check(cond, what: str, *detail) -> None:
    """Validation that survives ``python -O`` (assert does not)."""
    if not cond:
        raise AssertionError(f"{what}: {detail}" if detail else what)


# ---------------------------------------------------------------------------
# phase: native components build from source
# ---------------------------------------------------------------------------


def native_phase(ctx: dict) -> None:
    """The four .so files are git-ignored and built on demand; the copy on
    the chip machine starts without them.  A failed build fails the smoke —
    it must not fall back to the pure-Python codec in silence."""
    from dragonboat_tpu import native
    from dragonboat_tpu.native import natraft, natsm
    from dragonboat_tpu.wire import codec

    native._load()
    natraft._load()
    check(natsm.available(), "libnatsm.so did not build/load")
    check(codec._native is not None, "dbtpu_wirecodec.so did not build/load")
    emit("native", ctx, built=["libnativekv", "libnatraft", "libnatsm",
                               "dbtpu_wirecodec"])


# ---------------------------------------------------------------------------
# phase: live NodeHosts
# ---------------------------------------------------------------------------


def _kv_sm():
    from dragonboat_tpu import Result
    from dragonboat_tpu.statemachine import IStateMachine

    class KV16(IStateMachine):
        """In-memory KV: a 16-byte proposal is an 8-byte key + 8-byte value."""

        def __init__(self, cluster_id, node_id):
            self.kv = {}

        def update(self, cmd):
            self.kv[bytes(cmd[:8])] = bytes(cmd[8:])
            return Result(value=len(self.kv))

        def lookup(self, query):
            return self.kv.get(bytes(query))

        def save_snapshot(self, w, files, done):
            blob = b"".join(k + v for k, v in sorted(self.kv.items()))
            w.write(len(blob).to_bytes(8, "little") + blob)

        def recover_from_snapshot(self, r, files, done):
            n = int.from_bytes(r.read(8), "little")
            blob = r.read(n)
            self.kv = {
                blob[i:i + 8]: blob[i + 8:i + 16] for i in range(0, n, 16)
            }

        def close(self):
            pass

    return KV16


def _wait(pred, timeout_s: float, what: str, poll_s: float = 0.01):
    t0 = time.perf_counter()
    while True:
        got = pred()
        if got:
            return time.perf_counter() - t0
        if time.perf_counter() - t0 > timeout_s:
            raise TimeoutError(f"{what} not reached in {timeout_s}s")
        time.sleep(poll_s)


def _assert_state_on(platform: str, eng, n_devices: int, tag: str) -> list:
    """Every leaf of the engine's device state sits on ``platform``; a
    mesh engine's shard i sits on device i (n distinct devices)."""
    import jax

    shards = getattr(eng, "shards", None) or [eng]
    homes = []
    for s in shards:
        devs = set()
        for leaf in jax.tree_util.tree_leaves(s.dev):
            devs |= set(leaf.devices())
        check(len(devs) == 1, f"{tag}: shard state spans devices", devs)
        (d,) = devs
        check(d.platform == platform, f"{tag}: state not on {platform}", d)
        homes.append(d)
    check(len(set(homes)) == n_devices,
          f"{tag}: want {n_devices} distinct devices", homes)
    if len(shards) > 1:
        check(homes == list(eng.devices), f"{tag}: shard i not on device i",
              homes, eng.devices)
        glob = eng.dev  # the assembled P(groups)-sharded global view
        check(glob.match.shape[0] == eng.n_groups, f"{tag}: global view shape")
        check(len(glob.match.sharding.device_set) == n_devices,
              f"{tag}: global view does not span the mesh")
    return [str(d) for d in homes]


def live_phase(ctx: dict, groups: int, mesh_devices: int = 0,
               passes: int = 2) -> None:
    from dragonboat_tpu import Config, NodeHostConfig, monkey
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.ops.engine import compilation_cache_stats
    from dragonboat_tpu.transport import ChanRouter, ChanTransport

    sm = _kv_sm()
    router = ChanRouter()
    addrs = {i: f"smoke{i}:1" for i in range(1, LIVE_REPLICAS + 1)}
    cids = list(range(1, groups + 1))
    rng = np.random.default_rng(ctx["seed"])
    base = tempfile.mkdtemp(prefix="chip-smoke-")
    nhs = []
    cc0 = compilation_cache_stats()
    try:
        t_boot = time.perf_counter()
        for i in addrs:
            nhs.append(NodeHost(NodeHostConfig(
                node_host_dir=f"{base}/nh{i}",
                rtt_millisecond=RTT_MS,
                raft_address=addrs[i],
                raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                    src, rh, ch, router=router
                ),
                expert=ExpertConfig(
                    quorum_engine="tpu", fast_lane=False,
                    engine_block_groups=max(groups, 64),
                    engine_mesh_devices=mesh_devices,
                ),
            )))
        hosts_s = time.perf_counter() - t_boot
        for i, nh in enumerate(nhs, start=1):
            for cid in cids:
                nh.start_cluster(
                    addrs, False, sm,
                    Config(cluster_id=cid, node_id=i, election_rtt=10,
                           heartbeat_rtt=1),
                )
        boot_s = time.perf_counter() - t_boot
        election_s = _wait(
            lambda: all(
                any(nh.get_leader_id(cid)[1] for nh in nhs) for cid in cids
            ),
            180.0, f"leaders for {groups} groups",
        )
        coords = [nh.quorum_coordinator for nh in nhs]
        check(all(c is not None for c in coords), "a NodeHost has no engine")
        # the ten fused/single-round programs compile on a background
        # thread per host; the load below wants them warm (a first-use
        # compile on the round thread stalls that host's commits)
        warm_wait_s = _wait(
            lambda: all(c.eng.fused_ready for c in coords), 600.0,
            "fused warm-up on every host", poll_s=0.05,
        )

        # ---- load: 9:1 read:write over every group, from every host ----
        lat_ms, acked, reads = [], {}, 0
        mu = threading.Lock()

        def drive(cid: int, p: int) -> None:
            nonlocal reads
            nh = nhs[(cid + p) % len(nhs)]
            sess = nh.get_noop_session(cid)
            key = cid.to_bytes(4, "little") + p.to_bytes(4, "little")
            val = rng_bytes[cid, p]
            t0 = time.perf_counter()
            nh.sync_propose(sess, key + val, timeout=30.0)
            dt = (time.perf_counter() - t0) * 1e3
            for j in range(9):
                # linearizable reads, rotated over the hosts: an
                # acknowledged write is visible from every replica's host
                got = nhs[(cid + p + j) % len(nhs)].sync_read(
                    cid, key, timeout=30.0
                )
                check(got == val, "acknowledged write not read back",
                      cid, p, got, val)
            with mu:
                lat_ms.append(dt)
                acked[(cid, p)] = (key, val)
                reads += 9

        rng_bytes = {
            (cid, p): rng.bytes(8) for cid in cids for p in range(passes + 20)
        }
        t_load = time.perf_counter()
        with ThreadPoolExecutor(max_workers=4) as pool:
            for p in range(passes):
                for f in [pool.submit(drive, cid, p) for cid in cids]:
                    f.result()
            # the fused K-round program replays a tick backlog (deficit
            # > 1).  Whether the load above produced one depends on host
            # scheduling, so provoke one per host the way a stalled tick
            # worker does — two ticks before the next round — and see it
            # ride ONE fused dispatch
            fused_natural = [c.fused_dispatches for c in coords]
            for c in coords:
                before = c.fused_dispatches
                for attempt in range(20):
                    c.request_tick()
                    c.request_tick()
                    drive(cids[attempt % groups], passes + attempt)
                    if c.fused_dispatches > before:
                        break
                check(c.fused_dispatches > before,
                      "a tick backlog did not take the fused path",
                      c.warmup_stats)
        load_s = time.perf_counter() - t_load
        for c in coords:
            check(c.warmup_stats.get("error") is None, "warm-up failed",
                  c.warmup_stats)

        t_checks = time.perf_counter()
        # ---- every acknowledged write, once more, after the load ----
        for (cid, p), (key, val) in acked.items():
            got = nhs[p % len(nhs)].sync_read(cid, key, timeout=30.0)
            check(got == val, "write lost after load", cid, p)

        # ---- replicas agree (hash at equal applied index) ----
        def converged():
            try:
                for cid in cids:
                    monkey.assert_replicas_converged(nhs, cid)
                return True
            except AssertionError:
                return False

        _wait(converged, 60.0, "replica convergence", poll_s=0.05)
        for cid in cids:
            kvs = [nh.stale_read(cid, acked[(cid, 0)][0]) for nh in nhs]
            check(len(set(kvs)) == 1, "replica SM contents differ", cid, kvs)

        # ---- the engine owns the groups, on the accelerator ----
        homes = []
        for c in coords:
            check(set(c.eng.groups) == set(cids),
                  "engine does not hold every group", len(c.eng.groups))
            with c._mu:  # a concurrent dispatch donates the state
                homes.append(_assert_state_on(
                    ctx["platform"], c.eng, max(mesh_devices, 1), "live"
                ))
        cc1 = compilation_cache_stats()
        checks_s = time.perf_counter() - t_checks
        lat = np.array(lat_ms)
        emit(
            "live_mesh" if mesh_devices else "live", ctx,
            groups=groups, replicas=LIVE_REPLICAS, payload_bytes=PAYLOAD,
            writes=len(lat_ms), reads=reads,
            nodehosts_s=round(hosts_s, 3), boot_s=round(boot_s, 3),
            election_wait_s=round(election_s, 3),
            warmup_wait_s=round(warm_wait_s, 3), load_s=round(load_s, 3),
            checks_s=round(checks_s, 3),
            warmup_s=[round(c.warmup_stats["seconds"], 3) for c in coords],
            warmup_programs=[c.warmup_stats["programs"] for c in coords],
            fused_dispatches_under_load=fused_natural,
            fused_dispatches=[c.fused_dispatches for c in coords],
            cache_dir=cc1["dir"],
            cache_hits=cc1["hits"] - cc0["hits"],
            cache_misses=cc1["misses"] - cc0["misses"],
            commit_latency_ms_p50=float(np.percentile(lat, 50)),
            commit_latency_ms_max=float(lat.max()),
            state_devices=homes[0],
        )
    finally:
        for nh in nhs:
            nh.stop()
        shutil.rmtree(base, ignore_errors=True)


# ---------------------------------------------------------------------------
# phase: engine at deployment scale — seeded stream vs numpy model + oracles
# ---------------------------------------------------------------------------


def _oracle(peers, elected: bool):
    """A scalar Raft for node 1 of a fresh group: campaigning at term 1,
    or already elected (noop at index 1)."""
    from dragonboat_tpu.config import Config
    from dragonboat_tpu.raft import InMemLogDB, Raft
    from dragonboat_tpu.raft.remote import Remote
    from dragonboat_tpu.wire import Message, MessageType as MT

    r = Raft(
        Config(node_id=1, cluster_id=1, election_rtt=10, heartbeat_rtt=1),
        InMemLogDB(), seed=1,
    )
    for p in peers:
        if p not in r.remotes:
            r.remotes[p] = Remote(next=1)
    r.reset_match_value_array()
    r.has_not_applied_config_change = lambda: False
    r.handle(Message(from_=1, to=1, type=MT.ELECTION))
    check(r.is_candidate() and r.term == 1, "oracle did not campaign")
    if elected:
        for p in peers[1:3]:
            r.handle(Message(from_=p, to=1, term=1,
                             type=MT.REQUEST_VOTE_RESP))
        check(r.is_leader() and r.log.last_index() == 1, "oracle not leader")
    return r


def _ack_rows(eng, rows, slots, rels) -> None:
    """Bulk acks in global-row space; a mesh engine takes them per shard
    (global row = shard * groups_per_shard + local row)."""
    shards = getattr(eng, "shards", None)
    if shards is None:
        eng.ack_block(rows, slots, rels)
        return
    per = eng.shard_groups
    sid = rows // per
    for i, s in enumerate(shards):
        m = sid == i
        s.ack_block(rows[m] - i * per, slots[m], rels[m])


def engine_stream(ctx: dict, engines: dict, groups: int, n_peers: int,
                  sample: int, rounds: int = 4, fused_blocks: int = 2) -> dict:
    """Drive ONE seeded ack/vote stream into every engine of ``engines``,
    a numpy model of all rows, and scalar Raft oracles on a sample; compare
    the whole commit vector after the election rounds, after every
    single-round ``step`` and after every fused ``step_rounds`` block."""
    from dragonboat_tpu.ops.engine import WARM_K_BUCKETS
    from dragonboat_tpu.wire import Entry, Message, MessageType as MT

    rng = np.random.default_rng(ctx["seed"])
    peers = list(range(1, n_peers + 1))
    quorum = n_peers // 2 + 1
    k = max(WARM_K_BUCKETS)
    cids = np.arange(1, groups + 1)
    is_cand = rng.random(groups) < 0.125
    sampled = rng.choice(groups, size=sample, replace=False)
    oracles = {int(g): _oracle(peers, not is_cand[g]) for g in sampled}

    t0 = time.perf_counter()
    rows_of = {}
    for name, eng in engines.items():
        for g in range(groups):
            cid = int(cids[g])
            eng.add_group(cid, node_ids=peers, self_id=1)
            if is_cand[g]:
                eng.set_candidate(cid, term=1)
                eng.vote(cid, 1, granted=True)  # campaign self-vote
            else:
                eng.set_leader(cid, term=1, term_start=1, last_index=1)
        rows_of[name] = np.array(
            [eng.groups[int(c)].row for c in cids], np.int64
        )
    register_s = time.perf_counter() - t0

    # ---- numpy model of every row ----
    leader = ~is_cand
    last = np.where(leader, 1, 0).astype(np.int64)
    match = np.zeros((groups, n_peers), np.int64)
    match[leader, 0] = 1
    committed = np.zeros(groups, np.int64)

    def compare(tag: str) -> None:
        views = {}
        for name, eng in engines.items():
            views[name] = eng.committed_view()[rows_of[name]]
            bad = np.flatnonzero(views[name] != committed)
            check(bad.size == 0, f"{tag}: {name} commit vector != numpy model",
                  bad[:4], views[name][bad[:4]], committed[bad[:4]])
        for g, r in oracles.items():
            check(int(committed[g]) == r.log.committed,
                  f"{tag}: scalar oracle disagrees", g, int(committed[g]),
                  r.log.committed)
        names = list(views)
        for other in names[1:]:
            check(np.array_equal(views[names[0]], views[other]),
                  f"{tag}: {names[0]} and {other} commit vectors differ")

    # ---- elections: two vote rounds through the single-round step ----
    t_elect = time.perf_counter()
    cand = np.flatnonzero(is_cand)
    grants = rng.random((groups, n_peers)) < 0.6
    grants[:, 0] = True
    undecided = is_cand.copy()
    n_won = n_lost = 0
    for voters in (peers[1:3], peers[3:]):
        for g in cand:
            if not undecided[g]:
                continue
            for p in voters:
                for eng in engines.values():
                    eng.vote(int(cids[g]), p, granted=bool(grants[g, p - 1]))
                r = oracles.get(int(g))
                if r is not None:
                    r.handle(Message(
                        from_=p, to=1, term=1, type=MT.REQUEST_VOTE_RESP,
                        reject=not grants[g, p - 1],
                    ))
        seen = slice(0, voters[-1])
        yes = grants[:, seen].sum(axis=1)
        no = (~grants[:, seen]).sum(axis=1)
        want_won = set(cids[undecided & (yes >= quorum)].tolist())
        want_lost = set(cids[undecided & (no >= quorum)].tolist())
        for name, eng in engines.items():
            res = eng.step(do_tick=False)
            check(set(res.won) == want_won, f"{name}: won set differs",
                  len(res.won), len(want_won))
            check(set(res.lost) == want_lost, f"{name}: lost set differs",
                  len(res.lost), len(want_lost))
        for eng in engines.values():
            # one bulk device->host pull for every row about to change
            # (the coordinator's _drain_locked does the same), not a
            # readback per transition
            eng.sync_rows([
                eng.groups[cid].row for cid in want_won | want_lost
            ])
        for cid in want_won:
            g = cid - 1
            r = oracles.get(g)
            if r is not None:
                check(r.is_leader() and r.log.last_index() == 1,
                      "oracle did not win", g)
            for eng in engines.values():
                eng.set_leader(cid, term=1, term_start=1, last_index=1)
            leader[g], last[g], match[g, 0] = True, 1, 1
            undecided[g] = False
        for cid in want_lost:
            g = cid - 1
            r = oracles.get(g)
            if r is not None:
                check(r.is_follower(), "oracle did not lose", g)
            for eng in engines.values():
                eng.set_follower(cid, term=1)
            undecided[g] = False
        n_won += len(want_won)
        n_lost += len(want_lost)
    check(not undecided.any(), "a candidate stayed undecided")
    compare("elections")
    elect_s = time.perf_counter() - t_elect

    # ---- replication: one seeded round of proposals + (stale) acks ----
    def stage_round() -> None:
        nprop = np.where(leader & (rng.random(groups) < 0.7),
                         rng.integers(1, 3, groups), 0)
        last[:] = last + nprop
        acks = leader[:, None] & (rng.random((groups, n_peers)) < 0.5)
        acks[:, 0] = nprop > 0  # the leader's own append
        idx = (rng.random((groups, n_peers)) * (last[:, None] + 1)).astype(
            np.int64
        )
        idx[:, 0] = last
        g_i, p_i = np.nonzero(acks)
        rels = idx[g_i, p_i]
        for name, eng in engines.items():
            _ack_rows(eng, rows_of[name][g_i], p_i.astype(np.int32),
                      rels.astype(np.int32))
        for g, r in oracles.items():
            for _ in range(int(nprop[g])):
                r.handle(Message(from_=1, to=1, type=MT.PROPOSE,
                                 entries=[Entry(cmd=b"x" * PAYLOAD)]))
            for p in np.flatnonzero(acks[g, 1:]) + 2:
                r.handle(Message(from_=int(p), to=1, term=1,
                                 type=MT.REPLICATE_RESP,
                                 log_index=int(idx[g, p - 1])))
        np.maximum(match, np.where(acks, idx, 0), out=match)
        # matched[n - quorum] of the sorted row (raft.go:888-909); only
        # entries of the leader's own term (index >= term_start) commit
        kth = np.sort(match, axis=1)[:, n_peers - quorum]
        ok = leader & (kth >= 1)
        committed[ok] = np.maximum(committed[ok], kth[ok])

    t_phase = time.perf_counter()
    t_single = []
    for rnd in range(rounds):
        stage_round()
        for eng in engines.values():
            t0 = time.perf_counter()
            eng.step(do_tick=False)
            t_single.append(time.perf_counter() - t0)
        compare(f"step round {rnd}")

    single_s = time.perf_counter() - t_phase
    t_phase = time.perf_counter()
    t_fused = []
    for blk in range(fused_blocks):
        for _ in range(k):
            stage_round()
            for eng in engines.values():
                eng.begin_round()
        for eng in engines.values():
            t0 = time.perf_counter()
            if blk % 2:  # double-buffered form: dispatch, then harvest
                eng.step_rounds(do_tick=False, pipelined=True)
                eng.harvest()
            else:
                eng.step_rounds(do_tick=False)
            t_fused.append(time.perf_counter() - t0)
        compare(f"step_rounds block {blk} (K={k})")

    check(int(committed.max()) > 0 and (committed[leader] > 0).mean() > 0.9,
          "the stream committed almost nothing — the check is vacuous")
    return dict(
        groups=groups, peers=n_peers, sampled_oracles=sample,
        candidates=int(is_cand.sum()), won=n_won, lost=n_lost,
        single_rounds=rounds, fused_blocks=fused_blocks, k=k,
        committed_max=int(committed.max()),
        committed_sum=int(committed.sum()),
        register_s=round(register_s, 3), elections_s=round(elect_s, 3),
        single_rounds_s=round(single_s, 3),
        fused_blocks_s=round(time.perf_counter() - t_phase, 3),
        first_step_s=round(t_single[0], 3),
        first_fused_s=round(t_fused[0], 3),
    )


def engine_phase(ctx: dict, groups: int, sample: int) -> None:
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine
    from dragonboat_tpu.ops.state import state_layout

    eng = BatchedQuorumEngine(
        groups, ENGINE_PEERS, event_cap=max(4 * groups, 4096),
        device_ticks=False,
    )
    obs = engine_stream(ctx, {"engine": eng}, groups, ENGINE_PEERS, sample)
    homes = _assert_state_on(ctx["platform"], eng, 1, "engine")
    predicted = sum(
        f["nbytes"] for f in state_layout(groups, ENGINE_PEERS).values()
    )
    stats = ctx["devices"][0].memory_stats() or {}
    emit("engine", ctx, **obs, state_bytes_predicted=predicted,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         state_devices=homes)


def mesh_phase(ctx: dict, groups_per_shard: int, sample: int) -> None:
    """Four per-shard engines on four devices vs ONE single-device engine,
    same seeded stream: identical commit vectors, shard i on device i."""
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine
    from dragonboat_tpu.ops.mesh import MeshQuorumEngine

    n = len(ctx["devices"])
    groups = n * groups_per_shard
    mesh = MeshQuorumEngine(
        groups, ENGINE_PEERS, event_cap=max(4 * groups, 4096),
        devices=ctx["devices"], device_ticks=False,
    )
    single = BatchedQuorumEngine(
        groups, ENGINE_PEERS, event_cap=max(4 * groups, 4096),
        device_ticks=False,
    )
    try:
        obs = engine_stream(
            ctx, {"mesh": mesh, "single": single}, groups, ENGINE_PEERS,
            sample,
        )
        homes = _assert_state_on(ctx["platform"], mesh, n, "mesh")
        _assert_state_on(ctx["platform"], single, 1, "single")
        emit("mesh", ctx, **obs, shards=n, shard_devices=homes,
             identical_commit_vectors=True)
    finally:
        mesh.stop()


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path and its comparison")
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on (virtual) CPU devices; not a chip run")
    args = ap.parse_args(argv)

    from dragonboat_tpu import hostplatform

    if args.rehearse_cpu:
        hostplatform.set_host_device_count(args.chips)
        hostplatform.force_cpu()
    else:
        hostplatform.require_tpu(args.chips)
    import jax

    devices = jax.devices()[: args.chips]
    check(len(devices) == args.chips, "not enough devices", jax.devices())
    d0 = devices[0]
    ctx = dict(
        seed=args.seed, rehearsal=args.rehearse_cpu, devices=devices,
        platform=d0.platform,
    )
    live_groups = REHEARSAL["live_groups"] if args.rehearse_cpu else LIVE_GROUPS
    eng_groups = (
        REHEARSAL["engine_groups"] if args.rehearse_cpu else ENGINE_GROUPS
    )
    sample = REHEARSAL["sample"] if args.rehearse_cpu else ENGINE_SAMPLE

    t0 = time.perf_counter()
    native_phase(ctx)
    if args.chips == 4:
        mesh_phase(ctx, eng_groups, sample)
        live_phase(ctx, live_groups, mesh_devices=4)
    else:
        live_phase(ctx, live_groups)
        engine_phase(ctx, eng_groups, sample)
    from dragonboat_tpu.ops.engine import compilation_cache_stats

    cc = compilation_cache_stats()
    emit("total", ctx, wall_s=round(time.perf_counter() - t0, 3),
         cache_dir=cc["dir"], cache_hits=cc["hits"],
         cache_misses=cc["misses"])
    result = {
        "ok": True,
        "device": {
            "platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()),
        },
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
