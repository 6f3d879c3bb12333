"""Benchmark: batched quorum engine write throughput.

Headline metric (BASELINE.json): writes/sec through the quorum path at 16B
payload vs active group count.  The reference's published peak is 9M
writes/sec over 48 groups on a 3-node cluster (README Performance,
SURVEY.md §6).

Two operating points are measured, mirroring the reference's own
throughput-vs-latency trade (`docs/test.md:40-53` tables):

* **pipelined** — G groups each commit one write per engine round; the host
  stages R rounds of event batches and the device scans them in ONE fused
  dispatch (``quorum_multistep``), amortizing host↔device latency.  This is
  the throughput-maximal mode (the analog of the reference's
  accept-while-in-flight pipelining, ``execengine.go:954-966``).
* **latency-bounded** — continuous small-R dispatches (R from
  BENCH_LAT_ROUNDS, default 1) measuring per-dispatch wall time; the p99 of
  that is the device-side commit-latency floor (BASELINE.md's "P99 commit
  latency" axis).

Platform contract: the device sections run on the TPU or the script exits
non-zero before measuring anything (``hostplatform.require_tpu``).
``BENCH_PLATFORM=cpu`` is the explicit rehearsal setting; every section then
says ``platform: cpu`` and none of its numbers is a device metric.  On
success the script prints exactly one JSON line {"metric", "value", "unit",
"vs_baseline", "detail"} on stdout.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

BASELINE_WRITES_PER_SEC = 9_000_000.0


def _note(msg: str) -> None:
    """Diagnostics go to stderr — stdout carries exactly one JSON line."""
    print(f"# {msg}", file=sys.stderr)


def _resolve_platform() -> str:
    """The one platform decision: the explicit cpu rehearsal, or the TPU,
    or an error — never a quiet fallback."""
    from dragonboat_tpu import hostplatform

    forced = os.environ.get("BENCH_PLATFORM")
    if forced == "cpu":
        hostplatform.force_cpu()
        return "cpu"
    if forced is not None:
        raise SystemExit(f"BENCH_PLATFORM={forced!r}: only 'cpu' is supported")
    return hostplatform.require_tpu()[0].platform


def build_state(n_groups: int, event_cap: int, n_peers: int = 3,
                device_ticks: bool = True):
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine

    eng = BatchedQuorumEngine(
        n_groups, n_peers, event_cap=event_cap, device_ticks=device_ticks
    )
    peers = list(range(1, n_peers + 1))
    for cid in range(1, n_groups + 1):
        eng.add_group(cid, node_ids=peers, self_id=1)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
    eng._upload_dirty()
    return eng


def _staged_multistep_fn(n_groups: int, rounds: int):
    """Jitted R-round staged dispatch; event tensors derived on device.

    Uses the DENSE ingestion kernel (kernels.quorum_step_dense_impl): a
    round's acks collapse into a per-(group, peer) max matrix — exact,
    because scatter-max aggregation is order-independent — and ingestion
    becomes pure elementwise max/or, which measured 7× faster than the
    scatter form at this shape (14.0 → 2.0 ms/round at 131k groups).
    Each round every group's leader self-acks and one follower acks the
    next index, the same per-round traffic the sparse staging produced
    (committed advances exactly one index per group per round; _run_mode
    asserts it).
    """
    import jax
    import jax.numpy as jnp

    from dragonboat_tpu.ops.kernels import quorum_step_dense_impl

    n_peers = 3

    @functools.partial(jax.jit, donate_argnums=(0,))
    def staged_multistep(st, base_index):
        touched = jnp.broadcast_to(
            jnp.arange(n_peers, dtype=jnp.int32)[None, :] < 2,
            (n_groups, n_peers),
        )

        def body(carry, r):
            vals = jnp.where(
                jnp.arange(n_peers, dtype=jnp.int32)[None, :] < 2,
                base_index + 1 + r,
                0,
            )
            ack_max = jnp.broadcast_to(vals, (n_groups, n_peers))
            out = quorum_step_dense_impl(
                carry,
                ack_max,
                touched,
                jnp.zeros((1, 1), jnp.int8),
                do_tick=True,
                # every benched row is a LEADER (build_state set_leader),
                # and the contact reset writes only non-leader rows —
                # provably a no-op here, so it compiles out; ticks
                # themselves stay on (heartbeat/check-quorum clocks run)
                track_contact=False,
                has_votes=False,
            )
            return out.state, None

        st, _ = jax.lax.scan(
            body, st, jnp.arange(rounds, dtype=jnp.int32)
        )
        from dragonboat_tpu.ops.kernels import StepOutputs, TickFlags

        zeros = jnp.zeros((n_groups,), bool)
        return StepOutputs(
            st, st.committed, zeros, zeros, TickFlags(zeros, zeros, zeros)
        )

    return staged_multistep


def _run_mode(n_groups: int, rounds: int, dispatches: int, warmup: int = 3):
    """Run one operating point; returns (writes/s, per-dispatch times)."""
    import jax
    import jax.numpy as jnp

    # event_cap only matters for the engine's own sparse staging (unused
    # by the dense staged dispatch); keep it minimal
    eng = build_state(n_groups, 64)
    st = eng.dev
    staged = _staged_multistep_fn(n_groups, rounds)

    def dispatch(st, base_index):
        t0 = time.perf_counter()
        out = staged(st, jnp.int32(base_index))
        committed = np.asarray(out.committed)  # egress readback (blocks)
        return out.state, committed, time.perf_counter() - t0

    base = 1
    committed = None
    for _ in range(warmup):
        st, committed, _ = dispatch(st, base)
        base += rounds
    assert committed[0] == base, (committed[:4], base)

    times = []
    t0 = time.perf_counter()
    for _ in range(dispatches):
        st, committed, dt = dispatch(st, base)
        times.append(dt)
        base += rounds
    elapsed = time.perf_counter() - t0
    assert committed[0] == base

    writes = n_groups * rounds * dispatches
    return writes / elapsed, times


def _run_e2e(engine: str, extra_env=None, timeout_key: str = "BENCH_E2E_TIMEOUT") -> dict:
    """Run bench_e2e in a killable subprocess tree.

    Called BEFORE this process initializes jax: in multiprocess mode the
    rank-0 child attaches to the (single) TPU chip, which must not be held
    by the parent at that point.  The child decides its own platform
    (bench_e2e._decide_platform: the chip, or the inherited
    BENCH_PLATFORM=cpu rehearsal, or an error).
    """
    import subprocess

    env = dict(os.environ)
    env["E2E_ENGINE"] = engine
    env.update(extra_env or {})
    timeout_s = float(os.environ.get(timeout_key, "600"))
    env.setdefault("E2E_DEADLINE", str(max(60.0, timeout_s - 60.0)))
    try:
        r = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "bench_e2e.py")],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
        if r.returncode == 0 and r.stdout.strip():
            return json.loads(r.stdout.strip().splitlines()[-1])
        return {
            "error": f"rc={r.returncode}",
            "tail": (r.stderr or r.stdout)[-500:],
        }
    except Exception as e:
        return {"error": repr(e)}


def _check_cancel(cancel) -> None:
    """Cooperative watchdog flag for device-rung workers: a wedged
    dispatch degrades to an error entry, and the daemon worker stops
    DISPATCHING the moment the watchdog gives up — it must not keep
    feeding the device under the later sections."""
    if cancel is not None and cancel.is_set():
        raise RuntimeError("rung cancelled by watchdog")


def _run_host_loop(n_groups: int, rounds: int, k: int = 16,
                   cancel=None) -> dict:
    """Engine throughput through the real host staging path — now the
    K-round FUSED shape every ladder section runs (ISSUE 1 tentpole):
    per scanned round every group's leader self-ack and one follower ack
    are staged via the vectorized bulk-ingest API, ``begin_round`` closes
    the round, and ONE ``step_rounds`` dispatch scans all ``k`` rounds on
    device.  Host staging of block i+1 overlaps the in-flight dispatch of
    block i (``pipelined=True`` double-buffering), and egress is the
    vectorized watermark view — no per-row Python anywhere.  ``rounds``
    counts DISPATCHES; total engine rounds = rounds × k."""
    if rounds < 1 or n_groups < 1 or k < 1:
        return {"error": f"invalid parameters: groups={n_groups} rounds={rounds} k={k}"}
    # host-driven clocks: this mode never ticks on device, so the
    # contact-reset scatter compiles out (see kernels.quorum_step_impl)
    eng = build_state(n_groups, 2 * n_groups, device_ticks=False)
    rows = np.tile(np.arange(n_groups, dtype=np.int32), 2)
    slots = np.concatenate(
        [np.zeros(n_groups, np.int32), np.ones(n_groups, np.int32)]
    )

    def stage_block(base):
        # K rounds in one validated staging call: same (row, slot)
        # geometry every round, advancing rel indexes (ack_block_rounds)
        rels = (
            base + 1 + np.arange(k, dtype=np.int32)[:, None]
            + np.zeros((1, rows.size), np.int32)
        )
        eng.ack_block_rounds(rows, slots, rels)

    # warmup (jit compile of the fused K-round program)
    base = 1
    stage_block(base)
    eng.step_rounds(do_tick=False)
    base += k
    t0 = time.perf_counter()
    for _ in range(rounds):
        _check_cancel(cancel)
        stage_block(base)
        # returns the PREVIOUS block's egress; this block stays in flight
        # while the next one stages (ingress double-buffering)
        eng.step_rounds(do_tick=False, pipelined=True)
        base += k
    eng.harvest()
    view = eng.committed_view()
    elapsed = time.perf_counter() - t0
    assert view[0] == base, (view[:4], base)
    return {
        "groups": n_groups,
        "rounds": rounds,
        "rounds_per_dispatch": k,
        "writes_per_sec": round(n_groups * rounds * k / elapsed, 1),
    }


def _slim_e2e(e2e: dict) -> dict:
    """Headline-safe summary of an e2e result dict.

    The driver records only the last ~2000 chars of output: round 3's
    per-rank fast-lane stats bloated the JSON line past that and truncated
    the metric away (`BENCH_r03.json parsed: null`).  The full dict goes to
    BENCH_DETAIL.json; the stdout line carries only scalars.
    """
    if not isinstance(e2e, dict):
        return e2e
    out = {}
    for k in ("error", "groups", "hosts", "engine", "sm", "leader_mode",
              "writes_per_sec", "setup_s"):
        if k in e2e:
            out[k] = e2e[k]
    lat = e2e.get("commit_latency_ms")
    if isinstance(lat, dict):
        out["commit_latency_ms"] = {
            k: lat[k] for k in ("p50", "p99") if k in lat
        }
    mixed = e2e.get("mixed_phase")
    if isinstance(mixed, dict) and "ops_per_sec" in mixed:
        out["mixed_ops_per_sec"] = mixed["ops_per_sec"]
    fl = e2e.get("fastlane")
    if isinstance(fl, list):
        ranks = [r for r in fl if isinstance(r, dict)]
        if ranks:
            # scalars only: three e2e sections ride one stdout line and
            # the per-rank lists overflowed the driver's 2000-char tail
            # (full per-rank stats live in BENCH_DETAIL.json)
            duties = [
                r.get("enroll_duty") for r in ranks
                if isinstance(r.get("enroll_duty"), (int, float))
            ]
            out["fastlane"] = {
                "enroll_duty_min": min(duties) if duties else None,
                "ejects": sum(
                    sum((r.get("eject_reasons") or {}).values())
                    for r in ranks
                ),
                "dropped_spans": sum(
                    r.get("dropped_spans") or 0 for r in ranks
                ),
            }
    if e2e.get("rank_errors"):
        out["rank_errors"] = len(e2e["rank_errors"])
    if "tail" in e2e:
        out["tail"] = e2e["tail"][-200:]
    return out


def _run_rung4(n_groups: int = 65_536, rounds: int = 8, k: int = 16,
               cancel=None) -> dict:
    """Rung-4 batched-engine numbers (BASELINE.md ladder): 64k groups ×
    5 peer slots — every group commits once per scanned round via the
    vectorized ack_block ingest (quorum of 5 = self + 2 acks), K rounds
    fused per dispatch with double-buffered staging (ISSUE 1 tentpole),
    and sampled commit-watermark queries as the read-side probe.  The
    correctness twin (differential vs scalar oracles + membership/leader
    churn, and the genuinely mixed-load variant) is tests/test_rung4.py
    plus the fused-block differential in tests/test_multiround.py.
    ``rounds`` counts DISPATCHES; total engine rounds = rounds × k.

    A mixed 9:1 PHASE follows the pure-write window (ISSUE 3 tentpole):
    every group stages a batch of 9 ReadIndex requests per scanned round
    alongside its write, two followers echo the batch in the same round,
    and the fused ``read_confirm`` plane releases it in the dispatch that
    advances the commits.  ``reads_per_sec`` is the CONFIRMED ReadIndex
    rate through that plane (the honest read-path number VERDICT r5 weak
    #5 asked for); the old host-side watermark-query rate is kept as
    ``probe_reads_per_sec``."""
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine

    eng = BatchedQuorumEngine(
        n_groups, 5, event_cap=4 * n_groups, device_ticks=False
    )
    peers = [1, 2, 3, 4, 5]
    for cid in range(1, n_groups + 1):
        eng.add_group(cid, node_ids=peers, self_id=1)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
    eng._upload_dirty()
    rows = np.arange(n_groups, dtype=np.int32)
    rows3 = np.concatenate([rows, rows, rows])
    slots = np.concatenate([
        np.zeros(n_groups, np.int32), np.ones(n_groups, np.int32),
        np.full(n_groups, 2, np.int32),
    ])

    def stage_block(start_rel):
        # one validated staging call for the whole K-round block
        rels = (
            start_rel + np.arange(k, dtype=np.int32)[:, None]
            + np.zeros((1, rows3.size), np.int32)
        )
        eng.ack_block_rounds(rows3, slots, rels)

    # warmup (compile the fused K-round program)
    stage_block(2)
    eng.step_rounds(do_tick=False)
    reads = writes = 0
    # read probe rows (~576 sampled watermarks per dispatch): validated
    # against the vectorized egress view the dispatch already paid for
    # (one bulk transfer instead of a device readback per cid).
    # reads_per_sec measures the host-side watermark-query rate over
    # fresh egress data.
    probe = np.arange(0, n_groups, max(1, n_groups // 576), dtype=np.int64)
    rel = k + 1  # committed after warmup
    expect_prev = None  # watermark the in-flight block will land on
    t0 = time.perf_counter()
    for _ in range(rounds):
        _check_cancel(cancel)
        stage_block(rel + 1)
        res = eng.step_rounds(do_tick=False, pipelined=True)
        if res is not None:
            # probe the PREVIOUS block's egress vector directly — it is
            # already host-side; touching committed_view here would
            # harvest (and so serialize) the in-flight dispatch
            assert (res.committed_rel[probe] == expect_prev).all(), (
                res.committed_rel[probe][:4], expect_prev
            )
            reads += probe.size
        expect_prev = rel + k
        rel += k
        writes += n_groups * k
    final = eng.harvest()
    elapsed = time.perf_counter() - t0
    assert (final.committed_rel[probe] == rel).all(), (
        final.committed_rel[probe][:4], rel
    )
    reads += probe.size
    assert eng.committed_index(1) == rel

    # ---- mixed 9:1 phase: ReadIndex through the device read plane ----
    # (per scanned round: 1 write commit + a 9-read ctx batch per group;
    # echoes from followers 2 and 3 land the same round, so read_confirm
    # releases the batch inside the same fused dispatch)
    rows2 = np.concatenate([rows, rows])
    peers2 = np.concatenate(
        [np.ones(n_groups, np.int32), np.full(n_groups, 2, np.int32)]
    )
    counts9 = np.full(n_groups, 9, np.int32)
    reads_confirmed = 0
    mwrites = 0
    mtimes = []

    def mixed_dispatch():
        nonlocal rel
        for _ in range(k):
            rel += 1
            eng.ack_block(rows3, slots, np.full(rows3.size, rel, np.int32))
            sl = eng.stage_read_block(
                rows, np.full(n_groups, rel, np.int32), counts9
            )
            eng.read_ack_block(rows2, np.concatenate([sl, sl]), peers2)
            eng.begin_round()
        return eng.step_rounds(do_tick=False, pipelined=True)

    mixed_dispatch()  # warmup: compile the read-plane fused program
    eng.harvest()
    reads_confirmed = 0
    t0 = time.perf_counter()
    for _ in range(rounds):
        _check_cancel(cancel)
        td0 = time.perf_counter()
        res = mixed_dispatch()
        if res is not None and res.read_counts is not None:
            reads_confirmed += int(res.read_counts.sum())
        mtimes.append(time.perf_counter() - td0)
        mwrites += n_groups * k
    final = eng.harvest()
    melapsed = time.perf_counter() - t0
    if final is not None and final.read_counts is not None:
        reads_confirmed += int(final.read_counts.sum())
    expected = n_groups * 9 * rounds * k
    assert reads_confirmed == expected, (reads_confirmed, expected)
    assert eng.committed_index(1) == rel
    mixed = {
        "read_ratio": 9,
        "reads_per_sec": round(reads_confirmed / melapsed, 1),
        "writes_per_sec": round(mwrites / melapsed, 1),
        "ops_per_sec": round((reads_confirmed + mwrites) / melapsed, 1),
        "read_dispatch_p99_ms": round(
            float(np.percentile(np.array(mtimes) * 1e3, 99)), 3
        ),
    }
    return {
        "groups": n_groups,
        "peer_slots": 5,
        "rounds": rounds,
        "rounds_per_dispatch": k,
        "writes_per_sec": round(writes / elapsed, 1),
        # the ReadIndex-confirmation rate (device read plane); the
        # watermark-probe rate this field used to carry moved to
        # probe_reads_per_sec
        "reads_per_sec": mixed["reads_per_sec"],
        "probe_reads_per_sec": round(reads / elapsed, 1),
        "mixed": mixed,
    }


def _run_cpu_section(fn_name: str, spec: list, timeout: float = 420.0) -> dict:
    """Run a bench section on the LOCAL cpu backend in a subprocess.

    The parent process holds the TPU; these children stay on
    JAX_PLATFORMS=cpu and never load the TPU library (one process per
    chip), and every result says ``platform: cpu``.
    ``spec`` is [env_name, default, env_name, default, ...]; parsing
    happens HERE so a malformed env var degrades one section to an error
    entry instead of zeroing the whole record.
    """
    import subprocess

    try:
        args = [
            int(os.environ.get(spec[i], str(spec[i + 1])))
            for i in range(0, len(spec), 2)
        ]
    except (ValueError, TypeError) as e:
        return {"error": f"bad env for {fn_name}: {e!r}"}

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("BENCH_PLATFORM", None)
    # os._exit after the JSON lands: the result is already on stdout, and
    # interpreter teardown with large donated device buffers + a cleared
    # jit cache (the live-coord axis's restart simulation) can segfault
    # in the XLA CPU client's destructor order — a teardown-only crash
    # that must not discard a completed measurement
    code = (
        "from dragonboat_tpu import hostplatform; hostplatform.force_cpu(); "
        "import json, os, sys, bench; "
        f"print(json.dumps(bench.{fn_name}(*{args!r}))); "
        "sys.stdout.flush(); os._exit(0)"
    )
    try:
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        )
        if r.returncode != 0:
            return {"error": f"rc={r.returncode}", "tail": r.stderr[-400:]}
        out = json.loads(r.stdout.strip().splitlines()[-1])
        out["platform"] = "cpu"
        return out
    except Exception as e:
        return {"error": repr(e)[:300]}


def _run_rung5(n_groups: int = 100_000, rounds: int = 6, k: int = 8,
               churn_block: int = 2_048, cancel=None) -> dict:
    """Rung-5 batched-engine numbers (BASELINE.md ladder, final rung):
    100k groups × 5 peer slots with membership churn ROLLING THROUGH the
    load — every scanned round recycles ``churn_block`` rows while every
    surviving group commits once.  The churn now travels INSIDE the
    dispatched program (``stage_recycle`` → masked row resets in
    ``kernels.quorum_multiround``, the VERDICT §7 design pivot) instead
    of as per-recycle host re-uploads, so K churn+commit rounds fuse into
    ONE dispatch with double-buffered staging.  The correctness twin
    (differential vs scalar oracles, leader transfers, bit-identity every
    round) is tests/test_rung5.py plus the recycle-mid-block differential
    in tests/test_multiround.py.  ``rounds`` counts DISPATCHES."""
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine

    eng = BatchedQuorumEngine(
        n_groups, 5, event_cap=4 * n_groups, device_ticks=False
    )
    peers = [1, 2, 3, 4, 5]
    for cid in range(1, n_groups + 1):
        eng.add_group(cid, node_ids=peers, self_id=1)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
    eng._upload_dirty()
    rows = np.arange(n_groups, dtype=np.int32)
    rows3 = np.concatenate([rows, rows, rows])
    slots = np.concatenate([
        np.zeros(n_groups, np.int32), np.ones(n_groups, np.int32),
        np.full(n_groups, 2, np.int32),
    ])
    rel = np.full(n_groups, 1, np.int64)  # per-row committed rel watermark
    live = np.arange(1, n_groups + 1, dtype=np.int64)  # cid per row
    next_cid = n_groups + 1
    state = {"rel": rel, "next_cid": next_cid, "churn_at": 0}

    def stage_block():
        """K scanned rounds: recycle a rotating row block IN-PROGRAM,
        then every row commits one more entry."""
        rel = state["rel"]
        for _ in range(k):
            lo = state["churn_at"] % n_groups
            block = range(lo, min(lo + churn_block, n_groups))
            for i in block:
                cid = state["next_cid"]
                state["next_cid"] += 1
                eng.stage_recycle(
                    int(live[i]), cid, term=1, term_start=1, last_index=1
                )
                live[i] = cid
                rel[i] = 1
            state["churn_at"] += churn_block
            rel += 1
            rels3 = np.concatenate([rel, rel, rel]).astype(np.int32)
            eng.ack_block(rows3, slots, rels3)
            eng.begin_round()
            state["recycled"] = state.get("recycled", 0) + len(block)

    # warmup (compile the fused churn+commit program)
    stage_block()
    eng.step_rounds(do_tick=False)
    state["recycled"] = 0  # report only the measured window's churn
    probe = np.arange(0, n_groups, max(1, n_groups // 576), dtype=np.int64)
    reads = writes = 0
    prev_rel = None  # expected watermarks of the in-flight block
    t0 = time.perf_counter()
    for _ in range(rounds):
        _check_cancel(cancel)
        stage_block()
        res = eng.step_rounds(do_tick=False, pipelined=True)
        if res is not None:
            # vectorized probe of the PREVIOUS block's egress (rung-4
            # comment: committed_view here would serialize the pipeline)
            assert (res.committed_rel[probe] == prev_rel[probe]).all()
            reads += probe.size
        prev_rel = rel.copy()
        writes += n_groups * k
    final = eng.harvest()
    elapsed = time.perf_counter() - t0
    assert (final.committed_rel[probe] == rel[probe]).all(), (
        final.committed_rel[probe][:4], rel[probe][:4]
    )
    reads += probe.size
    return {
        "groups": n_groups,
        "peer_slots": 5,
        "rounds": rounds,
        "rounds_per_dispatch": k,
        "recycled_groups": state.get("recycled", 0),
        "writes_per_sec": round(writes / elapsed, 1),
        # host-side watermark-query rate (naming aligned with rung 4:
        # reads_per_sec is reserved for the ReadIndex confirm plane)
        "probe_reads_per_sec": round(reads / elapsed, 1),
    }


def _run_idle_axis(active: int = 1024, idle: int = 15_360, rounds: int = 6,
                   k: int = 8, cancel=None) -> dict:
    """Idle-groups-are-free axis (VERDICT r5 item 6; reference claim
    ``quiesce.go:84-86`` / README "thousands of idle Raft groups").

    Two engines of the SAME provisioned capacity (``active + idle``
    rows) run the identical fused write loop over the ``active`` set
    with device ticks firing every scanned round; variant A additionally
    registers ``idle`` live, device-clocked follower groups (clocks
    advance on every tick round; election timeouts large enough that no
    flag fires).  The measured delta is the steady-state cost of idle
    OCCUPANCY: per-tick host work is zero by construction (one fused
    tick kernel covers every row), staging cost keys off ACTIVE traffic,
    and the tensor cost keys off provisioned capacity — a deploy-time
    choice both variants share, exactly like the reference provisioning
    its worker pools.  The variants run INTERLEAVED windows and compare
    best-of (measured here: single A/B pairs on this box swing ±30%
    either direction from scheduler weather alone — best-of-interleaved
    is the same discipline PERF.md applies to the e2e A/Bs).  Asserts
    the delta < 10% and records it in the artifact."""
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine

    total = active + idle
    peers = [1, 2, 3]
    rows = np.arange(active, dtype=np.int32)
    rows2 = np.tile(rows, 2)
    slots = np.concatenate(
        [np.zeros(active, np.int32), np.ones(active, np.int32)]
    )

    def build(register_idle: bool):
        eng = BatchedQuorumEngine(
            total, 3, event_cap=4 * total, device_ticks=True
        )
        for cid in range(1, active + 1):
            eng.add_group(cid, node_ids=peers, self_id=1)
            eng.set_leader(cid, term=1, term_start=1, last_index=1)
        if register_idle:
            for cid in range(active + 1, total + 1):
                # device-clocked idle followers: election clocks advance
                # every tick round; the (huge) timeout never fires inside
                # the bench window, mirroring a quiesced group whose
                # clock ownership moved off the host
                eng.add_group(
                    cid, node_ids=peers, self_id=1,
                    election_timeout=1 << 20,
                )
        eng._upload_dirty()
        return eng

    engs = {"idle": build(True), "alone": build(False)}
    bases = {"idle": 1, "alone": 1}

    def window(name: str) -> float:
        eng = engs[name]
        base = bases[name]
        t0 = time.perf_counter()
        for _ in range(rounds):
            _check_cancel(cancel)
            rels = (
                base + 1 + np.arange(k, dtype=np.int32)[:, None]
                + np.zeros((1, rows2.size), np.int32)
            )
            eng.ack_block_rounds(rows2, slots, rels)
            eng.step_rounds(do_tick=True, pipelined=True)
            base += k
        eng.harvest()
        elapsed = time.perf_counter() - t0
        view = eng.committed_view()
        assert view[0] == base, (view[:4], base)
        bases[name] = base
        return active * rounds * k / elapsed

    for name in ("idle", "alone"):  # warmup: compile + first dispatch
        window(name)
    wps_idle = wps_alone = 0.0
    for pair in range(6):  # interleaved pairs, best-of
        wps_idle = max(wps_idle, window("idle"))
        wps_alone = max(wps_alone, window("alone"))
        if pair >= 2 and (wps_alone - wps_idle) / wps_alone < 0.05:
            break  # verdict already clear; spare the box
    delta_pct = round((wps_alone - wps_idle) / wps_alone * 100.0, 2)
    # the assert IS the axis: idle occupancy must cost < 10%
    assert delta_pct < 10.0, (
        f"idle groups not free: {delta_pct}% "
        f"({wps_idle:.0f} vs {wps_alone:.0f} w/s)"
    )
    return {
        "active_groups": active,
        "idle_groups": idle,
        "rounds": rounds,
        "rounds_per_dispatch": k,
        "writes_per_sec_with_idle": round(wps_idle, 1),
        "writes_per_sec_alone": round(wps_alone, 1),
        "idle_delta_pct": delta_pct,
        "idle_free_ok": True,
    }


def _run_obs_axis(active: int = 16_384, rounds: int = 6, k: int = 16,
                  cancel=None) -> dict:
    """Obs-overhead axis (ISSUE 5 satellite): the rung-5-shaped host loop
    with the flight recorder + metric instruments ON vs OFF.

    Two engines of identical capacity run the same fused K-round write
    loop; variant "obs" carries a FlightRecorder (stall watchdog off —
    this axis measures steady state, not stalls) and a private
    MetricsRegistry.  Interleaved windows, best-of (the same scheduler-
    weather discipline as the idle axis).  The assert IS the axis:
    obs-on throughput must stay within 5% of obs-off — the enable-latch
    contract that keeps the obs-off host path bit-identical has a twin
    obligation that obs-ON stays cheap enough to leave on in production.
    The recorder's JSON dump ships in the artifact so the perf ledger
    derives its dispatch-latency / multidev-wait columns from the record
    itself (tools/perf_ledger.py)."""
    from dragonboat_tpu.events import MetricsRegistry
    from dragonboat_tpu.obs import FlightRecorder
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine

    peers = [1, 2, 3]
    rows = np.arange(active, dtype=np.int32)
    rows2 = np.tile(rows, 2)
    slots = np.concatenate(
        [np.zeros(active, np.int32), np.ones(active, np.int32)]
    )

    def build():
        eng = BatchedQuorumEngine(
            active, 3, event_cap=4 * active, device_ticks=False
        )
        for cid in range(1, active + 1):
            eng.add_group(cid, node_ids=peers, self_id=1)
            eng.set_leader(cid, term=1, term_start=1, last_index=1)
        eng._upload_dirty()
        return eng

    engs = {"off": build(), "obs": build()}
    rec = FlightRecorder(capacity=64, stall_ms=0)
    reg = MetricsRegistry()
    engs["obs"].enable_obs(recorder=rec, registry=reg)
    bases = {"off": 1, "obs": 1}

    def window(name: str) -> float:
        eng = engs[name]
        base = bases[name]
        t0 = time.perf_counter()
        for _ in range(rounds):
            _check_cancel(cancel)
            rels = (
                base + 1 + np.arange(k, dtype=np.int32)[:, None]
                + np.zeros((1, rows2.size), np.int32)
            )
            eng.ack_block_rounds(rows2, slots, rels)
            eng.step_rounds(do_tick=False, pipelined=True)
            base += k
        eng.harvest()
        elapsed = time.perf_counter() - t0
        view = eng.committed_view()
        assert view[0] == base, (view[:4], base)
        bases[name] = base
        return active * rounds * k / elapsed

    for name in ("off", "obs"):  # warmup: compile + first dispatch
        window(name)
    wps_off = wps_obs = 0.0
    for pair in range(6):  # interleaved pairs, best-of
        wps_obs = max(wps_obs, window("obs"))
        wps_off = max(wps_off, window("off"))
        if pair >= 2 and (wps_off - wps_obs) / wps_off < 0.025:
            break  # verdict already clear; spare the box
    delta_pct = round((wps_off - wps_obs) / wps_off * 100.0, 2)
    assert delta_pct < 5.0, (
        f"obs overhead too high: {delta_pct}% "
        f"({wps_obs:.0f} vs {wps_off:.0f} w/s)"
    )
    return {
        "active_groups": active,
        "rounds": rounds,
        "rounds_per_dispatch": k,
        "writes_per_sec_obs_off": round(wps_off, 1),
        "writes_per_sec_obs_on": round(wps_obs, 1),
        "obs_overhead_pct": delta_pct,
        "obs_overhead_ok": True,
        "device_metric_families": len([
            f for f in reg.families() if f.startswith("dragonboat_device_")
        ]),
        # the recorder dump of record: the perf ledger sources its
        # dispatch-latency and multidev-wait columns from these spans
        "recorder": rec.to_json(limit=64),
    }


class _LiveNode:
    """Node shim for the live-coordinator axis: commit effects re-applied
    under raftMu with the scalar guards intact — the offload path the
    real NodeHost runs, minus transport."""

    __slots__ = ("cluster_id", "raft_mu", "peer", "commits", "obs_registry")

    def __init__(self, cid, raft):
        import threading

        self.cluster_id = cid
        self.raft_mu = threading.RLock()

        class _P:
            pass

        self.peer = _P()
        self.peer.raft = raft
        self.commits = 0
        self.obs_registry = None

    def offload_commit(self, q):
        r = self.peer.raft
        with self.raft_mu:
            if r.is_leader() and r.log.try_commit(q, r.term):
                self.commits += 1

    def offload_election(self, won, term):
        pass

    def offload_tick_elect(self):
        pass

    def offload_tick_heartbeat(self):
        pass

    def offload_tick_demote(self):
        pass


def _run_live_coord_axis(groups: int = 512, iters: int = 20) -> dict:
    """Live-coordinator adaptive-K axis (ISSUE 7 tentpole).

    The SAME live round — one append + two follower acks per group, a
    K-tick backlog, one coordinator round through the scalar-guarded
    offload path — driven through (a) a WARMED coordinator, whose round
    fuses the backlog into one multi-round dispatch, and (b) an UNWARMED
    one, whose round replays the backlog per-step (the pre-ISSUE-7
    behavior).  K sweeps the adaptive range; K=1 is the quiet-round
    case, where both modes run the identical single-round program.

    Also captured, because the perf ledger's live columns are
    ledger-backed, not prose: warm-enable wall seconds (cold and
    cache-hot after ``jax.clear_caches()`` — the in-process twin of a
    restart), persistent-cache hit/miss counts, the fused dispatch
    count, and the flight-recorder dump proving fused k_rounds>1
    dispatches on the live path with zero stalled spans."""
    from dragonboat_tpu.config import Config
    from dragonboat_tpu.obs import FlightRecorder
    from dragonboat_tpu.raft import InMemLogDB, Raft
    from dragonboat_tpu.raft.remote import Remote
    from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator
    from dragonboat_tpu.wire import Entry

    # the coordinators enable the persistent cache at its fixed place
    # (JAX_COMPILATION_CACHE_DIR, else the in-checkout default), so the
    # restart child below finds what this process compiled

    def mk_coord(warm: bool):
        coord = TpuQuorumCoordinator(
            capacity=groups, n_peers=4, drive_ticks=True, interval_s=60.0,
        )
        # deterministic drive: rounds run through flush() only (the
        # round thread would consume the staged tick backlog mid-stage)
        coord._stopped.set()
        coord._pending.set()
        coord._thread.join(timeout=10)
        if warm:
            coord.eng.warmup_fused(background=False)
        nodes = {}
        for g in range(groups):
            cid = 1 + g
            r = Raft(
                Config(node_id=1, cluster_id=cid, election_rtt=10,
                       heartbeat_rtt=1),
                InMemLogDB(), seed=g,
            )
            for p in (1, 2, 3):
                if p not in r.remotes:
                    r.remotes[p] = Remote(next=1)
            r.reset_match_value_array()
            r.has_not_applied_config_change = lambda: False
            r.become_candidate()
            r.become_leader()
            n = _LiveNode(cid, r)
            r.offload = coord
            nodes[cid] = n
            coord._nodes[cid] = n
            with coord._mu:
                coord._sync_row_locked(n)
        coord.flush()
        return coord, nodes

    t0 = time.perf_counter()
    warm_coord, warm_nodes = mk_coord(warm=True)
    warm_enable_s = round(warm_coord.warmup_stats["seconds"], 3)
    cold_stats = dict(warm_coord.warmup_stats)
    rec = FlightRecorder(capacity=256, stall_ms=1000.0)
    warm_coord.enable_obs(recorder=rec)
    single_coord, single_nodes = mk_coord(warm=False)
    setup_s = round(time.perf_counter() - t0, 2)

    def window(coord, nodes, k) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            for cid, n in nodes.items():
                r = n.peer.raft
                with n.raft_mu:
                    r.append_entries([Entry(cmd=b"w")])
                    idx = r.log.last_index()
                coord.ack(cid, 2, idx)
                coord.ack(cid, 3, idx)
            for _ in range(k):
                coord.request_tick()
            coord.flush()
        return groups * iters / (time.perf_counter() - t0)

    k_axis = {}
    for k in (1, 4, 8, 16):
        # first window per mode warms any residual first-use ops, then
        # interleaved best-of (the obs axis's scheduler-weather rule)
        window(warm_coord, warm_nodes, k)
        window(single_coord, single_nodes, k)
        wps_fused = wps_single = 0.0
        for _ in range(3):
            wps_fused = max(wps_fused, window(warm_coord, warm_nodes, k))
            wps_single = max(
                wps_single, window(single_coord, single_nodes, k)
            )
        k_axis[str(k)] = {
            "writes_per_sec_fused": round(wps_fused, 1),
            "writes_per_sec_single": round(wps_single, 1),
            "speedup": round(wps_fused / wps_single, 3),
        }

    spans = rec.spans()
    fused_spans = [s for s in spans if s["kind"] == "fused"]
    stalled = [
        s for s in spans
        if s.get("stalled") and s["kind"] in ("fused", "dispatch")
    ]
    warm_coord.stop()
    single_coord.stop()
    # cache-hot second enable: a REAL restart — a fresh process pointed
    # at the same cache directory warms the identical engine shape and
    # must deserialize every program from disk.  (An in-process
    # jax.clear_caches() twin segfaults jaxlib at this scale — double
    # free inside clear_all_caches with live donated executables.)
    import subprocess

    hot = {"hits": None, "misses": None, "enable_seconds": None}
    code = (
        "from dragonboat_tpu import hostplatform; hostplatform.force_cpu()\n"
        "import json, os, sys, time\n"
        "from dragonboat_tpu.ops.engine import (\n"
        "    BatchedQuorumEngine, enable_persistent_compilation_cache)\n"
        "enable_persistent_compilation_cache()\n"
        f"eng = BatchedQuorumEngine({groups}, 4, "
        f"event_cap={max(4 * groups, 4096)}, device_ticks=True)\n"
        "t0 = time.perf_counter()\n"
        "st = eng.warmup_fused(background=False)\n"
        "print(json.dumps({'enable_seconds': "
        "round(time.perf_counter() - t0, 3), 'hits': st['cache_hits'], "
        "'misses': st['cache_misses']}))\n"
        "sys.stdout.flush(); os._exit(0)\n"
    )
    try:
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300.0, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        if r.returncode == 0 and r.stdout.strip():
            hot = json.loads(r.stdout.strip().splitlines()[-1])
        else:
            hot["error"] = f"rc={r.returncode}"
    except Exception as e:
        hot["error"] = repr(e)[:200]
    return {
        "groups": groups,
        "iters": iters,
        "setup_s": setup_s,
        "k_axis": k_axis,
        "live_writes_per_sec": max(
            v["writes_per_sec_fused"] for v in k_axis.values()
        ),
        "live_writes_per_sec_single": max(
            v["writes_per_sec_single"] for v in k_axis.values()
        ),
        "fused_dispatches": warm_coord.fused_dispatches,
        "warm_enable_seconds": warm_enable_s,
        "warm_programs": cold_stats["programs"],
        "cache_cold": {
            "hits": cold_stats["cache_hits"],
            "misses": cold_stats["cache_misses"],
        },
        "cache_hot": hot,
        "stalled_spans": len(stalled),
        "fused_span_k_rounds": sorted(
            {int(s.get("k_rounds", 0)) for s in fused_spans}
        ),
        "recorder": rec.to_json(limit=96),
    }


def _run_mesh_axis(groups: int = 512, rounds: int = 4, k: int = 8,
                   cancel=None) -> dict:
    """Mesh-dispatch shard-count axis (ISSUE 16): the SAME fused K-round
    write loop at shards ∈ {1, 2, 4, 8} — one single-device engine at
    shards=1, the ``MeshQuorumEngine`` facade above that — reporting
    aggregate and implied per-shard writes/s per mesh size.

    Graduated from the driver's ``dryrun_multichip`` hook: the dry-run
    proved bit-identity on the 8-device virtual cpu mesh; this rung puts
    a THROUGHPUT number on the same topology, plus the live-migration
    wall time and the peak dispatch-stream concurrency read off the
    shared flight recorder's shard-tagged spans (>1 is the
    no-global-mutex evidence).

    On the cpu backend the 8 virtual devices share the host's real
    cores, so shard streams contend for the compute they are supposed to
    parallelize — the artifact carries an explicit ``noise`` label and
    the ≥0.6x-per-doubling scaling gate applies only off-cpu (where
    each shard owns real silicon).  The ledger prints the label next to
    every cpu row."""
    from dragonboat_tpu import hostplatform

    n_devices = 8
    hostplatform.set_host_device_count(n_devices)
    hostplatform.force_cpu()

    import jax

    from dragonboat_tpu.events import MetricsRegistry
    from dragonboat_tpu.obs import FlightRecorder
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine
    from dragonboat_tpu.ops.mesh import MeshQuorumEngine

    devices = jax.local_devices(backend="cpu")
    if len(devices) < n_devices:
        hostplatform.clear_backends()
        devices = jax.local_devices(backend="cpu")
    devices = devices[:n_devices]
    on_cpu = devices[0].platform == "cpu"
    peers = [1, 2, 3]

    def build(n_shards: int):
        # one spare row per shard: migration needs a free row on the
        # target, and the exactly-sized mesh would refuse every move
        cap = groups + n_shards
        if n_shards == 1:
            eng = BatchedQuorumEngine(cap, 3, event_cap=4 * groups)
        else:
            eng = MeshQuorumEngine(
                cap, 3, event_cap=4 * groups,
                devices=devices[:n_shards],
            )
        for cid in range(1, groups + 1):
            eng.add_group(cid, node_ids=peers, self_id=1)
            eng.set_leader(cid, term=1, term_start=1, last_index=1)
        eng._upload_dirty()
        return eng

    def window(eng, base: int) -> float:
        """One measured fused window: K staged rounds on every shard,
        one mesh fan-out, blocking harvest.  Returns elapsed seconds."""
        shards = getattr(eng, "shards", None) or [eng]
        t0 = time.perf_counter()
        for _ in range(rounds):
            _check_cancel(cancel)
            for s in shards:
                n = len(s.groups)
                rows = np.array(
                    sorted(gi.row for gi in s.groups.values()), np.int32
                )
                rows2 = np.tile(rows, 2)
                slots = np.concatenate(
                    [np.zeros(n, np.int32), np.ones(n, np.int32)]
                )
                rels = (
                    base + 1 + np.arange(k, dtype=np.int32)[:, None]
                    + np.zeros((1, rows2.size), np.int32)
                )
                s.ack_block_rounds(rows2, slots, rels)
            eng.step_rounds(do_tick=True, pipelined=True)
            base += k
        eng.harvest()
        elapsed = time.perf_counter() - t0
        # the highest cid never migrates in this rung: a stable probe of
        # the commit watermark on both engine shapes
        got = eng.committed_index(groups)
        assert got == base, (got, base)
        return elapsed

    axis = {}
    mesh8 = None
    for n_shards in (1, 2, 4, 8):
        eng = build(n_shards)
        window(eng, 1)  # warmup: compile + first dispatch
        base = 1 + rounds * k
        best = min(window(eng, base + p * rounds * k) for p in range(3))
        axis[str(n_shards)] = {
            "writes_per_sec": round(groups * rounds * k / best, 1),
        }
        if n_shards == 8:
            mesh8 = eng  # keep the widest mesh for migration/obs probes
        else:
            if hasattr(eng, "stop"):
                eng.stop()

    # live migration + concurrency evidence on the widest mesh
    reg = MetricsRegistry()
    rec = FlightRecorder(stall_ms=0)
    mesh8.enable_obs(rec, registry=reg)
    mig_walls = []
    base = 1 + 4 * rounds * k
    for m in range(4):
        cid = 1 + m
        src = mesh8.shard_index(cid)
        t0 = time.perf_counter()
        ok = mesh8.migrate_group(cid, (src + 1) % mesh8.n_shards)
        if ok:
            mig_walls.append((time.perf_counter() - t0) * 1e3)
    window(mesh8, base)  # instrumented window: shard-tagged spans
    spans = []
    for s in rec.spans():
        if s.get("shard") is None or "egress_ms" not in s:
            continue
        # the span's own interval (perf_counter)
        spans.append((s["t0"], s["t1"], s["shard"]))
    peak = 0
    for start, end, shard in spans:
        live = {
            sh for (a, b, sh) in spans if a < end and start < b
        }
        peak = max(peak, len(live))
    mesh8.stop()

    ws1 = axis["1"]["writes_per_sec"]
    out = {
        "groups": groups,
        "rounds": rounds,
        "rounds_per_dispatch": k,
        "shards_axis": axis,
        "migration": {
            "count": len(mig_walls),
            "wall_ms_p50": round(
                sorted(mig_walls)[len(mig_walls) // 2], 3
            ) if mig_walls else None,
        },
        "concurrency_peak": peak,
        "scaling_vs_1shard": {
            n: round(v["writes_per_sec"] / ws1, 3) for n, v in axis.items()
        },
    }
    if on_cpu:
        out["noise"] = (
            "cpu: 8 virtual devices share the host cores — shard "
            "streams contend, scaling gate waived"
        )
    else:
        # off-cpu every shard owns real silicon: gate the per-doubling
        # scaling factor (ISSUE 16 acceptance: >= 0.6x ideal)
        prev = None
        for n in ("1", "2", "4", "8"):
            ws = axis[n]["writes_per_sec"]
            if prev is not None:
                assert ws >= 0.6 * 2 * prev, (
                    f"mesh scaling below 0.6x ideal at shards={n}: "
                    f"{ws:.0f} vs {prev:.0f} w/s"
                )
            prev = ws
    return out


def dryrun_multichip(n_devices: int) -> None:
    """Device-ticks differential under an ``n_devices`` group-sharded mesh.

    Group-axis sharding is this framework's whole parallelism story (the
    analog of the reference's clusterID%workers partitioning — SURVEY.md
    §2.7): state tensors split on the group axis, event batches replicated,
    zero collectives in steady state.

    Not a single hand-built step: 64 groups run a full seeded scenario —
    elections fired by DEVICE tick processing (elect_due asserted against
    the exact tick each scalar oracle campaigns), seeded vote outcomes
    including lost elections that re-campaign, 100+ commit rounds with the
    FULL commit vector asserted bit-identical to the scalar oracles every
    round, and check-quorum: the device raises the window flag for every
    leader row while the scalar oracles (the demotion authority)
    verifiably step down.

    Graduated here from ``__graft_entry__.py`` (ISSUE 16) so the
    correctness dry-run and the ``_run_mesh_axis`` throughput rung live
    side by side; the driver's hook delegates to this function.
    """
    # Force the CPU platform BEFORE any jax backend is touched: the
    # virtual n-device CPU mesh never needs (or may take) the TPU.
    import random

    from dragonboat_tpu import hostplatform

    hostplatform.set_host_device_count(n_devices)
    hostplatform.force_cpu()

    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P

    from dragonboat_tpu.ops.engine import BatchedQuorumEngine
    from dragonboat_tpu.ops.sharding import GROUP_AXIS, make_mesh
    from dragonboat_tpu.raft import InMemLogDB, Raft
    from dragonboat_tpu.config import Config
    from dragonboat_tpu.wire import Entry, Message, MessageType as MT

    devices = jax.local_devices(backend="cpu")
    if len(devices) < n_devices:
        # jax was already imported with a smaller CPU device count: reset the
        # backend cache so the new XLA_FLAGS take effect
        hostplatform.clear_backends()
        devices = jax.local_devices(backend="cpu")
    devices = devices[:n_devices]
    assert len(devices) == n_devices, (
        f"need {n_devices} devices, have {len(devices)}"
    )
    mesh = make_mesh(np.array(devices))

    n_groups = 64
    assert n_groups % n_devices == 0
    rng = random.Random(42)
    # one prefix-spec sharding for every state field: group axis (dim 0)
    # split over the mesh, peer columns local to their group's chip
    eng = BatchedQuorumEngine(
        n_groups, n_peers=5, event_cap=4 * n_groups,
        sharding=NamedSharding(mesh, P(GROUP_AXIS)),
    )

    # scalar oracles: node 1's replica of each group, varied membership
    oracles = {}
    for g in range(n_groups):
        cid = 1 + g
        peers = [1, 2, 3] if cid % 2 else [1, 2, 3, 4, 5]
        cfg = Config(
            cluster_id=cid, node_id=1, election_rtt=10, heartbeat_rtt=1,
            check_quorum=True,
        )
        r = Raft(cfg, InMemLogDB(), seed=cid)
        for p in peers:
            r.add_node(p)
        oracles[cid] = (r, peers)
        eng.add_group(
            cid, node_ids=peers, self_id=1, election_timeout=10,
            rand_timeout=r.randomized_election_timeout,
            check_quorum=True,
        )
    eng._upload_dirty()

    # ---- phase A: elections fire from DEVICE ticks, outcomes seeded ----
    last_term = {cid: 0 for cid in oracles}
    leaders: set = set()
    ticks = 0
    while len(leaders) < n_groups and ticks < 400:
        ticks += 1
        campaigned = []
        for cid, (r, peers) in oracles.items():
            if cid in leaders:
                continue
            r.tick()
            if r.is_candidate() and r.term != last_term[cid]:
                last_term[cid] = r.term
                campaigned.append(cid)
        out = eng.step(do_tick=True)
        fired = set(out.elect)
        # the device must fire elect_due on EXACTLY the tick the scalar
        # oracle campaigns (first campaign; re-campaign backoff drifts by
        # design — the row clock resets at set_candidate time)
        for cid in campaigned:
            if last_term[cid] == 1:
                assert cid in fired, (ticks, cid, sorted(fired)[:8])
        for cid in campaigned:
            r, peers = oracles[cid]
            eng.set_candidate(cid, term=r.term)
            eng.vote(cid, 1, granted=True)  # campaign self-vote
            grant = rng.random() < 0.8  # ~20% of campaigns fail first
            for p in peers:
                if p == 1:
                    continue
                r.handle(Message(
                    from_=p, to=1, term=r.term,
                    type=MT.REQUEST_VOTE_RESP, reject=not grant,
                ))
                eng.vote(cid, p, granted=grant)
        if campaigned:
            out = eng.step(do_tick=False)
            for cid in campaigned:
                r, peers = oracles[cid]
                if r.is_leader():
                    assert cid in out.won, (cid, out.won[:8])
                    eng.set_leader(
                        cid, term=r.term,
                        term_start=r.log.last_index(),
                        last_index=r.log.last_index(),
                    )
                    leaders.add(cid)
                else:
                    assert cid in out.lost, (cid, out.lost[:8])
                    # lost: oracle stays candidate and re-campaigns on its
                    # next randomized timeout; resync the row's clock
                    eng.set_candidate(cid, term=r.term)
                    eng.set_randomized_timeout(
                        cid, r.randomized_election_timeout
                    )
    assert len(leaders) == n_groups, (
        f"only {len(leaders)}/{n_groups} elected in {ticks} ticks"
    )

    # ---- phase B: 100+ commit rounds, full-vector bit-identity ----
    rounds = 120
    for rnd in range(rounds):
        for cid, (r, peers) in oracles.items():
            if rng.random() < 0.7:  # sparse activity, like live traffic
                r.handle(Message(
                    from_=1, to=1, type=MT.PROPOSE, entries=[Entry(cmd=b"x")]
                ))
                idx = r.log.last_index()
                eng.ack(cid, 1, idx)  # self append
                followers = [p for p in peers if p != 1]
                rng.shuffle(followers)
                k = rng.randrange(0, len(followers) + 1)
                for p in followers[:k]:
                    r.handle(Message(
                        from_=p, to=1, term=r.term,
                        type=MT.REPLICATE_RESP, log_index=idx,
                    ))
                    eng.ack(cid, p, idx)
        eng.step(do_tick=False)
        # FULL commit vector, every round, bit-identical
        for cid, (r, _) in oracles.items():
            got, want = eng.committed_index(cid), r.log.committed
            assert got == want, (rnd, cid, got, want)

    # ---- phase C: check-quorum demotion, device window + scalar authority --
    # Leaders see no peer contact from here on.  The device fires the
    # check-quorum window flag every election_timeout ticks BY DESIGN
    # (kernels.py: the scalar handler is the authority and must consume
    # its activity bits each window), so the real assertion is two-sided:
    # the device raises the window for every leader row AND the scalar
    # oracles, ticked in lockstep with zero peer contact, actually step
    # down within two windows.
    demoted: set = set()
    for _ in range(2 * 10 + 5):
        for cid, (r, _) in oracles.items():
            r.tick()
        out = eng.step(do_tick=True)
        demoted.update(out.demote)
    assert len(demoted) == n_groups, (
        f"device raised check-quorum window for only {len(demoted)}/{n_groups}"
    )
    still_leading = [cid for cid, (r, _) in oracles.items() if r.is_leader()]
    assert not still_leading, (
        f"{len(still_leading)} stale leaders survived check-quorum: "
        f"{still_leading[:8]}"
    )

    total_committed = sum(r.log.committed for r, _ in oracles.values())

    # ---- phase D: the FULL stack on the sharded engine ----
    # 3 in-process NodeHosts whose TpuQuorumCoordinators are built with
    # ExpertConfig.engine_mesh_devices=n_devices: real registration/
    # staging/rounds through the coordinator, device-tick elections,
    # propose end to end — not the bare engine.  (Shared harness with
    # tests/test_sharding.py so the two cannot drift; the harness caps
    # dispatch streams at the host's core count.)
    from dragonboat_tpu.testing import run_sharded_stack_check

    n_stack_groups = 2 * n_devices
    stack_writes = run_sharded_stack_check(
        n_devices, groups=n_stack_groups, writes_per_group=5
    )

    print(
        f"dryrun_multichip ok: {n_devices} devices, {n_groups} groups, "
        f"{ticks} election ticks, {rounds} commit rounds bit-identical, "
        f"{total_committed} entries committed, "
        f"check-quorum demoted {len(demoted)}/{n_groups}; full stack: "
        f"{n_stack_groups} groups on 3 NodeHosts over the sharded "
        f"coordinator, {stack_writes} writes committed"
    )


def main() -> None:
    # ---- e2e NodeHost numbers first (ladder rung 3).  One process per
    # chip: the parent has not initialized jax yet, so the e2e rank-0 child
    # can own the device; the parent takes it only after they have exited.
    detail = {}
    if os.environ.get("BENCH_SKIP_E2E") != "1":
        # flagship: the winning configuration (auto's choice) — scalar
        # engine + fast lane + native C-ABI SM (apply path GIL-free)
        _note("running e2e (native SM, scalar engine, fast lane)...")
        detail["e2e"] = _run_e2e("scalar", {"E2E_SM": "native"})
        _note(f"e2e: {json.dumps(detail['e2e'])[:300]}")
        # round-3-comparable: same but the Python dict SM
        _note("running e2e (python SM, scalar engine, fast lane)...")
        detail["e2e_python_sm"] = _run_e2e(
            "scalar", timeout_key="BENCH_E2E_SCALAR_TIMEOUT"
        )
        _note(f"e2e_python_sm: {json.dumps(detail['e2e_python_sm'])[:300]}")
        # engine comparison under IDENTICAL placement: rank 0 runs the
        # device engine on the chip (or the run errors)
        _note("running e2e (tpu engine, same placement)...")
        detail["e2e_tpu"] = _run_e2e(
            "tpu", timeout_key="BENCH_E2E_SCALAR_TIMEOUT"
        )
        _note(f"e2e_tpu: {json.dumps(detail['e2e_tpu'])[:300]}")
        # scale rung (VERDICT r4 next #1): engine A/B at IDENTICAL
        # placement, 2,048 groups, leaders SPREAD (the production
        # shape).  Round-5 full dataset on a 1-vCPU box: tpu ~8.8k
        # ± 1.9k w/s over six runs vs scalar ~9.9k ± 1.0k over four —
        # parity within noise (r4 measured a 4x deficit), with the tpu
        # spread wide because every dispatch competes with the box's
        # single host core (PERF.md round-5 §3).  The rung keeps the
        # comparison honest run over run; single pairs on a small box
        # are weather.  2,048 keeps setup inside the section budget;
        # override with BENCH_SCALE_GROUPS.
        if os.environ.get("BENCH_SKIP_SCALE") != "1":
            scale_groups = os.environ.get("BENCH_SCALE_GROUPS", "2048")
            scale_env = {
                "E2E_SM": "native", "E2E_GROUPS": scale_groups,
                "E2E_DURATION": "20", "E2E_LEADER_TIMEOUT": "360",
            }
            for eng_name in ("tpu", "scalar"):
                key = f"e2e_scale_{eng_name}"
                _note(
                    f"running e2e scale rung ({scale_groups} groups, "
                    f"spread, {eng_name})..."
                )
                detail[key] = _run_e2e(
                    eng_name, dict(scale_env),
                    timeout_key="BENCH_E2E_SCALE_TIMEOUT",
                )
                _note(f"{key}: {json.dumps(_slim_e2e(detail[key]))[:300]}")
    if "e2e" in detail:
        e2e_ok = bool(
            detail["e2e"].get("writes_per_sec")
            and "error" not in detail["e2e"]
            and not detail["e2e"].get("rank_errors")
        )
    else:
        e2e_ok = None  # deliberately skipped ≠ failed

    # ---- kernel benches: the parent now takes the device
    platform = _resolve_platform()
    on_tpu = platform != "cpu"
    detail["platform"] = platform

    n_groups = int(os.environ.get("BENCH_GROUPS", "131072" if on_tpu else "16384"))
    # pipelined R: the deeper scan amortizes the dispatch round trip
    rounds = int(os.environ.get("BENCH_ROUNDS", "256" if on_tpu else "128"))
    dispatches = int(os.environ.get("BENCH_DISPATCHES", "5"))
    lat_rounds = int(os.environ.get("BENCH_LAT_ROUNDS", "1"))
    lat_groups = int(os.environ.get("BENCH_LAT_GROUPS", "1024"))
    lat_dispatches = int(os.environ.get("BENCH_LAT_DISPATCHES", "50"))

    # throughput-maximal pipelined mode
    writes_per_sec, times = _run_mode(n_groups, rounds, dispatches)
    detail.update(
        groups=n_groups,
        rounds_per_dispatch=rounds,
        dispatches=dispatches,
        # duplicated into the detail artifact so the PERF.md ledger
        # generator (tools/perf_ledger.py) has every figure in one file
        headline_writes_per_sec=round(writes_per_sec, 1),
        dispatch_p99_ms=round(
            float(np.percentile(np.array(times) * 1e3, 99)), 3
        ),
    )

    # latency-bounded mode: continuous small-R dispatches at rung-3 scale
    try:
        lat_wps, lat_times = _run_mode(
            lat_groups, lat_rounds, lat_dispatches, warmup=5
        )
        lat_ms = np.array(lat_times) * 1e3
        detail["latency_mode"] = {
            "groups": lat_groups,
            "rounds_per_dispatch": lat_rounds,
            "writes_per_sec": round(lat_wps, 1),
            "dispatch_p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "dispatch_p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        }
    except Exception as e:
        detail["latency_mode"] = {"error": repr(e)}

    # host-loop mode: the engine's REAL ingest path — events staged
    # host-side through eng.ack()/BatchedQuorumEngine.step() exactly as
    # the live tpuquorum coordinator drives it (persistent device state,
    # per-round event deltas).  Honest midpoint between the kernel-only
    # pipelined number (events derived on device) and the full e2e stack.
    try:
        detail["host_loop"] = _run_host_loop(
            int(os.environ.get("BENCH_HOST_GROUPS", "65536" if on_tpu else "16384")),
            int(os.environ.get("BENCH_HOST_ROUNDS", "8")),
            int(os.environ.get("BENCH_HOST_K", "16")),
        )
        detail["host_loop"].setdefault("platform", platform)
    except Exception as e:
        detail["host_loop"] = {"error": repr(e)}

    # rungs 4 and 5 of the config ladder (BASELINE.md): 64k / 100k groups
    # through the coordinator ingest path.  They run ON THE DEVICE the
    # parent holds (a failed device rung stays an error entry); only the
    # explicit BENCH_PLATFORM=cpu rehearsal runs the cpu-subprocess shape.
    def _rung_on_device(fn, env_groups, dflt_groups, env_rounds, dflt_rounds,
                        env_k, dflt_k, timeout=420.0):
        """Run a rung inline on the parent's device, bounded by a watchdog
        thread: a wedged dispatch must degrade to an error entry (like
        the cpu-subprocess path's timeout), not hang the bench.  The
        worker gets a CANCELLATION flag checked before every dispatch
        (_check_cancel): when the watchdog gives up, the abandoned daemon
        thread stops feeding the device instead of dispatching on in the
        background under the later sections."""
        import threading as _th

        box = {}
        cancel = _th.Event()

        def _work():
            try:
                g = int(os.environ.get(env_groups, str(dflt_groups)))
                rds = int(os.environ.get(env_rounds, str(dflt_rounds)))
                # same K override the cpu-subprocess spec honors — the
                # device and cpu capture must stay A/B-comparable
                kv = int(os.environ.get(env_k, str(dflt_k)))
                out = fn(g, rds, kv, cancel=cancel)
                out["platform"] = platform
                box["out"] = out
            except Exception as e:
                box["out"] = {"error": repr(e)[:300]}

        t = _th.Thread(target=_work, daemon=True)
        t.start()
        t.join(timeout)
        if t.is_alive():
            cancel.set()  # the worker aborts at its next dispatch boundary
            return {"error": f"device rung timed out after {timeout}s"}
        # BaseException (SystemExit etc.) ends the thread without a result
        return box.get("out", {"error": "device rung worker died"})

    if on_tpu:
        detail["rung4"] = _rung_on_device(
            _run_rung4, "BENCH_RUNG4_GROUPS", 65536, "BENCH_RUNG4_ROUNDS", 8,
            "BENCH_RUNG4_K", 16,
        )
        detail["rung5"] = _rung_on_device(
            _run_rung5, "BENCH_RUNG5_GROUPS", 100000, "BENCH_RUNG5_ROUNDS", 6,
            "BENCH_RUNG5_K", 8,
        )
    else:
        detail["rung4"] = _run_cpu_section(
            "_run_rung4", ["BENCH_RUNG4_GROUPS", 65536,
                           "BENCH_RUNG4_ROUNDS", 8, "BENCH_RUNG4_K", 16],
        )
        detail["rung5"] = _run_cpu_section(
            "_run_rung5", ["BENCH_RUNG5_GROUPS", 100000,
                           "BENCH_RUNG5_ROUNDS", 6, "BENCH_RUNG5_K", 8],
        )

    # idle-groups-are-free axis (VERDICT r5 item 6): always measured on
    # the local cpu backend — the axis isolates host-side occupancy cost
    # at fixed provisioned capacity, which is backend-agnostic by
    # construction
    if os.environ.get("BENCH_SKIP_IDLE_AXIS") != "1":
        detail["idle_axis"] = _run_cpu_section(
            "_run_idle_axis",
            ["BENCH_IDLE_ACTIVE", 1024, "BENCH_IDLE_IDLE", 15360,
             "BENCH_IDLE_ROUNDS", 6, "BENCH_IDLE_K", 8],
        )

    # obs-overhead axis (ISSUE 5): flight recorder + metrics ON vs OFF on
    # the fused host loop — asserts < 5% and ships the recorder dump the
    # perf ledger's observability columns derive from.  Always on the
    # local cpu backend: the axis isolates HOST-side instrument cost,
    # which is backend-agnostic by construction.
    if os.environ.get("BENCH_SKIP_OBS_AXIS") != "1":
        detail["obs_axis"] = _run_cpu_section(
            "_run_obs_axis",
            ["BENCH_OBS_ACTIVE", 16384, "BENCH_OBS_ROUNDS", 6,
             "BENCH_OBS_K", 16],
        )

    # live-coordinator adaptive-K axis (ISSUE 7): the warmed fused round
    # vs the single-round replay through the scalar-guarded offload path,
    # plus warm-enable seconds and compile-cache hit/miss counts — the
    # perf ledger's live columns derive from this section.  Always on the
    # local cpu backend (it measures host round cost, and the subprocess
    # keeps the compile-cache churn off the parent's jax state).
    if os.environ.get("BENCH_SKIP_LIVE_COORD_AXIS") != "1":
        detail["live_coord"] = _run_cpu_section(
            "_run_live_coord_axis",
            ["BENCH_LIVE_GROUPS", 512, "BENCH_LIVE_ITERS", 20],
            timeout=900.0,
        )

    # mesh-dispatch shard-count axis (ISSUE 16): the fused write loop at
    # shards 1/2/4/8 on the 8-virtual-device cpu mesh, plus live
    # migration wall time and the shard-tagged span concurrency peak —
    # the perf ledger's "Mesh dispatch" table derives from this section.
    # Always a subprocess: the axis needs XLA's host platform forced to
    # 8 devices BEFORE any jax init, which must not leak into the parent.
    if os.environ.get("BENCH_SKIP_MESH_AXIS") != "1":
        detail["mesh_axis"] = _run_cpu_section(
            "_run_mesh_axis",
            ["BENCH_MESH_GROUPS", 512, "BENCH_MESH_ROUNDS", 4,
             "BENCH_MESH_K", 8],
            timeout=600.0,
        )
        _note(f"mesh_axis: {json.dumps(detail['mesh_axis'])[:300]}")

    def _run_e2e_axis(flag: str, timeout_env: str, default_timeout: str):
        """Run a bench_e2e.py axis in a killable subprocess (cpu backend)
        and return its last-stdout-line JSON, or an error entry — the
        shared shape of the trace and crossdomain sections."""
        import subprocess as _sp

        try:
            r = _sp.run(
                [sys.executable, os.path.join(os.path.dirname(
                    os.path.abspath(__file__)), "bench_e2e.py"), flag],
                capture_output=True, text=True,
                timeout=float(os.environ.get(timeout_env, default_timeout)),
                env={**os.environ, "BENCH_PLATFORM": "cpu",
                     "JAX_PLATFORMS": "cpu"},
            )
            if r.returncode == 0 and r.stdout.strip():
                out = json.loads(r.stdout.strip().splitlines()[-1])
                out["platform"] = "cpu"
                return out
            return {
                "error": f"rc={r.returncode}",
                "tail": (r.stderr or r.stdout)[-500:],
            }
        except Exception as e:
            return {"error": repr(e)}

    # request-tracing axis (ISSUE 9): trace-on vs trace-off interleaved
    # best-of on one live cluster per engine (<5% asserted) plus the
    # per-stage latency attribution — the perf ledger's "Latency
    # attribution" table derives from this section.  Runs bench_e2e in a
    # killable subprocess like the other e2e sections (cpu backend; the
    # axis measures host-side stage cost, backend-agnostic).
    if os.environ.get("BENCH_SKIP_TRACE_AXIS") != "1":
        detail["trace_axis"] = _run_e2e_axis(
            "--trace-axis", "BENCH_TRACE_TIMEOUT", "900"
        )
        _note(f"trace_axis: {json.dumps(detail['trace_axis'])[:300]}")

    # cross-domain lease axis (ISSUE 10): leader-lease local reads vs the
    # ReadIndex fallback on a live 3-host group whose follower quorum sits
    # one injected far link (40ms RTT) from the leader — the perf ledger's
    # "Read plane" table derives from this section.  Always on the cpu
    # backend (it measures the scalar read path; no device involved).
    if os.environ.get("BENCH_SKIP_CROSSDOMAIN") != "1":
        # outer timeout dominates the rung's own worst case (2 variants x
        # 120s placement deadlines + load + 6-host setup/teardown)
        detail["crossdomain"] = _run_e2e_axis(
            "--crossdomain", "BENCH_XDOM_TIMEOUT", "600"
        )
        _note(f"crossdomain: {json.dumps(detail['crossdomain'])[:300]}")

    # device state machine rung (ISSUE 11): 9:1 mixed KV load, device_kv
    # on vs off on identical 3-host topology — the perf ledger's "Device
    # SM" table derives from this section.  The outer timeout dominates
    # the has_kv program warm (minutes on a cold 1-vCPU box) plus two
    # variants of placement + load.
    if os.environ.get("BENCH_SKIP_DEVSM") != "1":
        detail["devsm"] = _run_e2e_axis(
            "--devsm", "BENCH_DEVSM_TIMEOUT", "900"
        )
        _note(f"devsm: {json.dumps(detail['devsm'])[:300]}")

    # multi-process host plane axis (ISSUE 12): host_workers=0 vs N on
    # the many-session durable cluster — the perf ledger's "Host
    # workers" table derives from this section.  The assertion is
    # cpu-topology gated inside the axis (single-core boxes run the
    # parity variant and label themselves; the ≥5x target gates on
    # os.cpu_count()).
    if os.environ.get("BENCH_SKIP_HOST_WORKERS") != "1":
        detail["host_workers"] = _run_e2e_axis(
            "--host-workers", "BENCH_HOST_WORKERS_TIMEOUT", "600"
        )
        _note(
            "host_workers: "
            f"{json.dumps(detail['host_workers'])[:300]}"
        )

    # cluster health axis (ISSUE 13): health-on/off interleaved best-of
    # on one live cluster (<5% asserted) plus a leadership-churn phase
    # whose detector open/close events carry measured recovery durations
    # — the perf ledger's "Cluster health" table derives from this
    # section's ring dump.
    if os.environ.get("BENCH_SKIP_HEALTH_AXIS") != "1":
        detail["health_axis"] = _run_e2e_axis(
            "--health-axis", "BENCH_HEALTH_TIMEOUT", "600"
        )
        _note(f"health_axis: {json.dumps(detail['health_axis'])[:300]}")

    # device capacity & profiling axis (ISSUE 15): profile-on/off paired
    # windows on a live tpu-engine cluster (<5% + 2·SEM asserted), the
    # capacity model diffed against measured resident bytes (<10%
    # asserted) and the warm-set program registry with per-program XLA
    # cost/memory analysis — the perf ledger's "Device programs" and
    # "Device capacity" tables derive from this section.
    if os.environ.get("BENCH_SKIP_DEVPROF_AXIS") != "1":
        detail["devprof_axis"] = _run_e2e_axis(
            "--devprof-axis", "BENCH_DEVPROF_TIMEOUT", "900"
        )
        _note(f"devprof_axis: {json.dumps(detail['devprof_axis'])[:300]}")

    # BlackWater churn soak A/B (ISSUE 17): same-seed recovery OFF/ON
    # runs of soak.py --churn, scored by per-detector MTTR p99 with a
    # zero-linearizability-violation gate — the perf ledger's "Recovery"
    # table derives from this section.  Two full soak arms are minutes
    # of wall time, so the axis honors its own skip gate.
    if os.environ.get("BENCH_SKIP_CHURN") != "1":
        detail["churn_soak"] = _run_e2e_axis(
            "--churn-soak", "BENCH_CHURN_TIMEOUT", "3600"
        )
        _note(f"churn_soak: {json.dumps(detail['churn_soak'])[:300]}")

    # full detail (per-rank stats and all) goes to a FILE; the stdout line
    # stays small enough that a 2000-char tail capture can never truncate
    # the headline
    detail_file_ok = False
    try:
        with open(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_DETAIL.json"), "w"
        ) as f:
            json.dump(detail, f, indent=1)
        detail_file_ok = True
    except OSError as e:
        _note(f"could not write BENCH_DETAIL.json: {e!r}")
    slim = dict(detail)
    for k in ("e2e", "e2e_python_sm", "e2e_tpu"):
        if k in slim:
            slim[k] = _slim_e2e(slim[k])
    if isinstance(slim.get("obs_axis"), dict):
        # the recorder span dump stays in BENCH_DETAIL.json only — it
        # would blow the driver's 2000-char stdout tail capture
        slim["obs_axis"] = {
            k: v for k, v in slim["obs_axis"].items() if k != "recorder"
        }
    if isinstance(slim.get("live_coord"), dict):
        # scalars only on stdout; the k_axis table + recorder dump live
        # in BENCH_DETAIL.json
        slim["live_coord"] = {
            k: v for k, v in slim["live_coord"].items()
            if k in ("groups", "live_writes_per_sec",
                     "live_writes_per_sec_single", "warm_enable_seconds",
                     "fused_dispatches", "stalled_spans", "error", "tail")
        }
    if isinstance(slim.get("trace_axis"), dict):
        # verdict fields only on stdout; the per-stage attribution tables
        # and pair deltas (~KBs) live in BENCH_DETAIL.json — the adjacent
        # sections' 2000-char tail-capture discipline applies here too
        ta = slim["trace_axis"]
        slim["trace_axis"] = {
            k: v for k, v in ta.items()
            if k in ("trace_overhead_ok", "error", "tail")
        }
        for eng, e in (ta.get("engines") or {}).items():
            if isinstance(e, dict):
                slim["trace_axis"][eng] = {
                    k: v for k, v in e.items()
                    if k in ("trace_overhead_pct", "trace_overhead_sem_pct",
                             "trace_overhead_ok", "fused_dispatches")
                }
    if isinstance(slim.get("crossdomain"), dict):
        # headline fields only on stdout; full variant stats live in
        # BENCH_DETAIL.json
        slim["crossdomain"] = {
            k: v for k, v in slim["crossdomain"].items()
            if k in ("read_p99_ms_lease", "read_p99_ms_fallback",
                     "read_p99_speedup", "ops_ratio_on_off", "assert_ok",
                     "error", "tail")
        }
    if isinstance(slim.get("devsm"), dict):
        # headline fields only; per-stage attribution in BENCH_DETAIL.json
        slim["devsm"] = {
            k: v for k, v in slim["devsm"].items()
            if k in ("apply_share_pct_devsm", "apply_share_pct_host",
                     "read_p50_ms_devsm", "read_p50_ms_host", "assert_ok",
                     "error", "tail")
        }
    if isinstance(slim.get("health_axis"), dict):
        # verdict fields only on stdout; the ring dump + per-detector
        # recovery tables live in BENCH_DETAIL.json
        slim["health_axis"] = {
            k: v for k, v in slim["health_axis"].items()
            if k in ("health_overhead_pct", "health_overhead_ok",
                     "churn_events_ok", "samples_total", "error", "tail")
        }
    if isinstance(slim.get("devprof_axis"), dict):
        # verdict fields only on stdout; the program table + per-plane
        # ledger live in BENCH_DETAIL.json
        slim["devprof_axis"] = {
            k: v for k, v in slim["devprof_axis"].items()
            if k in ("devprof_overhead_pct", "devprof_overhead_ok",
                     "programs_ok", "error", "tail")
        }
        cap = (detail["devprof_axis"] or {}).get("capacity") or {}
        slim["devprof_axis"]["model_error_pct"] = cap.get("model_error_pct")
    if isinstance(slim.get("churn_soak"), dict):
        # verdict + per-detector p99 A/B only on stdout; the full arm
        # summaries (counts, actions, censored opens) live in
        # BENCH_DETAIL.json
        slim["churn_soak"] = {
            k: v for k, v in slim["churn_soak"].items()
            if k in ("churn_ok", "linearizable", "groups", "seed",
                     "mttr_p99", "error", "tail")
        }
    if isinstance(slim.get("host_workers"), dict):
        # headline fields only; the full A/B records live in
        # BENCH_DETAIL.json's host_workers.axis section
        hw = slim["host_workers"]
        slim["host_workers"] = {
            k: v for k, v in hw.items()
            if k in ("cores", "single_core", "workers", "restarts",
                     "assertion", "assert_ok", "error", "tail")
        }
        ax = (hw.get("axis") or [{}])[0]
        slim["host_workers"]["speedup"] = ax.get("speedup")
    for k in ("e2e_scale_tpu", "e2e_scale_scalar"):
        # ultra-slim: the A/B verdict fields only (full data in
        # BENCH_DETAIL.json); the driver's tail capture budget is 2000B
        if k in slim and isinstance(slim[k], dict):
            s = _slim_e2e(slim[k])
            slim[k] = {
                f: s[f]
                for f in ("writes_per_sec", "commit_latency_ms",
                          "mixed_ops_per_sec", "setup_s", "error", "tail")
                if f in s
            }
            if detail[k].get("led_groups") is not None:
                slim[k]["led"] = detail[k]["led_groups"]
    record = {
        "metric": "quorum_engine_writes_per_sec",
        "value": round(writes_per_sec, 1),
        "unit": "writes/s",
        "vs_baseline": round(writes_per_sec / BASELINE_WRITES_PER_SEC, 4),
        "platform": platform,
        # false only under the explicit BENCH_PLATFORM=cpu rehearsal:
        # none of this record's numbers is then a device metric
        "tpu_ok": on_tpu,
        # machine-readable e2e status: a consumer checking rc/parsed must
        # not read a partial failure as an unqualified pass
        "e2e_ok": e2e_ok,
        "detail": slim,
    }
    line = json.dumps(record)
    if len(line) > 1900:  # last-resort guard for the tail capture
        _note("slim detail still too large; dropping it from the line")
        record["detail"] = (
            {"see": "BENCH_DETAIL.json"}
            if detail_file_ok
            else {"error": "detail too large and BENCH_DETAIL.json unwritable"}
        )
        line = json.dumps(record)
    print(line)


if __name__ == "__main__":
    main()
