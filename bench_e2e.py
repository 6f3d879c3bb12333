"""End-to-end NodeHost benchmark: ladder rung 3 (BASELINE.md).

Drives the REAL runtime — NodeHost facade, step/apply engine, LogDB
persistence (C++ native segmented-WAL engine with fsync when durable),
transport between three NodeHosts, and the TPU batched quorum plugin
(``ExpertConfig.quorum_engine="tpu"``) — with G Raft groups × 3 replicas,
measuring:

* **writes/sec**: completed proposals (propose → user SM applied → future
  notified) per second at 16B payload
* **commit latency**: per-request propose→applied wall time, p50/p99

Two deployment modes:

* **multiprocess (default, E2E_PROCS=3)**: one OS process per NodeHost,
  framed-TCP transport on localhost — the same 3-server shape as the
  reference's published benchmark (``docs/test.md:40-53``) and, for a
  GIL-bound host runtime, the honest one: a single process hosting all
  three replicas serializes leader, follower and client work on one
  interpreter lock.  Leaders are placed deterministically via explicit
  campaigns (etcd ``raft.Campaign``) so setup converges in seconds.
* **single process (E2E_PROCS=1)**: all three NodeHosts in-process over
  the chan transport (the reference's memfs test build shape) — used by
  tests and as a fallback.

This is the honest companion to bench.py's kernel-only number: it includes
proposal ingest, host scheduling, log persistence, transport, apply and
request completion, exactly like the reference's published 9M writes/s
(which is measured through its full stack — ``tools/checkdisk/main.go:98``).

Run standalone:  python bench_e2e.py     (env: E2E_GROUPS, E2E_DURATION,
                 E2E_WINDOW, E2E_RTT_MS, E2E_ENGINE, E2E_DURABLE,
                 E2E_THREADS, E2E_PROCS, E2E_LEADER_MODE, E2E_DEADLINE,
                 E2E_MESH_DEVICES — tpu engine over the mesh dispatch plane)
From bench.py:   bench_e2e.run_quick() → dict for the JSON detail field.
"""
from __future__ import annotations

import collections
import json
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time


def _decide_platform() -> None:
    """The device engine runs on the TPU or not at all: ``BENCH_PLATFORM=cpu``
    (bench.py's explicit rehearsal setting, inherited through the env) is
    the only way onto the CPU backend.  Initializes the backend, so only
    the process that will hold the chip may call this."""
    from dragonboat_tpu import hostplatform

    if os.environ.get("BENCH_PLATFORM") == "cpu":
        hostplatform.force_cpu()
    else:
        hostplatform.require_tpu()


# Minimal in-memory SM (reference checkdisk uses a noop-ish SM).
# Imported — not defined here — because this file runs as __main__ for
# the bench axes: a __main__-scoped class has no ``module:qualname``
# spec a hostproc apply worker could import, so the worker tier would
# silently skip it (ISSUE 12); dragonboat_tpu.testing.CounterSM is the
# same machine in an importable home, marked process-spawnable.
from dragonboat_tpu.testing import CounterSM  # noqa: E402


def _payload() -> bytes:
    """E2E_PAYLOAD bytes (default 16; 1024 for the reference latency
    table's large-payload axis), rounded down to a 16B multiple."""
    return b"0123456789abcdef" * max(
        1, int(os.environ.get("E2E_PAYLOAD", "16")) // 16
    )


BASE_CID = 1000


def _percentiles(lats):
    if not lats:
        return None
    import numpy as np

    a = np.asarray(lats)
    return {
        "p50": round(float(np.percentile(a, 50)) * 1e3, 2),
        "p99": round(float(np.percentile(a, 99)) * 1e3, 2),
        "mean": round(float(a.mean()) * 1e3, 2),
    }


# ======================================================================
# load generation (shared by both modes)
# ======================================================================


def _load_worker(nh_by_cid, cids, payload, window, stop_at, drain_deadline, out):
    """Drive a slice of groups: keep `window` proposals in flight per group.

    Completions are consumed by POLLING finished futures in batches (apply
    order is FIFO per group, so each deque drains from the front) with a
    single blocking wait only when nothing has completed anywhere.  A
    per-op blocking ``Event.wait`` here throttles the whole benchmark: the
    GIL hands the client thread one wakeup per scheduling quantum, and the
    runtime ends up idle waiting for the client to refill windows (the
    native pipeline commits a full window in ~10ms; a blocking client took
    ~50ms to notice).  The throughput claim counts only completions inside
    [start, stop_at]; the drain afterwards is bounded and excluded."""
    lat = []
    in_window = 0
    done = 0
    errors = 0
    abandoned = 0
    inflight = {cid: collections.deque() for cid in cids}
    try:
        sessions = {cid: nh_by_cid[cid].get_noop_session(cid) for cid in cids}

        def refill(cid, dq):
            nonlocal errors
            want = window - len(dq)
            if want <= 0 or time.time() >= stop_at:
                return True
            t0 = time.perf_counter()
            try:
                # burst refill: one tracked future per command, one pass
                # through the propose path (NodeHost.propose_batch)
                states = nh_by_cid[cid].propose_batch(
                    sessions[cid], [payload] * want, timeout=30.0
                )
            except Exception:
                errors += 1
                time.sleep(0.005)  # don't busy-spin on a dead group
                return False
            for rs in states:
                dq.append((t0, rs))
            return True

        while time.time() < stop_at:
            progress = 0
            for cid, dq in inflight.items():
                while dq and dq[0][1].done():
                    t0, rs = dq.popleft()
                    r = rs.result  # property; set before the event
                    t1 = time.perf_counter()
                    if r is not None and r.completed:
                        lat.append(t1 - t0)
                        done += 1
                        progress += 1
                        if time.time() <= stop_at:
                            in_window += 1
                    else:
                        errors += 1
                refill(cid, dq)
            if not progress:
                oldest = None
                for dq in inflight.values():
                    if dq and (oldest is None or dq[0][0] < oldest[0]):
                        oldest = dq[0]
                if oldest is None:
                    time.sleep(0.002)
                else:
                    oldest[1].wait(0.05)
        # bounded drain (not counted toward the rate)
        for cid, dq in inflight.items():
            while dq and time.time() < drain_deadline:
                t0, rs = dq.popleft()
                r = rs.wait(max(0.1, min(10.0, drain_deadline - time.time())))
                t1 = time.perf_counter()
                if r.completed:
                    lat.append(t1 - t0)
                    done += 1
                else:
                    errors += 1
        abandoned = sum(len(dq) for dq in inflight.values())
    except Exception:
        errors += 1 + sum(len(dq) for dq in inflight.values())
    out.append((in_window, done, errors, abandoned, lat))


def _measure(
    leaders, cids, payload, window, stop_at, threads, drain_budget=30.0
) -> dict:
    nthreads = max(1, min(threads, len(cids)))
    slices = [cids[i::nthreads] for i in range(nthreads)]
    out = []
    t_begin = time.time()
    duration = max(stop_at - t_begin, 0.001)
    drain_deadline = stop_at + drain_budget
    ts = [
        threading.Thread(
            target=_load_worker,
            args=(leaders, s, payload, window, stop_at, drain_deadline, out),
        )
        for s in slices
        if s
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    in_window = sum(w for w, _, _, _, _ in out)
    done = sum(d for _, d, _, _, _ in out)
    errors = sum(e for _, _, e, _, _ in out)
    abandoned = sum(a for _, _, _, a, _ in out)
    lats = [l for _, _, _, _, ls in out for l in ls]
    return {
        "writes_per_sec": round(in_window / duration, 1),
        "completed_in_window": in_window,
        "completed": done,
        "errors": errors,
        "abandoned": abandoned,
        "duration_s": round(duration, 2),
        "proposing_groups": len(cids),
        "window": window,
        "latency_ms": _percentiles(lats),
        "_lats": lats,
    }


def _mixed_worker(nh_by_cid, cids, payload, read_ratio, stop_at, out,
                  window=None):
    """9:1-style mixed load (BASELINE.md's Mixed IO row): weighted
    round-robin of linearizable ReadIndex reads and writes, PIPELINED per
    thread — a window of ops is submitted, then completions are drained.

    Per-op latency stays an honest submit→complete round trip; the window
    only removes the client's own serialization (the reference's mixed
    number likewise comes from many concurrent in-flight clients).  The
    server collapses concurrent reads on a group into one ReadIndex
    context (``PendingReadIndex`` take-time batching), so the pipelined
    client measures server capacity instead of client turnaround."""
    if window is None:
        window = int(os.environ.get("E2E_MIXED_WINDOW", "8"))
    reads = writes = errors = 0
    lat_r = []
    lat_w = []
    try:
        sessions = {cid: nh_by_cid[cid].get_noop_session(cid) for cid in cids}
        i = 0
        while time.time() < stop_at:
            batch = []
            for _ in range(window):
                cid = cids[i % len(cids)]
                i += 1
                is_read = (i % (read_ratio + 1)) != 0
                t0 = time.perf_counter()
                try:
                    if is_read:
                        rs = nh_by_cid[cid].read_index(cid, 10.0)
                    else:
                        rs = nh_by_cid[cid].propose(
                            sessions[cid], payload, timeout=10.0
                        )
                    batch.append((is_read, cid, t0, rs))
                except Exception:
                    errors += 1
            for is_read, cid, t0, rs in batch:
                try:
                    r = rs.wait(10.0)
                    if is_read and not r.completed:
                        # dropped/timed-out reads are normal during leader
                        # movement and fast-lane ejects; sync_read retries
                        # them (_sync_retry), so the pipelined client must
                        # too or transient drops read as hard errors
                        rs = nh_by_cid[cid].read_index(cid, 10.0)
                        r = rs.wait(10.0)
                    if r.completed:
                        # completed_at (stamped at notify) keeps per-op
                        # latency honest: a slow op at the head of the
                        # drain loop must not inflate the ops behind it
                        done_t = rs.completed_at or time.perf_counter()
                        if is_read:
                            # the read value itself (sync_read tail)
                            nh_by_cid[cid].get_node(cid).sm.lookup(None)
                            lat_r.append(done_t - t0)
                            reads += 1
                        else:
                            lat_w.append(done_t - t0)
                            writes += 1
                    else:
                        errors += 1
                except Exception:
                    errors += 1
            if errors and not batch:
                time.sleep(0.01)
    except Exception:
        errors += 1
    out.append((reads, writes, errors, lat_r, lat_w))


def _measure_mixed(leaders, cids, payload, read_ratio, stop_at, threads) -> dict:
    nthreads = max(1, min(threads, len(cids)))
    slices = [cids[i::nthreads] for i in range(nthreads)]
    out = []
    t_begin = time.time()
    duration = max(stop_at - t_begin, 0.001)
    ts = [
        threading.Thread(
            target=_mixed_worker,
            args=(leaders, s, payload, read_ratio, stop_at, out),
        )
        for s in slices
        if s
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    reads = sum(r for r, _, _, _, _ in out)
    writes = sum(w for _, w, _, _, _ in out)
    errors = sum(e for _, _, e, _, _ in out)
    lat_r = [l for _, _, _, ls, _ in out for l in ls]
    lat_w = [l for _, _, _, _, ls in out for l in ls]
    return {
        "ops_per_sec": round((reads + writes) / duration, 1),
        "reads": reads,
        "writes": writes,
        "errors": errors,
        "read_ratio": read_ratio,
        "read_latency_ms": _percentiles(lat_r),
        "write_latency_ms": _percentiles(lat_w),
    }


# ======================================================================
# many-client/many-session axis (ISSUE 8: the commit-latency-bound
# scenario — a session serializes its series ids, so per-session
# throughput is one write per commit latency and aggregate throughput is
# sessions/latency; the compartmentalized host plane attacks exactly the
# per-write host overheads this shape exposes)
# ======================================================================


def _session_worker(nh, cid, stop_at, out):
    """One exactly-once session: register, serialized sync proposes until
    the deadline, close.  Latency is the full propose→applied→notified
    round trip (the session semantics forbid pipelining)."""
    done = 0
    errors = 0
    lats = []
    payload = _payload()
    try:
        s = nh.sync_get_session(cid, timeout=30.0)
    except Exception:
        out.append((0, 1, []))
        return
    try:
        while time.time() < stop_at:
            t0 = time.perf_counter()
            try:
                nh.sync_propose(s, payload, timeout=30.0)
                lats.append(time.perf_counter() - t0)
                done += 1
            except Exception:
                errors += 1
                time.sleep(0.01)
    finally:
        try:
            nh.sync_close_session(s, timeout=10.0)
        except Exception:
            pass
    out.append((done, errors, lats))


class _SlowDisk:
    """Simulated contended durability device: every fsync costs
    ``delay_ms`` of device time and the device serializes barrier
    flushes (one platter / one virtio queue — physically what an HDD or
    throttled cloud block device does).  CLEARLY A SIMULATION: the
    slow-disk axis labels its rows with the injected cost; the fast-disk
    axis next to it is the real device."""

    def __init__(self, delay_ms: float):
        self.delay_s = delay_ms / 1e3
        self.mu = threading.Lock()
        self.fsyncs = 0

    def wait(self):
        with self.mu:
            self.fsyncs += 1
            time.sleep(self.delay_s)


def _slow_fs(disk):
    from dragonboat_tpu import vfs

    class SlowFS(vfs.OSFS):
        def fsync(self, f):
            super().fsync(f)
            disk.wait()

        def fsync_dir(self, path):
            super().fsync_dir(path)
            disk.wait()

    return SlowFS()


def run_sessions(
    sessions: int = 32,
    groups: int = 32,
    duration: float = 10.0,
    rtt_ms: int = 50,
    compartments: bool = False,
    n_hosts: int = 3,
    engine: str = "scalar",
    fsync_ms: float = 0.0,
    host_workers: int = 0,
    wal_journal: str = "auto",
) -> dict:
    """Durable single-process 3-host cluster, S exactly-once sessions
    round-robined over G groups.  Returns w/s, commit p50/p99, fsyncs/s
    and (compartments on) the host-plane stats including the measured
    fsync amortization factor.

    ``fsync_ms > 0`` switches the LogDB to the pure-Python WAL backend on
    a SIMULATED serialized slow disk (see :class:`_SlowDisk`) — the
    contended-durability axis where every persisting group riding its own
    fsync is the bottleneck the cross-shard group commit removes."""
    from dragonboat_tpu import Config, NodeHostConfig
    from dragonboat_tpu.config import ExpertConfig, LogDBConfig
    from dragonboat_tpu.logdb import open_logdb
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport import ChanRouter, ChanTransport

    tmp = tempfile.mkdtemp(prefix="dbtpu-sess-")
    router = ChanRouter()
    nhs = []
    disk = _SlowDisk(fsync_ms) if fsync_ms > 0 else None
    slow_fs = _slow_fs(disk) if disk is not None else None
    shards = int(os.environ.get("E2E_SHARDS", "4"))
    try:
        for i in range(1, n_hosts + 1):
            logdb_factory = None
            if slow_fs is not None:
                from dragonboat_tpu.logdb.kv import WalKV

                ldb_dir = os.path.join(tmp, f"ldb{i}")
                logdb_factory = (
                    lambda nhc, d=ldb_dir: open_logdb(
                        d, shards=shards,
                        kv_factory=lambda sd: WalKV(
                            sd, fsync=True, fs=slow_fs
                        ),
                    )
                )
            nhs.append(
                NodeHost(
                    NodeHostConfig(
                        node_host_dir=os.path.join(tmp, f"nh{i}"),
                        rtt_millisecond=rtt_ms,
                        raft_address=f"e2e{i}:1",  # _start_groups wires these names
                        raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                            src, rh, ch, router=router
                        ),
                        logdb_config=LogDBConfig(fsync=True),
                        logdb_factory=logdb_factory,
                        expert=ExpertConfig(
                            quorum_engine=engine,
                            engine_block_groups=max(groups, 64),
                            logdb_shards=shards,
                            host_compartments=compartments,
                            # multi-process host plane (ISSUE 12): 0 =
                            # in-process tiers; N spawns N workers per
                            # host behind shared-memory rings
                            host_workers=host_workers,
                            host_wal_journal=wal_journal,
                            # the journal rides the same simulated device
                            fs=slow_fs,
                        ),
                    )
                )
            )
        cids = _start_groups(nhs, groups, election_rtt=20)
        leaders = _campaign_and_wait(nhs, cids, 120.0)
        fsync0 = sum(nh.logdb.fsync_count() for nh in nhs)
        t0 = time.time()
        stop_at = t0 + duration
        out = []
        ts = [
            threading.Thread(
                target=_session_worker,
                args=(leaders[cids[i % groups]], cids[i % groups], stop_at,
                      out),
            )
            for i in range(sessions)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        elapsed = max(time.time() - t0, 1e-6)
        fsyncs = sum(nh.logdb.fsync_count() for nh in nhs) - fsync0
        done = sum(d for d, _, _ in out)
        errors = sum(e for _, e, _ in out)
        lats = [l for _, _, ls in out for l in ls]
        res = {
            "sessions": sessions,
            "groups": groups,
            "hosts": n_hosts,
            "engine": engine,
            "compartments": compartments,
            "host_workers": host_workers,
            # >0 = the SIMULATED serialized-device axis (fsync costs this
            # many ms and flushes queue at one device); 0 = the real disk
            "fsync_ms": fsync_ms,
            "duration_s": round(elapsed, 2),
            "writes_per_sec": round(done / elapsed, 1),
            "completed": done,
            "errors": errors,
            "commit_latency_ms": _percentiles(lats),
            "fsyncs": fsyncs,
            "fsyncs_per_sec": round(fsyncs / elapsed, 1),
        }
        if compartments or host_workers:
            hp = [nh.hostplane.stats() for nh in nhs]
            res["hostplane"] = hp
            if host_workers:
                res["hostproc"] = [
                    nh.hostproc.stats() for nh in nhs
                    if nh.hostproc is not None
                ]
            # cross-committer fsync amortization, load-weighted across
            # hosts: committer submissions per flusher cycle
            subs = sum(h["wal"]["submissions"] for h in hp)
            flushes = sum(h["wal"]["flushes"] for h in hp)
            res["amortization"] = round(subs / flushes, 2) if flushes else 0.0
        return res
    finally:
        for nh in nhs:
            try:
                nh.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def run_sessions_ab(
    sessions: int = 32, groups: int = 32, duration: float = 10.0,
    fsync_ms: float = 0.0,
) -> dict:
    """Compartments on/off A/B on the many-session axis (ISSUE 8
    acceptance: >= 2.5x at 32 sessions, amortization factor > 1)."""
    off = run_sessions(
        sessions=sessions, groups=groups, duration=duration,
        compartments=False, fsync_ms=fsync_ms,
    )
    on = run_sessions(
        sessions=sessions, groups=groups, duration=duration,
        compartments=True, fsync_ms=fsync_ms,
    )
    speed = (
        round(on["writes_per_sec"] / off["writes_per_sec"], 2)
        if off["writes_per_sec"]
        else None
    )
    return {"off": off, "on": on, "speedup": speed}


def run_host_workers_axis(
    sessions: int = 32, groups: int = 8, duration: float = 8.0,
    workers: int = 0,
) -> dict:
    """Multi-process host plane A/B (ISSUE 12 acceptance): the same
    many-session durable cluster with ``host_workers=0`` (in-process
    compartmentalized plane) vs N worker processes per host.

    The assertion is CPU-topology gated, by design: on a multi-core box
    the worker tier must deliver the scaling target (≥5x e2e w/s at 32+
    sessions with ≥8 cores, pro-rated below that — override with env
    ``E2E_HW_TARGET``); on a single-core box there is no parallelism to
    win — every process time-slices one core and each ring handoff is a
    scheduling quantum — so the axis asserts parity-within-noise
    (workers ≥ ``E2E_HW_PARITY_FLOOR``, default 0.5x, of in-process;
    single-window weather on the 1-vCPU box is ±15%) and LABELS itself
    ``single_core`` so the ledger records the limitation instead of a
    fake win."""
    cores = os.cpu_count() or 1
    n = workers or max(1, min(cores, 4))
    single_core = cores < 2
    # journal mode FORCED symmetrically: a fast-disk auto probe keeps
    # the classic per-shard saves and the WAL worker would idle — the
    # axis wants the redo-journal cycle on both sides so "on" routes the
    # same durability work through the worker that "off" runs in-process
    off = run_sessions(
        sessions=sessions, groups=groups, duration=duration,
        compartments=True, host_workers=0, wal_journal="force",
    )
    on = run_sessions(
        sessions=sessions, groups=groups, duration=duration,
        compartments=True, host_workers=n, wal_journal="force",
    )
    speedup = (
        round(on["writes_per_sec"] / off["writes_per_sec"], 2)
        if off["writes_per_sec"] else None
    )
    if single_core:
        target = float(os.environ.get("E2E_HW_PARITY_FLOOR", "0.5"))
        assert_ok = speedup is not None and speedup >= target
        assertion = (
            f"single-core parity-within-noise: {speedup}x >= {target}x"
        )
    else:
        target = float(
            os.environ.get(
                "E2E_HW_TARGET",
                "5.0" if cores >= 8 else str(round(0.6 * cores, 2)),
            )
        )
        assert_ok = speedup is not None and speedup >= target
        assertion = f"multi-core scaling: {speedup}x >= {target}x"
    hp = on.get("hostproc") or []
    return {
        "cores": cores,
        "single_core": single_core,
        "workers": n,
        "axis": [{"off": off, "on": on, "speedup": speedup}],
        "restarts": sum(h.get("restarts", 0) for h in hp),
        "fallbacks": {
            k: sum(h.get("fallbacks", {}).get(k, 0) for h in hp)
            for k in ("encode", "wal", "apply")
        },
        "assertion": assertion,
        "assert_ok": assert_ok,
    }


# ======================================================================
# single-process mode (chan transport; tests + fallback)
# ======================================================================


def _mk_nodehosts(n_hosts, groups, rtt_ms, engine, dirs, trace=0):
    from dragonboat_tpu import NodeHostConfig
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport import ChanRouter, ChanTransport

    router = ChanRouter()
    nhs = []
    for i in range(1, n_hosts + 1):
        nhs.append(
            NodeHost(
                NodeHostConfig(
                    node_host_dir=dirs[i - 1] if dirs else ":memory:",
                    rtt_millisecond=rtt_ms,
                    raft_address=f"e2e{i}:1",
                    raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                        src, rh, ch, router=router
                    ),
                    trace_sample_every=trace,
                    expert=ExpertConfig(
                        quorum_engine=engine,
                        engine_block_groups=max(groups, 64),
                        logdb_shards=4,
                        # mesh-sharded dispatch plane (ISSUE 16): N > 1
                        # builds each tpu-engine coordinator over the
                        # MeshQuorumEngine facade — one dispatch stream
                        # per shard instead of one GSPMD program
                        engine_mesh_devices=int(
                            os.environ.get("E2E_MESH_DEVICES", "0")
                        ),
                    ),
                )
            )
        )
    return nhs


def _start_groups(nhs, groups, base_cid=BASE_CID, election_rtt=20):
    from dragonboat_tpu import Config

    addrs = {i: f"e2e{i}:1" for i in range(1, len(nhs) + 1)}
    for g in range(groups):
        cid = base_cid + g
        for i, nh in enumerate(nhs, start=1):
            nh.start_cluster(
                addrs,
                False,
                CounterSM,
                Config(
                    cluster_id=cid,
                    node_id=i,
                    election_rtt=election_rtt,
                    heartbeat_rtt=1,
                    snapshot_entries=0,
                ),
            )
    return [base_cid + g for g in range(groups)]


def _campaign_and_wait(nhs, cids, timeout):
    """Deterministic leader placement: replica ``cid % n_hosts`` campaigns
    explicitly (etcd raft.Campaign), spreading leaders evenly without
    waiting out randomized election timeouts."""
    n = len(nhs)
    for cid in cids:
        nhs[cid % n].get_node(cid).request_campaign()
    deadline = time.time() + timeout
    leaders = {}
    remaining = set(cids)
    while remaining and time.time() < deadline:
        for cid in list(remaining):
            for nh in nhs:
                lid, ok = nh.get_leader_id(cid)
                if ok and 1 <= lid <= len(nhs):
                    leaders[cid] = nhs[lid - 1]
                    remaining.discard(cid)
                    break
        if remaining:
            time.sleep(0.05)
    if remaining:
        raise TimeoutError(f"{len(remaining)}/{len(cids)} groups leaderless")
    return leaders


def run(
    groups: int = 1024,
    duration: float = 10.0,
    window: int = 16,
    rtt_ms: int = 500,
    engine: str = "tpu",
    durable: bool = True,
    threads: int = 16,
    n_hosts: int = 3,
    leader_timeout: float = 180.0,
    latency_groups: int = 64,
) -> dict:
    """Single-process run; two measurement phases over one live cluster:

    1. *throughput*: every group proposes with `window` in flight — the
       sustained writes/s number.  Per-request latency in this phase is
       queueing (Little's law: window/per-group-rate), reported but not the
       latency claim.
    2. *latency*: `latency_groups` groups propose with window=1 while the
       rest stay idle — the propose→applied commit-latency distribution
       (BASELINE.md's P99 commit latency axis).
    """
    payload = _payload()  # 16B default (BASELINE.md ladder payload)
    tmp = None
    dirs = None
    if durable:
        tmp = tempfile.mkdtemp(prefix="dbtpu-e2e-")
        dirs = [os.path.join(tmp, f"nh{i}") for i in range(n_hosts)]
    t_setup = time.perf_counter()
    nhs = _mk_nodehosts(n_hosts, groups, rtt_ms, engine, dirs)
    try:
        cids = _start_groups(nhs, groups)
        leaders = _campaign_and_wait(nhs, cids, leader_timeout)
        setup_s = time.perf_counter() - t_setup
        print(f"e2e setup_s={setup_s:.1f}", file=sys.stderr)

        tput = _measure(
            leaders, cids, payload, window, time.time() + duration, threads
        )
        lat = _measure(
            leaders,
            cids[: min(latency_groups, groups)],
            payload,
            1,
            time.time() + min(duration, 5.0),
            threads,
        )
        tput.pop("_lats", None)
        lat.pop("_lats", None)
        return {
            "groups": groups,
            "hosts": n_hosts,
            "procs": 1,
            "engine": engine,
            "durable": durable,
            "payload_bytes": len(payload),
            "setup_s": round(setup_s, 1),
            "writes_per_sec": tput["writes_per_sec"],
            "commit_latency_ms": lat["latency_ms"],
            "throughput_phase": tput,
            "latency_phase": lat,
        }
    finally:
        for nh in nhs:
            try:
                nh.stop()
            except Exception:
                pass
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


# ======================================================================
# trace axis (ISSUE 9): overhead A/B + per-stage latency attribution
# ======================================================================


def _set_tracing(nhs, on: bool) -> None:
    """Attach/detach the request tracer across a LIVE cluster.  Every
    hook gates on a plain ``is not None`` check, so the detached half of
    the A/B runs the trace-off path on the very same cluster — no
    cluster-to-cluster weather in the comparison.  The replication
    attribution plane (obs/replattr.py, ISSUE 14) lives and dies with
    the tracer: the same toggle detaches it everywhere down to the raft
    ack/commit hooks, so the off half also prices the replattr latch."""
    for nh in nhs:
        t = nh._trace_axis_tracer if on else None
        ra = (getattr(nh, "_trace_axis_replattr", None) or None) if on else None
        nh.tracer = t
        nh.replattr = ra
        nh.engine.tracer = t
        if nh.quorum_coordinator is not None:
            nh.quorum_coordinator.tracer = t
            nh.quorum_coordinator.replattr = ra
        with nh._mu:
            nodes = [n for n in nh._clusters.values() if n is not None]
        for n in nodes:
            n.tracer = t
            n.pending_reads._tracer = t
            n.replattr = ra
            n.peer.raft.replattr = ra


def _merged_stage_stats(nhs) -> dict:
    """Per-stage p50/p99 + share-of-e2e over every host's completed
    trace ring (leaders are spread, so each host traced its share) —
    the library's own ``compute_stage_stats`` does the math, so this
    table and ``nh.tracer.stage_stats()`` can never disagree."""
    from dragonboat_tpu.obs.trace import compute_stage_stats

    return compute_stage_stats(
        t for nh in nhs for t in nh._trace_axis_tracer.traces()
    )


def run_trace_axis() -> dict:
    """Request-tracing axis (ISSUE 9): trace-on vs trace-off throughput
    on the live host loop (interleaved windows on ONE cluster, best-of —
    the obs axis's scheduler-weather discipline; <5% asserted) plus the
    per-stage latency attribution tables, for BOTH the scalar and the
    tpu-engine (warmed fused) paths.  The perf ledger's "Latency
    attribution" table derives from this section.

    Env knobs: TRACE_AXIS_GROUPS (64), TRACE_AXIS_DURATION (5s/window),
    TRACE_AXIS_WINDOW (8 in flight/group), TRACE_AXIS_SAMPLE (1-in-8).
    """
    groups = int(os.environ.get("TRACE_AXIS_GROUPS", "64"))
    duration = float(os.environ.get("TRACE_AXIS_DURATION", "5"))
    window = int(os.environ.get("TRACE_AXIS_WINDOW", "8"))
    sample = int(os.environ.get("TRACE_AXIS_SAMPLE", "8"))
    threads = int(os.environ.get("TRACE_AXIS_THREADS", "4"))
    # rtt low enough that the loaded box's round thread (niced +5) sees
    # tick deficits > 1 — the tpu rows then measure the FUSED host loop
    # (fused_dispatches in the output evidences it), not just a warmed
    # one
    rtt_ms = int(os.environ.get("TRACE_AXIS_RTT_MS", "30"))
    payload = _payload()
    out = {
        "groups": groups,
        "window": window,
        "sample_every": sample,
        "window_duration_s": duration,
        "rtt_ms": rtt_ms,
        "engines": {},
    }
    for engine in ("scalar", "tpu"):
        tmp = tempfile.mkdtemp(prefix=f"dbtpu-trace-{engine}-")
        dirs = [os.path.join(tmp, f"nh{i}") for i in range(3)]
        nhs = _mk_nodehosts(3, groups, rtt_ms, engine, dirs, trace=sample)
        try:
            for nh in nhs:
                # keep a handle: the A/B detaches/reattaches mid-run
                nh._trace_axis_tracer = nh.tracer
                nh._trace_axis_replattr = nh.replattr
            cids = _start_groups(nhs, groups)
            leaders = _campaign_and_wait(nhs, cids, 180.0)
            fused_before = 0
            if engine == "tpu":
                # the fused host loop: wait for the background AOT warm
                # so measured rounds can replay tick backlogs fused
                deadline = time.time() + 180
                while time.time() < deadline and not all(
                    nh.quorum_coordinator.eng.fused_ready for nh in nhs
                ):
                    time.sleep(0.25)
                fused_before = sum(
                    nh.quorum_coordinator.fused_dispatches for nh in nhs
                )

            def measure(on):
                _set_tracing(nhs, on)
                m = _measure(
                    leaders, cids, payload, window,
                    time.time() + duration, threads, drain_budget=15.0,
                )
                return m["writes_per_sec"]

            measure(False)  # warmup window (compile, cache, enrollment)
            # paired A/B, MEAN of pair-wise deltas over an EVEN number
            # of alternating-order pairs: this axis has ±15%
            # window-to-window weather on a 1-vCPU box (BENCH_r09 note),
            # so single windows or best-of measure the weather, not the
            # tracer.  Adjacent windows pair off (drift cancels within
            # a pair); the order alternates per pair and the count is
            # even, so a systematic second-window penalty contributes
            # +p,-p,... and cancels EXACTLY in the mean.  The assert is
            # one-sided with a 2-SEM noise allowance — the residual
            # pair noise is published (pair_deltas/sem) so the artifact
            # shows the measurement's power, not just its verdict.
            pairs = max(2, int(os.environ.get("TRACE_AXIS_PAIRS", "6")) // 2 * 2)
            deltas = []
            wps_on = wps_off = 0.0
            for pair in range(pairs):
                if pair % 2 == 0:
                    on = measure(True)
                    off = measure(False)
                else:
                    off = measure(False)
                    on = measure(True)
                wps_on = max(wps_on, on)
                wps_off = max(wps_off, off)
                deltas.append((off - on) / off * 100.0)
            mean = sum(deltas) / len(deltas)
            var = sum((d - mean) ** 2 for d in deltas) / max(1, len(deltas) - 1)
            sem = (var / len(deltas)) ** 0.5
            overhead = round(mean, 2)
            # attribution phase: a DEDICATED traced window — the rings
            # are cleared (and widened past the steady-state cap) first,
            # so the published percentiles cover exactly this window's
            # population instead of the newest keep=256 tail of the A/B
            for nh in nhs:
                nh._trace_axis_tracer.reset_completed(keep=8192)
            _set_tracing(nhs, True)
            _measure(
                leaders, cids, payload, window, time.time() + duration,
                threads, drain_budget=15.0,
            )
            attribution = _merged_stage_stats(nhs)
            eng_out = {
                "writes_per_sec_trace_off": round(wps_off, 1),
                "writes_per_sec_trace_on": round(wps_on, 1),
                "trace_overhead_pct": overhead,  # mean pair-wise
                "trace_overhead_sem_pct": round(sem, 2),
                "pair_deltas_pct": [round(d, 2) for d in deltas],
                "trace_overhead_ok": overhead < 5.0 + 2 * sem,
                "attribution": attribution,
            }
            if engine == "tpu":
                eng_out["fused_dispatches"] = sum(
                    nh.quorum_coordinator.fused_dispatches for nh in nhs
                ) - fused_before
                eng_out["fused_ready"] = all(
                    nh.quorum_coordinator.eng.fused_ready for nh in nhs
                )
            assert overhead < 5.0 + 2 * sem, (
                f"trace overhead too high on {engine}: {overhead}% "
                f"(± {sem:.1f} SEM; {wps_on:.0f} vs {wps_off:.0f} w/s)"
            )
            out["engines"][engine] = eng_out
        finally:
            for nh in nhs:
                try:
                    nh.stop()
                except Exception:
                    pass
            shutil.rmtree(tmp, ignore_errors=True)
    out["trace_overhead_ok"] = all(
        e.get("trace_overhead_ok") for e in out["engines"].values()
    )
    return out


# ======================================================================
# cluster health axis (ISSUE 13): health-on/off overhead + a churn
# phase producing detector events with recovery durations
# ======================================================================


def _set_health(nhs, on: bool) -> None:
    """Attach/detach the health sampler across a LIVE cluster (the
    ``_set_tracing`` discipline): the tick-worker hook gates on a plain
    ``is not None`` check, so the detached half of the A/B runs the
    health-off path on the very same cluster."""
    for nh in nhs:
        nh.health = nh._health_axis_sampler if on else None


def run_health_axis() -> dict:
    """Cluster-health axis (ISSUE 13): health-on vs health-off
    throughput on a live 3-host cluster — interleaved windows on ONE
    cluster, but scored as the MEAN pair-wise delta ± SEM over
    alternating-order pairs (the trace-axis discipline, not raw
    best-of: this is the live e2e stack, whose window-to-window weather
    on a 1-vCPU box is ±15% — a best-of-3 measured the scheduler, and
    the first capture failed its own gate at 6.85% with the sampler
    costing ~1ms per 50ms cadence) — <5% + 2·SEM asserted; then a
    leadership-churn phase with health ON so the leader-flap detector
    opens and closes with real recovery durations.  The perf ledger's
    "Cluster health" table (detector counts, recovery p50/p99) derives
    from this section's health ring dump.

    Env knobs: HEALTH_AXIS_GROUPS (32), HEALTH_AXIS_DURATION (4s/window),
    HEALTH_AXIS_PAIRS (4), HEALTH_AXIS_SAMPLE_MS (50).
    """
    from dragonboat_tpu.obs.health import HealthSampler

    groups = int(os.environ.get("HEALTH_AXIS_GROUPS", "32"))
    duration = float(os.environ.get("HEALTH_AXIS_DURATION", "4"))
    pairs = max(2, int(os.environ.get("HEALTH_AXIS_PAIRS", "4")) // 2 * 2)
    sample_ms = int(os.environ.get("HEALTH_AXIS_SAMPLE_MS", "50"))
    window = int(os.environ.get("HEALTH_AXIS_WINDOW", "8"))
    threads = int(os.environ.get("HEALTH_AXIS_THREADS", "4"))
    payload = _payload()
    tmp = tempfile.mkdtemp(prefix="dbtpu-health-")
    dirs = [os.path.join(tmp, f"nh{i}") for i in range(3)]
    nhs = _mk_nodehosts(3, groups, 30, "scalar", dirs)
    out = {
        "groups": groups,
        "window_duration_s": duration,
        "pairs": pairs,
        "sample_ms": sample_ms,
    }
    try:
        cids = _start_groups(nhs, groups)
        leaders = _campaign_and_wait(nhs, cids, 180.0)
        for nh in nhs:
            # one sampler per host, constructed once and A/B-toggled;
            # tight flap knobs so the churn phase's transfers open the
            # leader-flap detector and a short quiet window closes it
            nh._health_axis_sampler = HealthSampler(
                nh, sample_ms=sample_ms,
                registry=nh.metrics_registry,
                leader_flap_changes=2,
                flap_window_s=3.0,
            )

        def measure(on):
            _set_health(nhs, on)
            m = _measure(
                leaders, cids, payload, window,
                time.time() + duration, threads, drain_budget=15.0,
            )
            return m["writes_per_sec"]

        measure(False)  # warmup window
        # paired A/B, mean of pair-wise deltas over an even number of
        # alternating-order pairs (drift cancels within a pair, a
        # systematic second-window penalty cancels across the
        # alternation) — the residual pair noise is published so the
        # artifact shows the measurement's power, not just its verdict
        deltas = []
        wps_on = wps_off = 0.0
        for pair in range(pairs):
            if pair % 2 == 0:
                on = measure(True)
                off = measure(False)
            else:
                off = measure(False)
                on = measure(True)
            wps_on = max(wps_on, on)
            wps_off = max(wps_off, off)
            deltas.append((off - on) / off * 100.0)
        mean = sum(deltas) / len(deltas)
        var = sum((d - mean) ** 2 for d in deltas) / max(1, len(deltas) - 1)
        sem = (var / len(deltas)) ** 0.5
        overhead = round(mean, 2)
        out["writes_per_sec_health_on"] = round(wps_on, 1)
        out["writes_per_sec_health_off"] = round(wps_off, 1)
        out["health_overhead_pct"] = overhead
        out["health_overhead_sem_pct"] = round(sem, 2)
        out["pair_deltas_pct"] = [round(d, 2) for d in deltas]
        out["health_overhead_ok"] = overhead < 5.0 + 2 * sem
        assert overhead < 5.0 + 2 * sem, (
            f"health overhead too high: {overhead}% (± {sem:.1f} SEM; "
            f"{wps_on:.0f} vs {wps_off:.0f} w/s)"
        )

        # churn phase: transfer one group's leadership around the ring
        # under sampling — each double-transfer is ≥2 leader changes
        # inside the flap window on some host, opening leader_flap;
        # the quiet tail closes it and records the recovery duration
        _set_health(nhs, True)
        churn_cid = cids[0]
        for i in range(4):
            for nh in nhs:
                lid, ok = nh.get_leader_id(churn_cid)
                if ok and 1 <= lid <= 3:
                    target = (lid % 3) + 1
                    try:
                        nhs[lid - 1].request_leader_transfer(
                            churn_cid, target
                        )
                    except Exception:
                        pass
                    break
            time.sleep(0.8)
        # quiet window: let the flap deque age out and the event close
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if any(
                nh._health_axis_sampler.recovery_stats().get("leader_flap")
                for nh in nhs
            ) and not any(
                nh._health_axis_sampler.open_events() for nh in nhs
            ):
                break
            time.sleep(0.5)

        # aggregate detector counts + merged recovery durations
        detectors: dict = {}
        merged: dict = {}
        samples_total = 0
        for nh in nhs:
            hs = nh._health_axis_sampler
            samples_total += hs._n
            for det, c in hs.opened.items():
                d = detectors.setdefault(det, {"opened": 0, "closed": 0})
                d["opened"] += c
                d["closed"] += len(hs._recoveries[det])
                merged.setdefault(det, []).extend(hs._recoveries[det])
        out["samples_total"] = samples_total
        out["detectors"] = {
            d: v for d, v in detectors.items() if v["opened"]
        }
        from dragonboat_tpu.obs.health import _pctile

        out["recovery"] = {
            det: {
                "n": len(durs),
                "p50_s": round(_pctile(durs, 50), 4),
                "p99_s": round(_pctile(durs, 99), 4),
                "max_s": round(max(durs), 4),
            }
            for det, durs in merged.items() if durs
        }
        out["churn_events_ok"] = bool(out["recovery"].get("leader_flap"))
        # the ring dump of the host that recorded the churn (artifact
        # evidence for the ledger; trimmed)
        dump_nh = max(
            nhs, key=lambda nh: len(
                nh._health_axis_sampler._recoveries["leader_flap"]
            ),
        )
        out["ring"] = dump_nh._health_axis_sampler.to_json(limit=24)
        return out
    finally:
        for nh in nhs:
            try:
                nh.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


# ======================================================================
# device telemetry axis (ISSUE 20): aggregate sampler wall flat in G +
# telem-fold dispatch overhead + on-device top-K hit rate
# ======================================================================


class _TelemNodeShim:
    """Per-group stand-in for the sampler walk: a bounded-cost
    ``health_snapshot`` like ``Node``'s, so sampler wall measures the
    walk discipline, not raft bookkeeping."""

    def health_snapshot(self, lock_timeout=0.0):
        return {"committed": 1, "applied": 1, "leader_id": 1}


class _TelemQcShim:
    """Engine-facade stand-in exposing exactly the coordinator surface
    ``HealthSampler.sample`` touches in aggregate mode."""

    def __init__(self, eng):
        self.eng = eng

    def telem_snapshot(self):
        return self.eng.telem_snapshot()

    def registered_cids(self):
        return set(self.eng.groups)

    def health_snapshot(self):
        return None


class _TelemNhShim:
    def __init__(self, eng, cids):
        self.quorum_coordinator = _TelemQcShim(eng)
        self._nodes = {c: _TelemNodeShim() for c in cids}
        self.tick_count = 0
        self.hostplane = None
        self.hostproc = None

    def _get_nodes(self):
        return None, self._nodes


def _telem_engine(groups, last_index=16, telem=True, topk=None):
    from dragonboat_tpu.ops.engine import BatchedQuorumEngine

    eng = BatchedQuorumEngine(groups, 3, event_cap=4 * groups)
    if telem:
        eng.enable_telem(topk=topk)
    for cid in range(1, groups + 1):
        eng.add_group(cid, node_ids=[1, 2, 3], self_id=1)
        eng.set_leader(cid, term=1, term_start=1, last_index=last_index)
    eng._upload_dirty()
    return eng


def run_telem_axis() -> dict:
    """Device telemetry axis (ISSUE 20): three pillars, engine-level so
    the rung-5-scale group counts fit the driver budget on cpu.

    1. **Sampler wall flat in G**: aggregate-mode sampler passes over a
       small and a 64×-larger device-backed engine — the walk set is
       top-K + open events, not the group axis, so the per-pass wall
       must grow ≤2× across the 64× group growth (the O(1)-in-G
       acceptance gate).  Full-walk wall at both sizes is captured for
       contrast (that one DOES scale with G).
    2. **Fold dispatch overhead**: telem-on vs telem-off dispatch wall
       on twin engines fed the same ack schedule, interleaved windows
       scored as mean pair-wise delta ± SEM (the trace-axis
       discipline) — <5% + 2·SEM asserted.
    3. **Top-K hit rate**: planted worst-lag groups must surface in the
       on-device top-K with their exact lags, fresh engine per trial.

    Env knobs: TELEM_AXIS_GROUPS (1024), TELEM_AXIS_SCALE (64),
    TELEM_AXIS_PASSES (50), TELEM_AXIS_PAIRS (4),
    TELEM_AXIS_DISPATCHES (30/window), TELEM_AXIS_TRIALS (4).
    """
    from dragonboat_tpu.events import MetricsRegistry
    from dragonboat_tpu.obs.health import HealthSampler

    g_small = int(os.environ.get("TELEM_AXIS_GROUPS", "1024"))
    scale = int(os.environ.get("TELEM_AXIS_SCALE", "64"))
    passes = int(os.environ.get("TELEM_AXIS_PASSES", "50"))
    pairs = max(2, int(os.environ.get("TELEM_AXIS_PAIRS", "4")) // 2 * 2)
    disp_per_win = int(os.environ.get("TELEM_AXIS_DISPATCHES", "30"))
    trials = int(os.environ.get("TELEM_AXIS_TRIALS", "4"))
    g_big = g_small * scale
    out: dict = {"groups_small": g_small, "groups_big": g_big,
                 "scale": scale}

    # -- pillar 1: sampler wall per pass, aggregate vs full walk -------
    def sampler_wall(groups, aggregate, n_passes):
        eng = _telem_engine(groups)
        # one real fold so the aggregate path has a snapshot to ride
        for cid in range(1, min(groups, 64) + 1):
            eng.ack(cid, 2, 1 + cid % 8)
        eng.step(do_tick=False)
        cids = list(range(1, groups + 1))
        hs = HealthSampler(
            _TelemNhShim(eng, cids), registry=MetricsRegistry(),
            aggregate=aggregate,
        )
        s = hs.sample()  # warm pass (drill-set cache, allocation)
        walls = []
        for _ in range(n_passes):
            s = hs.sample()
            walls.append(s["wall_ms"])
        walls.sort()
        return walls[len(walls) // 2], len(s.get("groups") or {})

    agg_small, walk_small = sampler_wall(g_small, True, passes)
    agg_big, walk_big = sampler_wall(g_big, True, passes)
    # the full-walk contrast pays O(G) per pass — a handful suffices
    full_small, _ = sampler_wall(g_small, False, max(3, passes // 10))
    full_big, _ = sampler_wall(g_big, False, max(3, passes // 10))
    # floor the denominator: a sub-10µs pass is measurement noise and
    # would flunk the ratio on jitter alone
    ratio = agg_big / max(agg_small, 0.01)
    out["sampler_wall_ms"] = {
        "aggregate_small": round(agg_small, 4),
        "aggregate_big": round(agg_big, 4),
        "full_small": round(full_small, 4),
        "full_big": round(full_big, 4),
        "aggregate_walk_small": walk_small,
        "aggregate_walk_big": walk_big,
        "aggregate_big_over_small": round(ratio, 2),
        "full_big_over_small": round(full_big / max(full_small, 0.01), 2),
    }
    out["sampler_flat_ok"] = ratio <= 2.0
    assert ratio <= 2.0, (
        f"aggregate sampler wall not flat in G: {agg_small:.3f}ms @ "
        f"{g_small} vs {agg_big:.3f}ms @ {g_big} ({ratio:.1f}x)"
    )

    # -- pillar 2: telem-fold dispatch overhead, paired A/B ------------
    # Gated on the FUSED MULTI-ROUND shape — the coordinator's deployed
    # dispatch (stage K rounds, one step_rounds scan) where the fold
    # runs ONCE on the block's final state, amortizing over the scanned
    # rounds exactly as it does in production.  The single-round shape
    # (fold per dispatch, the worst case) is measured too but recorded
    # informationally: on the cpu backend its ~2.7ms wall is host-
    # staging-dominated and the window weather (±15%, occasional 10×
    # outliers) swamps the fold's ~0.07ms program delta.
    rounds_per_block = int(os.environ.get("TELEM_AXIS_ROUNDS", "8"))
    eng_on = _telem_engine(g_small)
    eng_off = _telem_engine(g_small, telem=False)

    def window_multi(eng, seed):
        rng = random.Random(seed)
        t0 = time.perf_counter()
        for _ in range(disp_per_win):
            for _ in range(rounds_per_block):
                for _ in range(32):
                    eng.ack(rng.randrange(1, g_small + 1), 2,
                            rng.randrange(1, 17))
                eng.begin_round()
            eng.step_rounds(do_tick=False)
        return (disp_per_win * rounds_per_block) / (
            time.perf_counter() - t0
        )

    def window_single(eng, seed):
        rng = random.Random(seed)
        t0 = time.perf_counter()
        for _ in range(disp_per_win):
            for _ in range(32):
                eng.ack(rng.randrange(1, g_small + 1), 2,
                        rng.randrange(1, 17))
            eng.step(do_tick=False)
        return disp_per_win / (time.perf_counter() - t0)

    def paired_delta(win_fn, n_pairs, seed0):
        deltas = []
        for pair in range(n_pairs):
            seed = seed0 + pair
            if pair % 2 == 0:  # ABBA cancels slow box drift
                on = win_fn(eng_on, seed)
                off = win_fn(eng_off, seed)
            else:
                off = win_fn(eng_off, seed)
                on = win_fn(eng_on, seed)
            deltas.append((off - on) / off * 100.0)
        mean = sum(deltas) / len(deltas)
        var = sum((d - mean) ** 2 for d in deltas) / max(
            1, len(deltas) - 1
        )
        sem = (var / len(deltas)) ** 0.5
        return mean, sem, deltas

    window_multi(eng_on, 0)   # compile all variants before scoring
    window_multi(eng_off, 0)
    window_single(eng_on, 0)
    window_single(eng_off, 0)
    mean, sem, deltas = paired_delta(window_multi, pairs, 100)
    s_mean, s_sem, _ = paired_delta(window_single, max(2, pairs // 2), 500)
    out["rounds_per_block"] = rounds_per_block
    out["dispatch_overhead_pct"] = round(mean, 2)
    out["dispatch_overhead_sem_pct"] = round(sem, 2)
    out["pair_deltas_pct"] = [round(d, 2) for d in deltas]
    out["single_round_overhead_pct"] = round(s_mean, 2)
    out["single_round_overhead_sem_pct"] = round(s_sem, 2)
    out["dispatch_overhead_ok"] = mean < 5.0 + 2 * sem
    assert mean < 5.0 + 2 * sem, (
        f"telem fold dispatch overhead too high: {mean:.2f}% "
        f"(± {sem:.2f} SEM)"
    )

    # -- pillar 3: top-K hit rate on planted worst lags ----------------
    k = 8
    hits = total = 0
    for trial in range(trials):
        rng = random.Random(7000 + trial)
        g = 512
        eng = _telem_engine(g, last_index=8, topk=k)
        planted = rng.sample(range(1, g + 1), k)
        for cid in range(1, g + 1):
            if cid not in planted:
                eng.ack(cid, 2, 8)  # lag 0
        for i, cid in enumerate(planted):
            eng.ack(cid, 2, i % 4)  # lag 8 - i%4: the worst in the shard
        eng.step(do_tick=False)
        top = {c for c, _lag in eng.telem_snapshot()["topk"]}
        hits += len(top & set(planted))
        total += k
    hit_rate = hits / total
    out["topk_trials"] = trials
    out["topk_hit_rate"] = round(hit_rate, 4)
    out["topk_ok"] = hit_rate == 1.0
    assert hit_rate == 1.0, f"planted worst groups missed top-K: {hit_rate}"
    return out


# ======================================================================
# device capacity & profiling axis (ISSUE 15): profile-on/off overhead
# + capacity-model-vs-measured error + the warm-set program registry
# ======================================================================


def _set_devprof(nhs, on: bool) -> None:
    """Attach/detach the device profiling plane across a LIVE tpu-engine
    cluster (the ``_set_health``/``_set_tracing`` discipline): every
    engine dispatch site gates on a plain ``_devprof is not None``
    check, so the detached half of the A/B runs the profile-off path on
    the very same cluster."""
    for nh in nhs:
        if on:
            # the coordinator helper is THE wiring point (binds the
            # engine, records coordinator.devprof, hands the plane the
            # coordinator for devsm snapshots) — hand-rolled binds here
            # would silently fork from it
            nh.quorum_coordinator.enable_devprof(nh._devprof_axis)
        else:
            nh.quorum_coordinator.eng.disable_devprof()


def run_devprof_axis() -> dict:
    """Device capacity & profiling axis (ISSUE 15): profile-on vs
    profile-off throughput on a live 3-host TPU-ENGINE cluster —
    interleaved windows on one cluster, scored as the MEAN pair-wise
    delta ± SEM over alternating-order pairs (the r13 health-axis
    discipline: single-window weather on a 1-vCPU box is ±15%, pairing
    + alternation cancels it) — <5% + 2·SEM asserted.  Then the
    capacity phase: every host's HBM ledger is diffed against the
    capacity model (|error| < 10% asserted — the model is the sizing
    input for ROADMAP items 2/3), and the warm-set program registry is
    collected on one host with non-zero cost/memory analysis asserted
    per program (the perf ledger's "Device programs" table).

    Env knobs: DEVPROF_AXIS_GROUPS (8), DEVPROF_AXIS_DURATION
    (4s/window), DEVPROF_AXIS_PAIRS (4), DEVPROF_AXIS_SAMPLE (8),
    DEVPROF_AXIS_THREADS (4).
    """
    from dragonboat_tpu.obs.devprof import DevProf

    groups = int(os.environ.get("DEVPROF_AXIS_GROUPS", "8"))
    duration = float(os.environ.get("DEVPROF_AXIS_DURATION", "4"))
    pairs = max(2, int(os.environ.get("DEVPROF_AXIS_PAIRS", "4")) // 2 * 2)
    sample_every = int(os.environ.get("DEVPROF_AXIS_SAMPLE", "8"))
    window = int(os.environ.get("DEVPROF_AXIS_WINDOW", "8"))
    threads = int(os.environ.get("DEVPROF_AXIS_THREADS", "4"))
    payload = _payload()
    tmp = tempfile.mkdtemp(prefix="dbtpu-devprof-")
    dirs = [os.path.join(tmp, f"nh{i}") for i in range(3)]
    nhs = _mk_nodehosts(3, groups, 30, "tpu", dirs)
    out = {
        "groups": groups,
        "window_duration_s": duration,
        "pairs": pairs,
        "sample_every": sample_every,
    }
    try:
        cids = _start_groups(nhs, groups)
        leaders = _campaign_and_wait(nhs, cids, 180.0)
        for nh in nhs:
            # one DevProf per host, constructed once and A/B-toggled;
            # the registry is the host's own so the exposition carries
            # the families during the on-windows
            nh._devprof_axis = DevProf(
                registry=nh.metrics_registry,
                recorder=nh.flight_recorder,
                sample_every=sample_every,
            )

        def measure(on):
            _set_devprof(nhs, on)
            m = _measure(
                leaders, cids, payload, window,
                time.time() + duration, threads, drain_budget=15.0,
            )
            return m["writes_per_sec"]

        measure(False)  # warmup window
        deltas = []
        wps_on = wps_off = 0.0
        for pair in range(pairs):
            if pair % 2 == 0:
                on = measure(True)
                off = measure(False)
            else:
                off = measure(False)
                on = measure(True)
            wps_on = max(wps_on, on)
            wps_off = max(wps_off, off)
            deltas.append((off - on) / off * 100.0)
        mean = sum(deltas) / len(deltas)
        var = sum((d - mean) ** 2 for d in deltas) / max(1, len(deltas) - 1)
        sem = (var / len(deltas)) ** 0.5
        overhead = round(mean, 2)
        out["writes_per_sec_devprof_on"] = round(wps_on, 1)
        out["writes_per_sec_devprof_off"] = round(wps_off, 1)
        out["devprof_overhead_pct"] = overhead
        out["devprof_overhead_sem_pct"] = round(sem, 2)
        out["pair_deltas_pct"] = [round(d, 2) for d in deltas]
        out["devprof_overhead_ok"] = overhead < 5.0 + 2 * sem
        assert overhead < 5.0 + 2 * sem, (
            f"devprof overhead too high: {overhead}% (± {sem:.1f} SEM; "
            f"{wps_on:.0f} vs {wps_off:.0f} w/s)"
        )

        # capacity phase (profile back ON so the ledger gauges are live)
        _set_devprof(nhs, True)
        errors = []
        for nh in nhs:
            led = nh._devprof_axis.hbm_ledger()
            cap = led["capacity"]
            errors.append(abs(cap["model_error_pct"]))
            assert abs(cap["model_error_pct"]) < 10.0, cap
        dp0 = nhs[0]._devprof_axis
        led0 = dp0.hbm_ledger()
        cap0 = led0["capacity"]
        # reference sizing at a 16 GiB HBM budget (no chip attached on
        # the capture box — the per-group figure is backend-exact, the
        # budget is the documented reference input)
        ref = dp0.capacity_model(budget_bytes=16 << 30)
        out["capacity"] = {
            "planes": led0["planes"],
            "state_bytes": led0["state_bytes"],
            "measured_state_bytes": cap0.get("measured_state_bytes"),
            "bytes_per_group": round(cap0["bytes_per_group"], 1),
            "bytes_per_group_with_dispatch": round(
                cap0["bytes_per_group_with_dispatch"], 1
            ),
            "dispatch_bytes": cap0["dispatch_bytes"],
            "model_error_pct": cap0["model_error_pct"],
            "model_error_max_abs_pct": round(max(errors), 4),
            "max_groups_at_16gib": ref["max_groups"],
            "capacity_model_ok": max(errors) < 10.0,
        }

        # program registry on host 0's engine: the whole warm set with
        # non-zero cost/memory analysis per program (compiles ride the
        # jit/persistent caches where warm)
        rows = dp0.collect_programs(include_kv=False)
        assert rows and all(
            r.get("flops", 0) > 0 and r.get("bytes_accessed", 0) > 0
            for r in rows
        ), rows
        out["programs"] = rows
        out["programs_ok"] = True

        # estimator evidence from the on-windows (plus this phase) —
        # counters summed AND the device-ms sample windows MERGED before
        # the percentiles, so the ledger row's percentiles describe the
        # same population as its sample counts (host-0-only percentiles
        # against cluster-wide counts would misattribute)
        est = dp0.estimator_stats()
        merged_ms = list(dp0._device_ms)
        for nh in nhs[1:]:
            e2 = nh._devprof_axis.estimator_stats()
            est["dispatches"] += e2["dispatches"]
            est["sampled"] += e2["sampled"]
            est["padded_rounds"] += e2["padded_rounds"]
            est["wasted_rounds"] += e2["wasted_rounds"]
            merged_ms.extend(nh._devprof_axis._device_ms)
        est["padding_waste_ratio"] = (
            round(est["wasted_rounds"] / est["padded_rounds"], 4)
            if est["padded_rounds"] else 0.0
        )
        if merged_ms:
            from dragonboat_tpu.obs.health import _pctile

            est["device_ms"] = {
                "n": len(merged_ms),
                "p50": round(_pctile(merged_ms, 50), 4),
                "p99": round(_pctile(merged_ms, 99), 4),
                "max": round(max(merged_ms), 4),
            }
        out["estimator"] = est
        out["fused_ready"] = all(
            nh.quorum_coordinator.eng.fused_ready for nh in nhs
        )
        return out
    finally:
        for nh in nhs:
            try:
                nh.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


# ======================================================================
# cross-domain lease axis (ISSUE 10): leader-lease local reads vs the
# ReadIndex fallback across injected high-RTT domains
# ======================================================================


def _mk_xdom_hosts(rtt_ms, far_one_way_s, trace=0):
    from dragonboat_tpu import NodeHostConfig
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.monkey import set_latency
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport import ChanRouter, ChanTransport
    from dragonboat_tpu.transport.latency import crossdomain

    router = ChanRouter()
    nhs = []
    for i in (1, 2, 3):
        nhs.append(
            NodeHost(
                NodeHostConfig(
                    node_host_dir=":memory:",
                    rtt_millisecond=rtt_ms,
                    raft_address=f"xd{i}:1",
                    raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                        src, rh, ch, router=router
                    ),
                    trace_sample_every=trace,
                    expert=ExpertConfig(
                        quorum_engine="scalar", logdb_shards=2
                    ),
                )
            )
        )
    # host 1 is the near/leader domain; the QUORUM (hosts 2+3) sits one
    # far link away — every ReadIndex confirmation and every commit pays
    # the cross-domain RTT, while lease reads stay in the near domain
    set_latency(
        nhs, crossdomain(["xd1:1"], ["xd2:1", "xd3:1"], far_one_way_s)
    )
    return nhs


def _xdom_place_leaders(nhs, cids):
    """Deterministic placement: the NEAR host (rank 1) leads every
    group.  The first campaign can race the bootstrap config-change
    apply (campaign_skipped) or lose to a randomized timeout on a far
    host — retry, transferring back when a far host won."""
    deadline = time.time() + 120
    led = set()
    while len(led) < len(cids) and time.time() < deadline:
        for cid in cids:
            if cid in led:
                continue
            n1 = nhs[0].get_node(cid)
            if n1.is_leader():
                led.add(cid)
                continue
            lid, ok = n1.get_leader_id()
            if ok and lid != 1 and 1 <= lid <= 3:
                try:
                    nhs[lid - 1].request_leader_transfer(cid, 1)
                except Exception:
                    pass
            else:
                n1.request_campaign()
        time.sleep(0.2)
    assert len(led) == len(cids), (
        f"near-domain leaders: {len(led)}/{len(cids)}"
    )


def run_crossdomain() -> dict:
    """Cross-domain lease rung (ISSUE 10; ROADMAP item 4 seed): a 3-host
    group whose follower quorum lives one injected far link (default
    40ms RTT) from the leader, under a 9:1 mixed read/write load.

    Two variants on identical topology: ``read_lease=True`` (clock-bound
    leader lease, reads served locally — dragonboat_tpu/lease.py) vs
    ``read_lease=False`` (every read pays the heartbeat-echo round across
    the far link).  Asserted: the lease variant's read p99 is single-digit
    milliseconds (vs the r07 device mixed-phase read-dispatch p99 of
    1.08s, and vs this rung's own ReadIndex fallback at ≥ the domain
    RTT), with a ≥90% lease hit ratio and write throughput unchanged
    within the box's noise band.

    Env knobs: E2E_XDOM_GROUPS (8), E2E_XDOM_DURATION (8s),
    E2E_XDOM_RTT_MS (20 tick), E2E_XDOM_FAR_MS (20 one-way),
    E2E_XDOM_THREADS (4), E2E_XDOM_ASSERT_MS (10).
    """
    groups = int(os.environ.get("E2E_XDOM_GROUPS", "8"))
    duration = float(os.environ.get("E2E_XDOM_DURATION", "8"))
    rtt_ms = int(os.environ.get("E2E_XDOM_RTT_MS", "20"))
    far_ms = float(os.environ.get("E2E_XDOM_FAR_MS", "20"))
    threads = int(os.environ.get("E2E_XDOM_THREADS", "4"))
    assert_ms = float(os.environ.get("E2E_XDOM_ASSERT_MS", "10"))
    payload = _payload()
    from dragonboat_tpu import Config

    out = {
        "groups": groups,
        "rtt_ms": rtt_ms,
        "far_one_way_ms": far_ms,
        "duration_s": duration,
        "topology": "leader near; 2-follower quorum one far link away",
        "variants": {},
    }
    for lease in (True, False):
        nhs = _mk_xdom_hosts(rtt_ms, far_ms / 1e3)
        try:
            addrs = {i: f"xd{i}:1" for i in (1, 2, 3)}
            cids = [BASE_CID + g for g in range(groups)]
            for cid in cids:
                for i, nh in enumerate(nhs, start=1):
                    nh.start_cluster(
                        addrs, False, CounterSM,
                        Config(
                            cluster_id=cid, node_id=i, election_rtt=10,
                            heartbeat_rtt=1, check_quorum=True,
                            read_lease=lease,
                        ),
                    )
            _xdom_place_leaders(nhs, cids)
            leaders = {cid: nhs[0] for cid in cids}
            # warm: one committed write per group (thesis §6.4 step 1 —
            # the lease serves only past a current-term commit) and a few
            # heartbeat round trips so quorum acks arm the lease
            for cid in cids:
                nhs[0].sync_propose(
                    nhs[0].get_noop_session(cid), payload, timeout=30.0
                )
            time.sleep(1.0)
            mixed = _measure_mixed(
                leaders, cids, payload, 9, time.time() + duration, threads
            )
            stats = None
            if lease:
                agg = {"reads_local": 0, "reads_fallback": 0, "grants": 0,
                       "expiries": 0}
                for cid in cids:
                    s = nhs[0].lease_status(cid) or {}
                    for k in agg:
                        agg[k] += s.get(k, 0)
                total = agg["reads_local"] + agg["reads_fallback"]
                agg["hit_ratio"] = (
                    round(agg["reads_local"] / total, 4) if total else None
                )
                stats = agg
            out["variants"]["lease_on" if lease else "lease_off"] = {
                **{k: v for k, v in mixed.items()},
                "lease": stats,
            }
        finally:
            for nh in nhs:
                try:
                    nh.stop()
                except Exception:
                    pass
    on = out["variants"]["lease_on"]
    off = out["variants"]["lease_off"]
    p99_on = (on.get("read_latency_ms") or {}).get("p99")
    p99_off = (off.get("read_latency_ms") or {}).get("p99")
    out["read_p99_ms_lease"] = p99_on
    out["read_p99_ms_fallback"] = p99_off
    out["read_p99_speedup"] = (
        round(p99_off / p99_on, 1) if p99_on and p99_off else None
    )
    wps_ratio = (
        on["ops_per_sec"] / off["ops_per_sec"] if off["ops_per_sec"] else None
    )
    out["ops_ratio_on_off"] = round(wps_ratio, 3) if wps_ratio else None
    # acceptance: lease reads are single-digit ms; the fallback pays at
    # least the far-domain RTT; throughput within the box's noise band
    hit = (on.get("lease") or {}).get("hit_ratio") or 0.0
    assert p99_on is not None and p99_on < assert_ms, (
        f"lease read p99 {p99_on}ms not single-digit (limit {assert_ms}ms)"
    )
    assert p99_off is not None and p99_off >= 2 * far_ms, (
        f"fallback read p99 {p99_off}ms below the {2 * far_ms}ms domain RTT "
        "— the injected topology is not being exercised"
    )
    assert hit >= 0.9, f"lease hit ratio {hit} < 0.9"
    assert wps_ratio is None or 0.5 <= wps_ratio <= 2.0, (
        f"mixed throughput moved {wps_ratio}x between lease on/off"
    )
    # commit attribution (ISSUE 14): READS got their cross-domain story
    # above; this phase prices what COMMITS still pay — per-peer quorum
    # attribution on the identical topology, trace on/off paired
    out["commit_attribution"] = _xdom_commit_attribution(
        groups, rtt_ms, far_ms, duration, threads, payload
    )
    out["assert_ok"] = True
    return out


def _xdom_commit_attribution(groups, rtt_ms, far_ms, duration, threads,
                             payload) -> dict:
    """Commit-attribution phase of the cross-domain rung (ISSUE 14
    tentpole): same 3-host topology (near leader, 2-follower quorum one
    far link away), pure-write load, the replication attribution plane
    (obs/replattr.py) decomposing every sampled commit's quorum close
    per peer.  Asserted: the far-domain peers are the ONLY laggards and
    closers (by latency class, not bare node id), the quorum close pays
    the far round trip, the closing path's stage share is wire-dominated
    (the number ROADMAP item 4's domain-local sub-quorum attacks), and
    the paired trace-on/off overhead stays under 5% + 2·SEM (the r10
    trace-axis pairing discipline) with the off half structurally
    detached down to the raft hooks.

    Env knobs: E2E_XDOM_TRACE_SAMPLE (1-in-4), E2E_XDOM_TRACE_PAIRS (4
    windows), E2E_XDOM_TRACE_WINDOW (duration/2 s).
    """
    from dragonboat_tpu import Config

    sample = int(os.environ.get("E2E_XDOM_TRACE_SAMPLE", "4"))
    pairs = max(2, int(os.environ.get("E2E_XDOM_TRACE_PAIRS", "4")) // 2 * 2)
    win = (
        float(os.environ.get("E2E_XDOM_TRACE_WINDOW", "0"))
        or max(2.0, duration / 2)
    )
    nhs = _mk_xdom_hosts(rtt_ms, far_ms / 1e3, trace=sample)
    try:
        for nh in nhs:
            # handles for the A/B detach/reattach (_set_tracing)
            nh._trace_axis_tracer = nh.tracer
            nh._trace_axis_replattr = nh.replattr
        addrs = {i: f"xd{i}:1" for i in (1, 2, 3)}
        cids = [BASE_CID + g for g in range(groups)]
        for cid in cids:
            for i, nh in enumerate(nhs, start=1):
                nh.start_cluster(
                    addrs, False, CounterSM,
                    Config(cluster_id=cid, node_id=i, election_rtt=10,
                           heartbeat_rtt=1, check_quorum=True),
                )
        _xdom_place_leaders(nhs, cids)
        leaders = {cid: nhs[0] for cid in cids}
        for cid in cids:
            nhs[0].sync_propose(
                nhs[0].get_noop_session(cid), payload, timeout=30.0
            )

        def measure(on):
            _set_tracing(nhs, on)
            if not on:
                # trace-off structural identity on the live cluster:
                # nothing below the latch may survive the detach
                n = nhs[0].get_node(cids[0])
                assert n.replattr is None
                assert n.peer.raft.replattr is None
            m = _measure_mixed(
                leaders, cids, payload, 0, time.time() + win, threads
            )
            return m["ops_per_sec"]

        measure(False)  # warmup window
        deltas = []
        wps_on = wps_off = 0.0
        for pair in range(pairs):
            if pair % 2 == 0:
                on = measure(True)
                off = measure(False)
            else:
                off = measure(False)
                on = measure(True)
            wps_on = max(wps_on, on)
            wps_off = max(wps_off, off)
            deltas.append((off - on) / off * 100.0)
        mean = sum(deltas) / len(deltas)
        var = sum((d - mean) ** 2 for d in deltas) / max(1, len(deltas) - 1)
        sem = (var / len(deltas)) ** 0.5
        overhead = round(mean, 2)
        # dedicated attribution window, then let straggler (laggard)
        # acks land so their RTTs make the table
        _set_tracing(nhs, True)
        _measure_mixed(leaders, cids, payload, 0, time.time() + win, threads)
        time.sleep(max(1.0, 4 * far_ms / 1e3))
        summ = nhs[0].replattr.summary()
        inj = nhs[0].transport.latency
        out = {
            "sample_every": sample,
            "window_s": win,
            "writes_per_sec_trace_on": round(wps_on, 1),
            "writes_per_sec_trace_off": round(wps_off, 1),
            "trace_overhead_pct": overhead,
            "trace_overhead_sem_pct": round(sem, 2),
            "pair_deltas_pct": [round(d, 2) for d in deltas],
            "trace_overhead_ok": overhead < 5.0 + 2 * sem,
            "summary": summ,
            "latency_domains": (
                inj.health_snapshot() if inj is not None else None
            ),
        }
        # every quorum member besides the leader is far-class: each
        # sampled commit must close on a far ack AND laggard the other
        # far peer — per-peer attribution by latency class
        peers = summ["peers"]
        assert peers and all(d["cls"] == "B" for d in peers.values()), (
            f"far quorum not labeled by latency class: {peers}"
        )
        laggard_total = sum(d["laggard"] for d in peers.values())
        closer_total = sum(d["closer"] for d in peers.values())
        assert closer_total > 0 and laggard_total > 0, (
            f"attribution empty: closers {closer_total}, "
            f"laggards {laggard_total} "
            f"({summ['commits_attributed']} commits)"
        )
        # the quorum close pays the far round trip (lower bounds NOT
        # load-scaled; pipelined sends coalesce onto shared far round
        # trips, so p50 can undershoot the full RTT a little — p99 sees
        # the uncoalesced close)
        assert summ["close_ms"]["p99"] >= 2 * far_ms * 0.9, (
            f"close p99 {summ['close_ms']} below the {2 * far_ms}ms "
            "domain RTT — attribution is not seeing the far quorum"
        )
        assert summ["close_ms"]["p50"] >= far_ms, (
            f"close p50 {summ['close_ms']} below the {far_ms}ms far "
            "one-way leg"
        )
        shares = summ["close_stage_share_pct"]
        wire = shares.get("wire_out", 0.0) + shares.get("wire_back", 0.0)
        out["wire_share_pct"] = round(wire, 1)
        assert wire >= 50.0, (
            f"closing path not wire-dominated: {shares}"
        )
        assert overhead < 5.0 + 2 * sem, (
            f"repl-trace overhead too high: {overhead}% "
            f"(± {sem:.1f} SEM; {wps_on:.0f} vs {wps_off:.0f} w/s)"
        )
        out["attribution_ok"] = True
        return out
    finally:
        for nh in nhs:
            try:
                nh.stop()
            except Exception:
                pass


# ======================================================================
# hierarchical commit rung (hier, ISSUE 18)
# ======================================================================


def _mk_hier_hosts(rtt_ms, far_one_way_s, trace=0):
    """Four hosts in a 2+2 domain split: hd1+hd2 near (domain A), hd3+hd4
    one far link away (domain B).  With n=4 voters the classic quorum is
    3, so every classic commit must wait on a far ack — the topology the
    domain-local sub-quorum (raft/hier.py) is built to beat."""
    from dragonboat_tpu import NodeHostConfig
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.monkey import set_latency
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport import ChanRouter, ChanTransport
    from dragonboat_tpu.transport.latency import crossdomain

    router = ChanRouter()
    nhs = []
    for i in (1, 2, 3, 4):
        nhs.append(
            NodeHost(
                NodeHostConfig(
                    node_host_dir=":memory:",
                    rtt_millisecond=rtt_ms,
                    raft_address=f"hd{i}:1",
                    raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                        src, rh, ch, router=router
                    ),
                    trace_sample_every=trace,
                    expert=ExpertConfig(
                        quorum_engine="scalar", logdb_shards=2
                    ),
                )
            )
        )
    set_latency(
        nhs,
        crossdomain(
            ["hd1:1", "hd2:1"], ["hd3:1", "hd4:1"], far_one_way_s
        ),
    )
    return nhs


def _hier_place_leaders(nhs, cids):
    """_xdom_place_leaders for the 4-host topology: host 1 (near domain)
    leads every group."""
    deadline = time.time() + 120
    led = set()
    while len(led) < len(cids) and time.time() < deadline:
        for cid in cids:
            if cid in led:
                continue
            n1 = nhs[0].get_node(cid)
            if n1.is_leader():
                led.add(cid)
                continue
            lid, ok = n1.get_leader_id()
            if ok and lid != 1 and 1 <= lid <= len(nhs):
                try:
                    nhs[lid - 1].request_leader_transfer(cid, 1)
                except Exception:
                    pass
            else:
                n1.request_campaign()
        time.sleep(0.2)
    assert len(led) == len(cids), (
        f"near-domain leaders: {len(led)}/{len(cids)}"
    )


def _closer_by_class(summ) -> dict:
    """Collapse the per-peer attribution table to closer counts per
    latency class — the number the hier rung's flip assertion reads."""
    agg: dict = {}
    for d in summ["peers"].values():
        agg[d["cls"]] = agg.get(d["cls"], 0) + d["closer"]
    return agg


def _hier_far_read_phase(nhs, cids, threads=4, reads_per_thread=25) -> dict:
    """Far-domain read path (ISSUE 18 tentpole, part 4): concurrent
    linearizable reads issued FROM a far-domain host (hd3) while the
    leader sits in the near domain.  Without batching each read pays its
    own cross-domain leader round trip; the FarReadBatcher coalesces
    mid-flight arrivals onto the in-flight confirmation."""
    far = nhs[2]  # hd3, domain B
    cid = cids[0]
    errors = [0]

    def worker():
        for _ in range(reads_per_thread):
            try:
                far.sync_read(cid, None, timeout=30.0)
            except Exception:
                errors[0] += 1

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    elapsed = time.perf_counter() - t0
    fr = far.get_node(cid).peer.raft.far_reads
    total = threads * reads_per_thread
    return {
        "reads": total,
        "errors": errors[0],
        "elapsed_s": round(elapsed, 3),
        "reads_per_sec": round(total / elapsed, 1) if elapsed else None,
        "leader_round_trips": fr.batches,
        "reads_coalesced": fr.coalesced,
        "coalesce_ratio": (
            round(fr.coalesced / total, 3) if total else None
        ),
    }


def run_hier() -> dict:
    """Hierarchical commit rung (ISSUE 18 tentpole): a 4-host group in a
    2+2 domain split (near leader + one near follower; two followers one
    far link away).  n=4 voters makes the classic quorum 3, so WITHOUT
    hier every commit close pays the far round trip; WITH
    ``hier_commit=True`` the near-domain sub-quorum (2 of {hd1,hd2})
    closes at the near RTT and the far acks catch up asynchronously.

    Two variants on identical topology and identical pure-write load,
    both with replication attribution sampling on (the trace overhead
    cancels in the A/B).  Asserted: the closer table flips far→near (off:
    every sampled close is a far-class ack; on: near-class closers
    dominate), commit close p99 drops from ≥ the far RTT to ≤ 0.5× the
    far RTT, write throughput does not regress beyond noise, the
    sub-quorum counters confirm the near rule (not a lucky topology) did
    the closing, and the far-domain read phase coalesces concurrent
    follower reads onto shared leader round trips.

    Env knobs: E2E_HIER_GROUPS (8), E2E_HIER_DURATION (8s),
    E2E_HIER_RTT_MS (20 tick), E2E_HIER_FAR_MS (20 one-way),
    E2E_HIER_THREADS (4), E2E_HIER_TRACE_SAMPLE (1-in-4).
    """
    groups = int(os.environ.get("E2E_HIER_GROUPS", "8"))
    duration = float(os.environ.get("E2E_HIER_DURATION", "8"))
    rtt_ms = int(os.environ.get("E2E_HIER_RTT_MS", "20"))
    far_ms = float(os.environ.get("E2E_HIER_FAR_MS", "20"))
    threads = int(os.environ.get("E2E_HIER_THREADS", "4"))
    sample = int(os.environ.get("E2E_HIER_TRACE_SAMPLE", "4"))
    payload = _payload()
    from dragonboat_tpu import Config

    doms = {1: "A", 2: "A", 3: "B", 4: "B"}
    far_rtt_ms = 2 * far_ms
    out = {
        "groups": groups,
        "rtt_ms": rtt_ms,
        "far_one_way_ms": far_ms,
        "duration_s": duration,
        "sample_every": sample,
        "domains": {str(k): v for k, v in doms.items()},
        "topology": (
            "2+2 split: leader + 1 near follower; 2-follower far "
            "domain; classic quorum (3/4) must cross the far link"
        ),
        "variants": {},
    }
    for hier in (False, True):
        nhs = _mk_hier_hosts(rtt_ms, far_ms / 1e3, trace=sample)
        try:
            addrs = {i: f"hd{i}:1" for i in (1, 2, 3, 4)}
            cids = [BASE_CID + g for g in range(groups)]
            for cid in cids:
                for i, nh in enumerate(nhs, start=1):
                    nh.start_cluster(
                        addrs, False, CounterSM,
                        Config(
                            cluster_id=cid, node_id=i, election_rtt=10,
                            heartbeat_rtt=1, check_quorum=True,
                            hier_commit=hier,
                            hier_domains=dict(doms) if hier else {},
                        ),
                    )
            _hier_place_leaders(nhs, cids)
            leaders = {cid: nhs[0] for cid in cids}
            for cid in cids:
                nhs[0].sync_propose(
                    nhs[0].get_noop_session(cid), payload, timeout=30.0
                )
            time.sleep(0.5)
            mixed = _measure_mixed(
                leaders, cids, payload, 0, time.time() + duration, threads
            )
            # let straggler far acks land so their RTTs make the table
            time.sleep(max(1.0, 4 * far_ms / 1e3))
            summ = nhs[0].replattr.summary()
            hsnap = None
            far_read = None
            if hier:
                hsnap = {
                    "subquorum_closes": 0, "fallback_closes": 0,
                    "election_holds": 0,
                }
                for cid in cids:
                    s = nhs[0].get_node(cid).peer.raft.hier.snapshot()
                    for k in hsnap:
                        hsnap[k] += s[k]
                far_read = _hier_far_read_phase(nhs, cids)
            out["variants"]["hier_on" if hier else "hier_off"] = {
                **{k: v for k, v in mixed.items()},
                "close_ms": summ["close_ms"],
                "closer_by_class": _closer_by_class(summ),
                "peers": summ["peers"],
                "commits_attributed": summ["commits_attributed"],
                "hier": hsnap,
                "far_read": far_read,
            }
        finally:
            for nh in nhs:
                try:
                    nh.stop()
                except Exception:
                    pass
    on = out["variants"]["hier_on"]
    off = out["variants"]["hier_off"]
    p99_on = on["close_ms"]["p99"]
    p99_off = off["close_ms"]["p99"]
    out["close_p99_ms_hier"] = p99_on
    out["close_p99_ms_classic"] = p99_off
    out["close_p99_speedup"] = (
        round(p99_off / p99_on, 1) if p99_on and p99_off else None
    )
    wps_ratio = (
        on["ops_per_sec"] / off["ops_per_sec"] if off["ops_per_sec"] else None
    )
    out["ops_ratio_on_off"] = round(wps_ratio, 3) if wps_ratio else None
    # acceptance (ISSUE 18): the closer table flips far→near ...
    cls_off = off["closer_by_class"]
    cls_on = on["closer_by_class"]
    assert cls_off.get("B", 0) > 0 and cls_off.get("A", 0) == 0, (
        f"classic closers not all far-class: {cls_off} — the 2+2 "
        "topology is not forcing the far ack"
    )
    assert cls_on.get("A", 0) > cls_on.get("B", 0), (
        f"hier closers did not flip to the near class: {cls_on}"
    )
    # ... commit close p99 drops below half the far RTT (vs >= it off) ...
    assert p99_off is not None and p99_off >= far_rtt_ms * 0.9, (
        f"classic close p99 {p99_off}ms below the {far_rtt_ms}ms far "
        "RTT — the injected topology is not being exercised"
    )
    assert p99_on is not None and p99_on <= 0.5 * far_rtt_ms, (
        f"hier close p99 {p99_on}ms not under half the {far_rtt_ms}ms "
        "far RTT"
    )
    # ... the sub-quorum did the closing ...
    assert on["hier"]["subquorum_closes"] > 0, (
        f"no sub-quorum closes recorded: {on['hier']}"
    )
    # ... throughput within noise (the sub-quorum path should only help:
    # sync_propose unblocks at the near close) ...
    assert wps_ratio is None or wps_ratio >= 0.8, (
        f"hier-on write throughput regressed {wps_ratio}x"
    )
    # ... and far-domain reads coalesce onto shared leader round trips
    fr = on["far_read"]
    assert fr["errors"] == 0, f"far-domain reads failed: {fr}"
    assert fr["reads_coalesced"] > 0, (
        f"far reads never coalesced: {fr}"
    )
    assert fr["leader_round_trips"] < fr["reads"], (
        f"every far read paid its own leader round trip: {fr}"
    )
    out["assert_ok"] = True
    return out


# ======================================================================
# device state machine rung (devsm, ISSUE 11)
# ======================================================================


def _devsm_mixed_worker(nh, cids, read_ratio, stop_at, out):
    """9:1 mixed KV load through the sync APIs: writes are fixed-width
    devsm SET ops, reads are linearizable key lookups with the value
    CHECKED against the last committed write per key (a stale device
    read fails the rung, not just slows it)."""
    from dragonboat_tpu.devsm import encode_op

    reads = writes = errors = 0
    lat_r, lat_w = [], []
    stale = None
    last = {}  # (cid, key) -> last written value
    sessions = {cid: nh.get_noop_session(cid) for cid in cids}
    i = 0
    while time.time() < stop_at and stale is None:
        cid = cids[i % len(cids)]
        key = (i // len(cids)) % 8
        i += 1
        is_read = (i % (read_ratio + 1)) != 0
        t0 = time.perf_counter()
        try:
            if is_read:
                v = nh.sync_read(cid, key, timeout=10.0)
                lat_r.append(time.perf_counter() - t0)
                reads += 1
                expect = last.get((cid, key))
                if expect is not None and v != expect:
                    # recorded, not raised: an exception on this bare
                    # thread would die silently and the rung would
                    # report assert_ok over a linearizability violation
                    stale = f"stale devsm read {cid}/{key}: {v} != {expect}"
            else:
                val = i & 0x7FFFFFFF
                nh.sync_propose(
                    sessions[cid], encode_op(key, val), timeout=10.0
                )
                lat_w.append(time.perf_counter() - t0)
                writes += 1
                last[(cid, key)] = val
        except Exception:
            errors += 1
    out.append((reads, writes, errors, lat_r, lat_w, stale))


def run_devsm() -> dict:
    """Device SM rung (ISSUE 11): a 3-host tpu-engine cluster under a
    9:1 mixed KV load, ``Config.device_kv`` on vs off on identical
    topology (same DeviceKVStateMachine class both ways — the off
    variant IS the host-apply oracle).  Leaders concentrate on host 1 so
    every client read hits the leader host, where the devsm variant
    serves straight from device state (zero host apply on the read
    path).  Reported per variant: mixed ops/s, read/write latency
    percentiles, and the sampled per-stage trace attribution — the
    acceptance signal is the READ path's ``apply`` share collapsing on
    the devsm variant (reads release at the device commit watermark, the
    fold having run inside that very dispatch).

    Env knobs: E2E_DEVSM_GROUPS (4), E2E_DEVSM_DURATION (8s),
    E2E_DEVSM_RTT_MS (20), E2E_DEVSM_THREADS (2),
    E2E_DEVSM_WARM_TIMEOUT (240s).
    """
    from dragonboat_tpu import Config, NodeHostConfig
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.devsm import DeviceKVStateMachine
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.obs.trace import compute_stage_stats
    from dragonboat_tpu.transport import ChanRouter, ChanTransport

    groups = int(os.environ.get("E2E_DEVSM_GROUPS", "4"))
    duration = float(os.environ.get("E2E_DEVSM_DURATION", "8"))
    rtt_ms = int(os.environ.get("E2E_DEVSM_RTT_MS", "20"))
    threads = int(os.environ.get("E2E_DEVSM_THREADS", "2"))
    warm_timeout = float(os.environ.get("E2E_DEVSM_WARM_TIMEOUT", "240"))
    out = {
        "groups": groups,
        "duration_s": duration,
        "rtt_ms": rtt_ms,
        "read_ratio": 9,
        "variants": {},
    }
    for devsm in (True, False):
        router = ChanRouter()
        addrs = {i: f"dsm{i}:1" for i in (1, 2, 3)}
        nhs = [
            NodeHost(
                NodeHostConfig(
                    node_host_dir=":memory:",
                    rtt_millisecond=rtt_ms,
                    raft_address=addrs[i],
                    raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                        src, rh, ch, router=router
                    ),
                    trace_sample_every=2,
                    expert=ExpertConfig(
                        quorum_engine="tpu",
                        engine_block_groups=max(groups, 64),
                    ),
                )
            )
            for i in (1, 2, 3)
        ]
        try:
            cids = [BASE_CID + g for g in range(groups)]
            for cid in cids:
                for i, nh in enumerate(nhs, start=1):
                    nh.start_cluster(
                        addrs, False, DeviceKVStateMachine,
                        Config(
                            cluster_id=cid, node_id=i, election_rtt=10,
                            heartbeat_rtt=1, device_kv=devsm,
                        ),
                    )
            if devsm:
                # first-use XLA compiles of the has_kv programs must not
                # stall the round thread mid-measurement (warmup_devsm is
                # kicked at registration; wait it out)
                deadline = time.time() + warm_timeout
                while time.time() < deadline:
                    if all(
                        nh.quorum_coordinator.eng.kv_fused_ready
                        for nh in nhs
                    ):
                        break
                    time.sleep(0.25)
            # concentrate leaders on host 1 (the crossdomain placement
            # dance): device-served reads require the client to read on
            # the leader host
            deadline = time.time() + 120
            led = set()
            while len(led) < len(cids) and time.time() < deadline:
                for cid in cids:
                    if cid in led:
                        continue
                    n1 = nhs[0].get_node(cid)
                    if n1.is_leader():
                        led.add(cid)
                        continue
                    lid, ok = n1.get_leader_id()
                    if ok and lid != 1 and 1 <= lid <= 3:
                        try:
                            nhs[lid - 1].request_leader_transfer(cid, 1)
                        except Exception:
                            pass
                    else:
                        n1.request_campaign()
                time.sleep(0.2)
            assert len(led) == len(cids), (
                f"host-1 leaders: {len(led)}/{len(cids)}"
            )
            if devsm:
                plane = nhs[0].quorum_coordinator.devsm
                deadline = time.time() + 60
                while time.time() < deadline and not all(
                    plane.bound(cid) for cid in cids
                ):
                    time.sleep(0.1)
            time.sleep(0.5)  # settle startup config-change resyncs
            stop_at = time.time() + duration
            outs = []
            slices = [cids[i::threads] for i in range(threads)]
            ts = [
                threading.Thread(
                    target=_devsm_mixed_worker,
                    args=(nhs[0], s, 9, stop_at, outs),
                )
                for s in slices
                if s
            ]
            t_begin = time.time()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = max(time.time() - t_begin, 1e-3)
            # every worker must have reported, and none may have seen a
            # stale read (the worker records instead of raising — a
            # thread death would silently shrink the stats)
            assert len(outs) == len([s for s in slices if s]), (
                f"devsm worker died: {len(outs)} reports"
            )
            stales = [s for *_rest, s in outs if s]
            assert not stales, stales[0]
            reads = sum(r for r, _, _, _, _, _ in outs)
            writes = sum(w for _, w, _, _, _, _ in outs)
            errors = sum(e for _, _, e, _, _, _ in outs)
            lat_r = [l for _, _, _, ls, _, _ in outs for l in ls]
            lat_w = [l for _, _, _, _, ls, _ in outs for l in ls]
            attribution = compute_stage_stats(
                t for nh in nhs if nh.tracer is not None
                for t in nh.tracer.traces()
            )
            variant = {
                "ops_per_sec": round((reads + writes) / wall, 1),
                "reads": reads,
                "writes": writes,
                "errors": errors,
                "read_latency_ms": _percentiles(lat_r),
                "write_latency_ms": _percentiles(lat_w),
                "attribution": attribution,
            }
            if devsm:
                plane = nhs[0].quorum_coordinator.devsm
                served = plane.reads_served
                fb = plane.read_fallbacks
                variant["devsm"] = {
                    "reads_served": served,
                    "read_fallbacks": fb,
                    "ops_staged": plane.ops_staged,
                    "binds": plane.binds,
                    "served_ratio": (
                        round(served / (served + fb), 4)
                        if served + fb else None
                    ),
                }
            out["variants"]["devsm_on" if devsm else "devsm_off"] = variant
        finally:
            for nh in nhs:
                try:
                    nh.stop()
                except Exception:
                    pass
    on = out["variants"]["devsm_on"]
    off = out["variants"]["devsm_off"]

    def _apply_share(v):
        st = (v.get("attribution") or {}).get("stages") or {}
        return (st.get("apply") or {}).get("share_pct")

    out["apply_share_pct_devsm"] = _apply_share(on)
    out["apply_share_pct_host"] = _apply_share(off)
    out["read_p50_ms_devsm"] = (on.get("read_latency_ms") or {}).get("p50")
    out["read_p50_ms_host"] = (off.get("read_latency_ms") or {}).get("p50")
    # acceptance: the device plane (not the shadow fallback) served the
    # read load, correctness held (the worker asserts read-your-writes
    # inline), and the apply share collapsed on the devsm path
    served_ratio = (on.get("devsm") or {}).get("served_ratio") or 0.0
    assert served_ratio >= 0.5, (
        f"device served only {served_ratio} of leader-host reads"
    )
    assert on["errors"] == 0 or on["errors"] < on["reads"] // 10
    a_on, a_off = out["apply_share_pct_devsm"], out["apply_share_pct_host"]
    if a_on is not None and a_off is not None and a_off > 1.0:
        assert a_on <= max(5.0, 0.5 * a_off), (
            f"devsm apply share {a_on}% did not collapse vs host {a_off}%"
        )
    out["assert_ok"] = True
    return out


# ======================================================================
# multiprocess mode: one process per NodeHost over framed TCP
# ======================================================================


def _rank_env_int(name, default):
    return int(os.environ.get(name, str(default)))


def rank_main() -> int:
    """Child body: one NodeHost + this rank's share of the load threads.

    Line protocol on stdio (parent drives):
      child → parent:  READY {...}   then   RESULT {...}
      parent → child:  RUN {"t0":…, "duration":…, "lat_t0":…,
                            "lat_duration":…, "lat_cids":[…]}
    """
    rank = _rank_env_int("E2E_RANK", 0)
    # GIL switch interval is tunable for experiments; the default (5ms)
    # measured best — shorter intervals add context-switch overhead
    # without improving the pipeline's wakeup latency
    si = os.environ.get("E2E_SWITCH_INTERVAL")
    if si:
        sys.setswitchinterval(float(si))
    if os.environ.get("DBTPU_CPROFILE_STEP_DIR"):
        os.environ["DBTPU_CPROFILE_STEP"] = os.path.join(
            os.environ["DBTPU_CPROFILE_STEP_DIR"], f"step_rank{rank}.prof"
        )
    procs = _rank_env_int("E2E_PROCS", 3)
    groups = _rank_env_int("E2E_GROUPS", 1024)
    rtt_ms = _rank_env_int("E2E_RTT_MS", 500)
    window = _rank_env_int("E2E_WINDOW", 16)
    threads = _rank_env_int("E2E_THREADS", 8)
    durable = os.environ.get("E2E_DURABLE", "1") == "1"
    engine = os.environ.get("E2E_ENGINE", "tpu")
    leader_mode = os.environ.get("E2E_LEADER_MODE", "spread")
    leader_timeout = float(os.environ.get("E2E_LEADER_TIMEOUT", "120"))
    ports = [int(p) for p in os.environ["E2E_PORTS"].split(",")]
    base_dir = os.environ.get("E2E_DIR", "")

    # engine per rank: the device engine lives where the leaders it serves
    # live; with one TPU chip only rank 0 attaches to it (leader_mode
    # "rank0" puts every leader there so ALL commit tallying runs through
    # the device).  Other ranks never import jax.  (An all-ranks-engined
    # spread variant was tried and thrashes elections: three device-ticked
    # replicas per group contend through three round pipelines.)
    my_engine = engine if (engine != "tpu" or rank == 0) else "scalar"
    if my_engine == "tpu":
        _decide_platform()

    from dragonboat_tpu import Config, NodeHostConfig
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.nodehost import NodeHost

    t_setup = time.perf_counter()
    addr = f"127.0.0.1:{ports[rank]}"
    from dragonboat_tpu.config import LogDBConfig

    ldb = LogDBConfig()
    ldb.fsync = os.environ.get("E2E_FSYNC", "1") == "1"
    # native replication fast lane (fastlane.py): the steady-state data
    # plane of enrolled groups runs in C++ — the host-path answer to the
    # ~75us-of-Python-per-write bound documented in PERF.md.  On by
    # default in this benchmark's deployment shape (TCP + durable native
    # LogDB); E2E_FAST_LANE=0 measures the pure-Python path.
    fast_lane = durable and os.environ.get("E2E_FAST_LANE", "1") == "1"
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=(
                os.path.join(base_dir, f"nh{rank}") if durable else ":memory:"
            ),
            rtt_millisecond=rtt_ms,
            raft_address=addr,
            logdb_config=ldb,
            expert=ExpertConfig(
                quorum_engine=my_engine,
                engine_block_groups=max(groups, 64),
                logdb_shards=int(os.environ.get("E2E_SHARDS", "4")),
                fast_lane=fast_lane,
                # 4ms: the round-4 sweep (0.5/2/4/6/8ms at rung 3, native
                # SM) found the best throughput/latency balance here —
                # w=4 gave 17.3k w/s at p50 10ms / p99 60ms vs 15k at
                # p99 90-120ms for the old 2ms (PERF.md)
                fast_lane_commit_window_ms=float(
                    os.environ.get("E2E_COMMIT_WINDOW_MS", "4.0")
                ),
                # compartmentalized host plane A/B axis (ISSUE 8);
                # default off — the scalar path is the baseline
                host_compartments=os.environ.get("E2E_COMPARTMENTS", "0")
                == "1",
            ),
        )
    )
    addrs = {i + 1: f"127.0.0.1:{ports[i]}" for i in range(procs)}
    # E2E_SM=native: the C-ABI KV state machine (natsm.py) — enrolled
    # groups then apply committed entries natively with only batched
    # completion records crossing the GIL (PERF.md ~40us/write apply rim)
    sm_factory = CounterSM
    if os.environ.get("E2E_SM", "python") == "native":
        from dragonboat_tpu.native.natsm import NativeKVStateMachine

        sm_factory = NativeKVStateMachine
    cids = [BASE_CID + g for g in range(groups)]

    election_rtt = int(os.environ.get("E2E_ELECTION_RTT", "20"))

    def _start_one(cid):
        nh.start_cluster(
            addrs,
            False,
            sm_factory,
            Config(
                cluster_id=cid,
                node_id=rank + 1,
                election_rtt=election_rtt,
                heartbeat_rtt=1,
                snapshot_entries=0,
            ),
        )

    # start_cluster is thread-safe (the id is reserved under the NodeHost
    # lock); at 4k+ groups the serial loop is the setup bottleneck (round
    # 4: 223s for 12,288 replicas) — the cost is IO/lock waits, which a
    # small pool overlaps
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(
        max_workers=int(os.environ.get("E2E_START_THREADS", "4"))
    ) as ex:
        for _ in ex.map(_start_one, cids):
            pass

    def preferred(cid):
        return 0 if leader_mode == "rank0" else cid % procs

    mine = [cid for cid in cids if preferred(cid) == rank]
    started_s = time.perf_counter() - t_setup

    platform = ""
    if my_engine == "tpu":
        try:
            import jax

            platform = jax.devices()[0].platform
        except Exception:
            platform = "unknown"

    def emit(tag, obj):
        sys.stdout.write(tag + " " + json.dumps(obj) + "\n")
        sys.stdout.flush()

    def expect(tag):
        line = sys.stdin.readline()
        if not line.startswith(tag + " ") and line.strip() != tag:
            raise RuntimeError(f"expected {tag}, got {line!r}")
        rest = line[len(tag) :].strip()
        return json.loads(rest) if rest else None

    # barrier 1: every rank has started all replicas before anyone
    # campaigns — campaigning into a peer that hasn't started the group
    # yet just drops the vote request and burns a retry cycle
    emit("STARTED", {"rank": rank, "started_s": round(started_s, 1)})
    expect("CAMPAIGN")

    t_campaign = time.perf_counter()
    deadline = time.time() + leader_timeout
    # staggered initial campaigns (round-4 election storm: 3,049/4,096
    # elected in 300s when every group campaigned at once — simultaneous
    # campaigns collide on the wire and their vote responses starve behind
    # each other's Replicate/noop traffic).  Keep at most `wave` unresolved
    # campaigns in flight; each resolved election frees a slot.
    #
    # A campaign is RESOLVED when the group has any leader — not
    # necessarily this rank's replica: under storm pressure another
    # replica's own randomized timeout can win the election first, and
    # re-campaigning against that healthy leader just deposes it (a term
    # war that stalled the round-4 tail indefinitely).  Whoever leads,
    # drives: the final scan below picks up every locally-led group,
    # preferred or adopted.
    wave = int(os.environ.get("E2E_CAMPAIGN_WAVE", "384"))
    # Explicit campaigns are only a bootstrap accelerant; the tail is
    # raft's own job.  Two measured anti-patterns shaped this: (1)
    # aggressive restarts bump terms and invalidate in-flight votes
    # (475/1,365 resolved at 171s); (2) a log-behind replica can NEVER
    # win (vote rejections, raft §5.4.1) and each of its campaigns resets
    # its peers' election clocks (term bump → become_follower → etick=0),
    # so retrying it forever starves the replica that could win (32
    # groups/rank wedged at term 40).  So: up to `attempts_max` spaced
    # campaigns per preferred group, then hands off to the replicas'
    # randomized election timeouts, with the resolution scan accepting a
    # leader wherever it emerges.
    attempts_max = int(os.environ.get("E2E_CAMPAIGN_ATTEMPTS", "3"))
    to_campaign = list(reversed(mine))
    inflight: dict = {}  # cid -> [last campaign wall time, attempts]
    resolved = 0
    next_retry = time.time() + 2.0
    next_report = time.time() + 5.0
    # wait until every LOCAL replica sees LIVE leadership — self-led, or
    # follower with leader known and a fresh election clock (a stale
    # leader_id with a growing clock means the leader died post-election;
    # its replicas will re-elect naturally and the scan keeps waiting)
    def _resolved(cid):
        r = nh.get_node(cid).peer.raft
        return r.leader_id != 0 and (
            r.is_leader() or r.election_tick < r.election_timeout
        )

    leaderless = set(cids)
    all_live = False
    next_scan = 0.0
    while not all_live and time.time() < deadline:
        now = time.time()
        for cid in list(leaderless):
            # raw raft read (GIL-atomic): Node.leader_id is the scalar
            # tick path's change cache and goes quiet once the group
            # enrolls in the fast lane
            if nh.get_node(cid).peer.raft.leader_id != 0:
                leaderless.discard(cid)
                inflight.pop(cid, None)
                if preferred(cid) == rank:
                    resolved += 1
        if not leaderless and now >= next_scan:
            all_live = all(_resolved(cid) for cid in cids)
            next_scan = now + 2.0
        while to_campaign and len(inflight) < wave:
            cid = to_campaign.pop()
            if cid not in leaderless:
                continue
            nh.get_node(cid).request_campaign()
            inflight[cid] = [now, 1]
        if now >= next_retry:
            for cid, slot in list(inflight.items()):
                t0, attempts = slot
                node = nh.get_node(cid)
                if attempts >= attempts_max or node.peer.raft.is_candidate():
                    continue
                if now - t0 >= 2.0:
                    node.request_campaign()
                    slot[0], slot[1] = now, attempts + 1
            next_retry = now + 2.0
        if time.time() >= next_report:
            # election progress to stderr so a slow run is diagnosable
            # from the driver capture
            print(
                f"rank{rank}: resolved {resolved}/{len(mine)} at "
                f"{time.perf_counter() - t_campaign:.1f}s",
                file=sys.stderr, flush=True,
            )
            next_report = time.time() + 5.0
        time.sleep(0.05)
    # unresolved-tail diagnostics: every replica of every leaderless
    # group, so the three rank logs together give the full picture
    for cid in cids:
        node = nh.get_node(cid)
        r = node.peer.raft
        if r.leader_id != 0:
            continue
        print(
            f"rank{rank}: STUCK cid={cid} state={r.state} term={r.term} "
            f"voted_for={r.vote} votes={dict(r.votes)} "
            f"etick={r.election_tick}/{r.randomized_election_timeout} "
            f"fastlane={node.fast_lane} "
            f"mq={len(node.mq._left) + len(node.mq._right)} "
            f"trace={list(r.vote_trace)}",
            file=sys.stderr, flush=True,
        )
    # drive every group THIS rank leads, preferred or adopted
    led = {cid for cid in cids if nh.get_node(cid).is_leader()}
    leaders = {cid: nh for cid in led}
    setup_s = time.perf_counter() - t_setup

    emit(
        "READY",
        {
            "rank": rank,
            "led": len(led),
            "mine": len(mine),
            "setup_s": round(setup_s, 1),
            "engine": my_engine,
            "platform": platform,
        },
    )

    sampler = None
    prof_dir = os.environ.get("E2E_PROFILE_DIR", "")
    if prof_dir:
        from profile_e2e import Sampler

        sampler = Sampler()
        sampler.start()

    rc = 0
    stage = "TPUT"  # tag the parent is blocked on; errors must carry it
    try:
        payload = _payload()
        # phase 1: throughput — every led group, window in flight.  The
        # per-group window is capped so AGGREGATE in-flight per rank stays
        # bounded: at 4k+ groups a fixed per-group window floods the
        # pipeline with 100k+ queued proposals and the measurement window
        # only sees the queue ramp (Little's law: latency = inflight/rate),
        # not steady-state throughput.
        target_inflight = int(os.environ.get("E2E_TARGET_INFLIGHT", "16384"))
        window = max(1, min(window, target_inflight // max(1, len(led))))
        plan = expect("RUN")
        while time.time() < plan["t0"]:
            time.sleep(0.005)
        # enrollment duty cycle, bracketed around the MEASUREMENT windows
        # only (drain budgets and cross-rank barriers between phases would
        # otherwise dilute the denominator)
        _fl_on = nh.fastlane is not None and nh.fastlane.enabled
        _dgs = nh.fastlane.duty_group_seconds if _fl_on else (lambda: 0.0)
        duty_gs = duty_el = 0.0
        _w_t0, _w_g0 = time.monotonic(), _dgs()
        tput = _measure(
            leaders, sorted(led), payload, window,
            plan["t0"] + plan["duration"], threads,
            drain_budget=plan.get("drain_budget", 30.0),
        )
        duty_gs += _dgs() - _w_g0
        duty_el += time.monotonic() - _w_t0
        tput_lats = tput.pop("_lats")
        tput["window"] = window  # effective (aggregate-inflight-capped)
        emit(
            "TPUT",
            {
                "rank": rank,
                "tput": tput,
                "tput_lats": tput_lats[:: max(1, len(tput_lats) // 20000)],
            },
        )
        # phase 2 (own barrier — starts only after every rank drained):
        # latency — window=1 on the designated subset
        stage = "RESULT"
        plan = expect("LAT")
        lat_cids = [c for c in plan["lat_cids"] if c in led]
        while time.time() < plan["t0"]:
            time.sleep(0.005)
        _w_t0, _w_g0 = time.monotonic(), _dgs()
        lat = _measure(
            leaders, lat_cids, payload, 1,
            plan["t0"] + plan["duration"], threads,
        )
        duty_gs += _dgs() - _w_g0
        duty_el += time.monotonic() - _w_t0
        lat_lats = lat.pop("_lats")
        fl_stats = (
            nh.fastlane.stats() if nh.fastlane is not None else {"enabled": False}
        )
        # round-3-comparable key: groups this rank LEADS that are enrolled
        # (stats() separately reports enrolled_replicas = all local
        # replicas in the lane, followers included)
        fl_stats["enrolled_now"] = sum(
            1 for cid in led if nh.get_node(cid).fast_lane
        )
        fl_stats["led"] = len(led)
        if _fl_on:
            # duty cycle over the measurement windows: fraction of
            # group-seconds this rank's REPLICAS (not just leaders — every
            # local replica can enroll) spent in the lane
            fl_stats["enroll_duty"] = round(
                duty_gs / (max(1, groups) * max(1e-9, duty_el)), 4
            )
        emit(
            "RESULT",
            {
                "rank": rank,
                "lat": lat,
                "engine_stats": nh.engine.stats(),
                "fastlane": fl_stats,
                "lat_lats": lat_lats[:: max(1, len(lat_lats) // 20000)],
            },
        )
        # phase 3: mixed 9:1 read:write (BASELINE.md Mixed IO axis)
        stage = "MIXED"
        plan = expect("MIX")
        mix_cids = [c for c in plan["cids"] if c in led]
        while time.time() < plan["t0"]:
            time.sleep(0.005)
        mixed = _measure_mixed(
            leaders, mix_cids, payload, plan.get("read_ratio", 9),
            plan["t0"] + plan["duration"], threads,
        )
        emit("MIXED", {"rank": rank, "mixed": mixed})
        # final barrier: a rank with no leaders finishes its phases
        # instantly — it must NOT stop its NodeHost (killing quorum for
        # the others) until every rank is done measuring
        expect("EXIT")
    except Exception as e:  # noqa: BLE001 — report, don't die silently
        # emit the error under the tag the parent is currently waiting for,
        # plus every later tag, so the parent never hangs or drops it
        err = {"rank": rank, "error": str(e)}
        emit(stage, err)
        for later in {"TPUT": ("RESULT", "MIXED"), "RESULT": ("MIXED",)}.get(
            stage, ()
        ):
            emit(later, err)
        rc = 1
    finally:
        if sampler is not None:
            sampler.stop()
            with open(os.path.join(prof_dir, f"rank{rank}.txt"), "w") as f:
                f.write(sampler.report() + "\n")
        try:
            nh.stop()
        except Exception:
            pass
    return rc


def _aggregate_mixed(mixed_results):
    oks = [r["mixed"] for r in mixed_results if "mixed" in r]
    if not oks:
        return {"error": "no rank completed the mixed phase"}
    return {
        "ops_per_sec": round(sum(m["ops_per_sec"] for m in oks), 1),
        "reads": sum(m["reads"] for m in oks),
        "writes": sum(m["writes"] for m in oks),
        "errors": sum(m["errors"] for m in oks),
        "read_ratio": oks[0]["read_ratio"],
        "read_latency_ms": oks[0]["read_latency_ms"],
        "write_latency_ms": oks[0]["write_latency_ms"],
    }


def _free_ports(n):
    socks = []
    ports = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


def run_mp(
    groups: int = 1024,
    duration: float = 10.0,
    window: int = 16,
    rtt_ms: int = 500,
    engine: str = "tpu",
    durable: bool = True,
    threads: int = 8,
    procs: int = 3,
    leader_mode: str = "",
    leader_timeout: float = 180.0,
    latency_groups: int = 64,
    deadline_s: float = 420.0,
) -> dict:
    """Parent orchestration: spawn one rank per NodeHost, coordinate the
    two measurement phases by wall clock, aggregate."""
    if not leader_mode:
        # With the native fast lane carrying steady-state replication,
        # leaders spread evenly in BOTH modes: concentrating all 1,024
        # leaders on the device rank (round 2's shape, when the device
        # engine was the only commit-tally offload) overloads one process
        # and wedges the mixed phase.  The device engine still runs on
        # rank 0 serving election tallies, device ticks and any
        # non-enrolled group's commit math; enrolled steady-state commits
        # are native (see PERF.md).
        leader_mode = "spread"
        if engine == "tpu" and os.environ.get("E2E_FAST_LANE", "1") != "1":
            leader_mode = "rank0"  # round-2 shape: device tallies it all
    t_start = time.time()
    hard_deadline = t_start + deadline_s
    ports = _free_ports(procs)
    tmp = tempfile.mkdtemp(prefix="dbtpu-e2e-") if durable else ""
    env = dict(os.environ)
    env.update(
        {
            "E2E_PROCS": str(procs),
            "E2E_GROUPS": str(groups),
            "E2E_RTT_MS": str(rtt_ms),
            "E2E_WINDOW": str(window),
            "E2E_THREADS": str(threads),
            "E2E_DURABLE": "1" if durable else "0",
            "E2E_ENGINE": engine,
            "E2E_LEADER_MODE": leader_mode,
            "E2E_LEADER_TIMEOUT": str(leader_timeout),
            "E2E_PORTS": ",".join(str(p) for p in ports),
            "E2E_DIR": tmp,
        }
    )
    children = []
    hogs = []
    try:
        rank_log_dir = os.environ.get("E2E_RANK_LOG_DIR", "")
        if rank_log_dir:
            os.makedirs(rank_log_dir, exist_ok=True)
        for rank in range(procs):
            cenv = dict(env)
            cenv["E2E_RANK"] = str(rank)
            stderr_to = subprocess.DEVNULL
            if rank_log_dir:
                stderr_to = open(
                    os.path.join(rank_log_dir, f"rank{rank}.err"), "w"
                )
            children.append(
                subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__), "--rank"],
                    stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE,
                    stderr=stderr_to,
                    env=cenv,
                    text=True,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                )
            )
            if stderr_to is not subprocess.DEVNULL:
                stderr_to.close()  # the child holds its own duplicated fd

        import queue as _queue

        # one reader thread per child: readline() can't be timed out
        # directly, so a hung rank must not wedge the parent past deadline_s
        rank_lines = [_queue.Queue() for _ in children]

        def _reader(proc, q):
            for line in proc.stdout:
                q.put(line)
            q.put(None)

        for c, q in zip(children, rank_lines):
            threading.Thread(target=_reader, args=(c, q), daemon=True).start()

        def read_tagged(idx, tag, deadline):
            """Read lines until one starts with tag; enforce deadline."""
            q = rank_lines[idx]
            while True:
                timeout = deadline - time.time()
                if timeout <= 0:
                    raise TimeoutError(f"deadline waiting for {tag}")
                try:
                    line = q.get(timeout=min(timeout, 1.0))
                except _queue.Empty:
                    continue
                if line is None:
                    raise RuntimeError(f"rank died before {tag}")
                if line.startswith(tag + " "):
                    return json.loads(line[len(tag) + 1 :])

        def broadcast(tag, obj=None):
            line = tag + (" " + json.dumps(obj) if obj is not None else "") + "\n"
            for c in children:
                try:
                    c.stdin.write(line)
                    c.stdin.flush()
                except (BrokenPipeError, OSError):
                    pass  # an errored rank may already have exited

        # barrier 1: all ranks started → campaign
        started = [
            read_tagged(i, "STARTED", hard_deadline - 30)
            for i in range(len(children))
        ]
        print(f"e2e mp started={started}", file=sys.stderr)
        broadcast("CAMPAIGN", {})
        readies = [
            read_tagged(i, "READY", hard_deadline - 20)
            for i in range(len(children))
        ]
        setup_s = time.time() - t_start
        print(f"e2e mp setup_s={setup_s:.1f} readies={readies}", file=sys.stderr)
        led_total = sum(r["led"] for r in readies)

        # E2E_HOG=N: spawn N busy-loop processes for the MEASUREMENT
        # phases only (setup/elections stay clean) — the contended-box
        # robustness axis (VERDICT r4 #2).  The assertion of interest is
        # the fastlane duty staying ~1.0 (no contact-loss/quorum-loss
        # eject cascade) while throughput degrades gracefully; killed in
        # the finally block below.
        n_hog = int(os.environ.get("E2E_HOG", "0"))
        for _ in range(n_hog):
            hogs.append(subprocess.Popen(
                [sys.executable, "-c", "while True:\n pass"],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            ))

        # phase 1: throughput
        broadcast("RUN", {"t0": time.time() + 0.5, "duration": duration,
                          "drain_budget": 30.0})
        tputs = [
            read_tagged(i, "TPUT", hard_deadline) for i in range(len(children))
        ]
        # phase 2: latency (after every rank drained).  If any rank
        # abandoned in-flight proposals at the drain deadline the runtime is
        # still chewing on them — give it a bounded quiesce window so the
        # window=1 latency probes don't queue behind leftover backlog
        abandoned_now = sum(
            r["tput"]["abandoned"] for r in tputs if "tput" in r
        )
        quiesce = 0.5 if not abandoned_now else min(20.0, 2.0 + abandoned_now / 1000.0)
        lat_cids = [BASE_CID + g for g in range(min(latency_groups, groups))]
        broadcast("LAT", {"t0": time.time() + quiesce,
                          "duration": min(duration, 5.0),
                          "lat_cids": lat_cids})
        results = [
            read_tagged(i, "RESULT", hard_deadline)
            for i in range(len(children))
        ]
        # phase 3: mixed 9:1 read:write on a bounded group subset
        mix_cids = [BASE_CID + g for g in range(min(256, groups))]
        broadcast("MIX", {"t0": time.time() + 0.5,
                          "duration": min(duration, 5.0),
                          "read_ratio": 9, "cids": mix_cids})
        mixed_results = []
        for i in range(len(children)):
            try:
                mixed_results.append(read_tagged(i, "MIXED", hard_deadline))
            except Exception as e:  # a rank that died earlier
                mixed_results.append({"rank": i, "error": str(e)})
        broadcast("EXIT", {})
        # one entry per failed rank (a TPUT-stage error is re-emitted under
        # RESULT so the parent never hangs — don't double-count it)
        errors = list(
            {r["rank"]: r for r in tputs + results if "error" in r}.values()
        )
        tput_oks = [r for r in tputs if "tput" in r]
        lat_oks = [r for r in results if "lat" in r]
        tput_done = sum(r["tput"]["completed_in_window"] for r in tput_oks)
        tput_errs = sum(r["tput"]["errors"] for r in tput_oks)
        abandoned = sum(r["tput"]["abandoned"] for r in tput_oks)
        lat_done = sum(r["lat"]["completed"] for r in lat_oks)
        tput_lats = [l for r in tput_oks for l in r["tput_lats"]]
        lat_lats = [l for r in lat_oks for l in r["lat_lats"]]
        writes_per_sec = round(tput_done / duration, 1)
        out = {
            "groups": groups,
            "hosts": procs,
            "procs": procs,
            "engine": engine,
            "sm": os.environ.get("E2E_SM", "python"),
            "leader_mode": leader_mode,
            "durable": durable,
            "payload_bytes": len(_payload()),
            "setup_s": round(setup_s, 1),
            "led_groups": led_total,
            "writes_per_sec": writes_per_sec,
            "commit_latency_ms": _percentiles(lat_lats),
            "throughput_phase": {
                "writes_per_sec": writes_per_sec,
                "completed_in_window": tput_done,
                "errors": tput_errs,
                "abandoned": abandoned,
                "latency_ms": _percentiles(tput_lats),
                # effective per-rank windows (the aggregate-inflight cap
                # depends on each rank's led count)
                "window": sorted(
                    r["tput"].get("window", window) for r in tput_oks
                ) or [window],
            },
            "latency_phase": {
                "completed": lat_done,
                "proposing_groups": len(lat_cids),
                "latency_ms": _percentiles(lat_lats),
            },
            "mixed_phase": _aggregate_mixed(mixed_results),
            "ranks": [
                {k: r[k] for k in ("rank", "engine", "platform", "led", "setup_s")}
                for r in readies
            ],
        }
        if os.environ.get("E2E_KEEP_STATS") == "1":
            out["rank_engine_stats"] = [r.get("engine_stats") for r in lat_oks]
        out["fastlane"] = [r.get("fastlane") for r in lat_oks]
        if errors:
            out["rank_errors"] = errors
        return out
    finally:
        for h in hogs:
            try:
                h.kill()
                h.wait(timeout=5)  # reap: a kill without wait leaves a zombie
            except Exception:
                pass
        for c in children:
            # let ranks finish their own cleanup (NodeHost.stop, profile
            # dumps) before the hard kill
            try:
                c.stdin.close()
            except Exception:
                pass
        deadline = time.time() + 8
        for c in children:
            try:
                c.wait(timeout=max(0.1, deadline - time.time()))
            except Exception:
                pass
        for c in children:
            try:
                c.kill()
            except Exception:
                pass
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def run_churn_soak() -> dict:
    """BlackWater churn soak A/B (ISSUE 17), scored by automated MTTR.

    Shells ``soak.py --churn`` twice with the SAME seed and schedule —
    recovery plane OFF, then ON — and merges the two summaries into one
    record.  The scored quantity is per-detector MTTR (health-event open
    to close, p50/p99 across the fleet, censored opens included); the
    gate is zero linearizability violations in BOTH arms.  Env knobs:
    CHURN_GROUPS (default 100), CHURN_MINUTES, CHURN_SEED,
    CHURN_ARM_TIMEOUT (seconds, per arm).
    """
    groups = int(os.environ.get("CHURN_GROUPS", "100"))
    minutes = float(os.environ.get("CHURN_MINUTES", "0.1"))
    seed = int(os.environ.get("CHURN_SEED", "7"))
    arm_timeout = float(os.environ.get("CHURN_ARM_TIMEOUT", "1800"))
    soak = os.path.join(os.path.dirname(os.path.abspath(__file__)), "soak.py")

    def _arm(recover: bool) -> dict:
        cmd = [
            sys.executable, soak, "--churn",
            "--minutes", str(minutes),
            "--groups", str(groups),
            "--seed", str(seed),
        ]
        if recover:
            cmd.append("--recover")
        try:
            p = subprocess.run(
                cmd, capture_output=True, text=True, timeout=arm_timeout,
            )
        except subprocess.TimeoutExpired:
            return {"churn_ok": False, "linearizable": False,
                    "error": f"arm timed out after {arm_timeout}s"}
        # the summary is the last stdout line; stderr carries progress
        lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
        try:
            s = json.loads(lines[-1])
        except Exception:
            s = {"churn_ok": False, "linearizable": False,
                 "error": f"unparseable summary (exit {p.returncode}): "
                          f"{(lines or ['<empty>'])[-1][:200]}"}
        s["exit_code"] = p.returncode
        return s

    off = _arm(False)
    on = _arm(True)
    improvement = {}
    for det, o in (off.get("mttr") or {}).items():
        n = (on.get("mttr") or {}).get(det)
        if not n or o.get("p99_s") is None or n.get("p99_s") is None:
            continue
        improvement[det] = {
            "off_p99_s": o["p99_s"],
            "on_p99_s": n["p99_s"],
            "off_p50_s": o.get("p50_s"),
            "on_p50_s": n.get("p50_s"),
            "speedup_x": (
                round(o["p99_s"] / n["p99_s"], 3) if n["p99_s"] else None
            ),
        }
    return {
        "groups": groups,
        "minutes": minutes,
        "seed": seed,
        "churn_ok": bool(off.get("churn_ok")) and bool(on.get("churn_ok")),
        "linearizable": (
            bool(off.get("linearizable")) and bool(on.get("linearizable"))
        ),
        "mttr_p99": improvement,
        "recovery_actions": on.get("recovery_actions"),
        "off": off,
        "on": on,
    }


def run_quick() -> dict:
    """Bounded run for bench.py's detail field (driver time budget)."""
    groups = int(os.environ.get("E2E_GROUPS", "1024"))
    # 15s measurement window: at 1,024 groups the 10s window showed ±30%
    # run-to-run spread from election/enrollment timing riding the edges
    duration = float(os.environ.get("E2E_DURATION", "15"))
    window = int(os.environ.get("E2E_WINDOW", "32"))
    rtt_ms = int(os.environ.get("E2E_RTT_MS", "1000"))
    engine = os.environ.get("E2E_ENGINE", "tpu")
    durable = os.environ.get("E2E_DURABLE", "1") == "1"
    threads = int(os.environ.get("E2E_THREADS", "8"))
    procs = int(os.environ.get("E2E_PROCS", "3"))
    deadline = float(os.environ.get("E2E_DEADLINE", "420"))
    if procs > 1:
        return run_mp(
            groups=groups,
            duration=duration,
            window=window,
            rtt_ms=rtt_ms,
            engine=engine,
            durable=durable,
            threads=threads,
            procs=procs,
            # honor an explicit placement request (E2E_LEADER_MODE=rank0
            # for the concentrated topology); "" keeps run_mp's policy
            # default — without this passthrough the orchestrator
            # silently overwrote the caller's env with "spread"
            leader_mode=os.environ.get("E2E_LEADER_MODE", ""),
            leader_timeout=float(os.environ.get("E2E_LEADER_TIMEOUT", "180")),
            deadline_s=deadline,
        )
    return run(
        groups=groups,
        duration=duration,
        window=window,
        rtt_ms=rtt_ms,
        engine=engine,
        durable=durable,
        threads=threads,
        leader_timeout=float(os.environ.get("E2E_LEADER_TIMEOUT", "180")),
    )


if __name__ == "__main__":
    if "--rank" in sys.argv:
        sys.exit(rank_main())
    # multi-process run_quick leaves the chip to its rank-0 child; every
    # other mode runs its NodeHosts in THIS process
    if len(sys.argv) > 1 or int(os.environ.get("E2E_PROCS", "3")) <= 1:
        _decide_platform()
    if "--trace-axis" in sys.argv:
        print(json.dumps(run_trace_axis()), file=sys.stdout)
        sys.exit(0)
    if "--crossdomain" in sys.argv:
        print(json.dumps(run_crossdomain()), file=sys.stdout)
        sys.exit(0)
    if "--devsm" in sys.argv:
        print(json.dumps(run_devsm()), file=sys.stdout)
        sys.exit(0)
    if "--host-workers" in sys.argv:
        print(json.dumps(run_host_workers_axis()), file=sys.stdout)
        sys.exit(0)
    if "--health-axis" in sys.argv:
        print(json.dumps(run_health_axis()), file=sys.stdout)
        sys.exit(0)
    if "--telem-axis" in sys.argv:
        print(json.dumps(run_telem_axis()), file=sys.stdout)
        sys.exit(0)
    if "--devprof-axis" in sys.argv:
        print(json.dumps(run_devprof_axis()), file=sys.stdout)
        sys.exit(0)
    if "--churn-soak" in sys.argv:
        print(json.dumps(run_churn_soak()), file=sys.stdout)
        sys.exit(0)
    if "--hier-axis" in sys.argv:
        print(json.dumps(run_hier()), file=sys.stdout)
        sys.exit(0)
    print(json.dumps(run_quick()), file=sys.stdout)
