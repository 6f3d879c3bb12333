"""Soak driver — the Drummer analog (reference ``docs/test.md:6-36``).

Opt-in, minutes-long chaos soak over the REAL deployment shape: three
NodeHost processes on framed TCP with durable native storage and the fast
lane on, G Raft groups replicated across all three.  For N minutes the
parent repeatedly ``kill -9``s a random rank and restarts it against the
same data dirs (WAL replay + snapshot catch-up), while every rank runs
continuous client load.  Aggressive snapshot settings keep snapshot
save/compact/stream churning throughout.

Verification, continuously and at the end:

- **cross-replica state hashes** (reference ``monkey.go:110-144``): at
  every converge window the parent pauses load, waits for equal applied
  indices on every live rank, and compares per-group state hashes;
- **linearizability** (reference Jepsen/Knossos role): every rank records
  an invoke/response history of puts and linearizable reads on per-group
  shared keys (wall-clock timestamps — one box); the parent merges all
  histories and runs ``linearizability.check_linearizable`` per key;
- **fast-lane invariants**: dropped apply spans must be 0 on every rank.

On failure the run's artifacts (per-rank histories, rank stderr logs, the
failure report) are preserved in the run directory and its path printed.

Usage::

    python soak.py --minutes 10 --groups 16        # the make soak target
    python soak.py --minutes 1 --groups 8          # quick smoke

**Churn mode** (``--churn``, ISSUE 17 — the BlackWater soak): four hosts
(three voters + a standby host carrying observers), ≥100 groups with
witness-heavy quorums, check-quorum + lease groups, and a seeded round
schedule of leader-flap storms, netsplits, SIGSTOP freezes, kill -9
restarts and membership recycles.  The health detectors run on every
host in BOTH arms; ``--recover`` additionally turns on the closed-loop
recovery plane (``NodeHostConfig.auto_recover``).  The run is scored by
automated MTTR — per-detector open→close durations merged fleet-wide —
while keeping the base soak's gates: linearizable histories, no
same-applied divergence, zero dropped fast-lane spans.  Run both arms on
the same seed and compare (``make soak-churn``)::

    python soak.py --churn --minutes 2 --groups 100 --seed 7            # OFF arm
    python soak.py --churn --minutes 2 --groups 100 --seed 7 --recover  # ON arm

``--hier`` (ISSUE 18) layers the hierarchical commit plane onto churn
mode: hosts 1+2 form domain A, hosts 3+4 domain B, and the netsplit
wave becomes domain-correlated (both B hosts cut at once) — every
commit closed during the hold closed through A's sub-quorum, and the
same linearizability gate scores them.

Exit code 0 = green.  Prints one JSON summary line last.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

# --------------------------------------------------------------------- rank


def _churn_layout(groups):
    """Deterministic group layout for churn mode, shared by the parent
    and every rank (both derive it from ``SOAK_GROUPS`` alone):

    - ``sample``  (cids 1..8): check-quorum voters {1,2,3} plus a
      standing observer (node 4) on the standby host — the groups the
      quorum_at_risk detector watches and the recovery plane repairs
      (evict the dead voter, promote the observer);
    - ``lease``   (cids 1..4): additionally ``read_lease=True`` — lease
      grant/expiry churns with every flap and split;
    - ``flap``    (cids 9..14): the leader-flap storm targets;
    - ``witness`` (cids 16..47, every 4th): witness-heavy quorums —
      voters {1,2} plus witness node 3, one voter loss from stall;
    - everything else: plain 3-voter groups {1,2,3}.
    """
    cids = list(range(1, groups + 1))
    witness = [c for c in cids if 16 <= c <= 47 and c % 4 == 0]
    sample = [c for c in cids if c <= 8]
    lease = [c for c in cids if c <= 4]
    flap = [c for c in cids if 9 <= c <= 14]
    return cids, witness, sample, lease, flap


class _KVSM:
    def __init__(self, cluster_id, node_id):
        self.kv = {}

    def update(self, cmd):
        k, v = cmd.decode().split("=", 1)
        self.kv[k] = v
        from dragonboat_tpu import Result

        return Result(value=len(self.kv))

    def lookup(self, query):
        return self.kv.get(query)

    def save_snapshot(self, w, files, done):
        data = json.dumps(sorted(self.kv.items())).encode()
        w.write(len(data).to_bytes(8, "little") + data)

    def recover_from_snapshot(self, r, files, done):
        n = int.from_bytes(r.read(8), "little")
        self.kv = dict(json.loads(r.read(n).decode()))

    def close(self):
        pass


def rank_main() -> int:
    import faulthandler

    # divergence triage: the parent sends SIGUSR2 before teardown so the
    # rank's stderr log captures every thread's stack at failure time
    faulthandler.register(signal.SIGUSR2, all_threads=True)

    from dragonboat_tpu import Config, NodeHost, NodeHostConfig
    from dragonboat_tpu.config import ExpertConfig

    rank = int(os.environ["SOAK_RANK"])
    groups = int(os.environ["SOAK_GROUPS"])
    threads = int(os.environ.get("SOAK_THREADS", "4"))
    addrs = {
        i + 1: a for i, a in enumerate(os.environ["SOAK_ADDRS"].split(","))
    }
    base = os.environ["SOAK_DIR"]
    nid = rank + 1

    churn = os.environ.get("SOAK_CHURN") == "1"
    recover = os.environ.get("SOAK_RECOVER") == "1"
    hier = os.environ.get("SOAK_HIER") == "1"
    nhc_kw = {}
    if churn:
        # BlackWater churn profile (ISSUE 17): the health detectors run
        # at a tight cadence on EVERY host in BOTH arms (MTTR is scored
        # from detector open→close); the recovery plane only in the ON
        # arm.  Slower ticks than the base soak: 4 hosts x 100+ groups
        # on one box.
        nhc_kw.update(
            health_sample_ms=int(os.environ.get("SOAK_HEALTH_MS", "100")),
            enable_metrics=True,
        )
        if recover:
            nhc_kw.update(
                auto_recover=True,
                auto_recover_knobs=dict(
                    # cooldown > the flap quiet window: one escape
                    # transfer per open event — repeat transfers are
                    # themselves leader changes and would hold the
                    # detector open (MTTR regression, not remediation)
                    rate_limit_s=0.5, cooldown_s=8.0, retry_delay_s=0.2,
                    max_attempts=10, max_reopens=4, reopen_window_s=30.0,
                ),
                # boot in dry-run: 100-group elections on one vCPU look
                # exactly like quorum risk, and a controller that evicts
                # live voters mid-bootstrap wrecks the SETUP config
                # changes.  The first RESUME (parent sends it when setup
                # is complete) arms the controller for real.
                auto_recover_dry_run=True,
            )
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=os.path.join(base, f"nh{rank}"),
            rtt_millisecond=50 if churn else 20,
            raft_address=addrs[nid],
            expert=ExpertConfig(fast_lane=True, logdb_shards=2),
            **nhc_kw,
        )
    )
    if churn and nh.health is not None:
        # shrink the flap window so leader_flap can close (and be
        # scored) inside a single churn round; 4+ changes = a real flap
        # (a single election contributes 2-3: leader -> none -> leader)
        nh.health.flap_window_s = 6.0
        nh.health.leader_flap_changes = 4
        # startup elections produce sub-second unreachability blips; a
        # 2-sample debounce would let the ON arm evict LIVE voters while
        # the fleet is still settling.  8 sustained samples (~0.8s at the
        # 100ms cadence) ignores election noise yet still detects a real
        # kill/netsplit an order of magnitude faster than the 12s hold.
        nh.health.quorum_risk_samples = 8
    cids = list(range(1, groups + 1))
    if churn:
        _, witness_cids, sample_cids, lease_cids, _ = _churn_layout(groups)
    else:
        witness_cids, sample_cids, lease_cids = [], [], []
    user_sms = {}

    # SOAK_NATIVE_SM=1: the C-ABI KV + native session store — enrolled
    # groups then apply (and dedup) natively, so the churn exercises the
    # native apply/session path instead of the Python RSM rim
    native_sm = os.environ.get("SOAK_NATIVE_SM") == "1"
    if native_sm:
        from dragonboat_tpu.native.natsm import NativeKVStateMachine

    def _mk_sm(cluster_id, node_id):
        if native_sm:
            sm = NativeKVStateMachine(cluster_id, node_id)
        else:
            sm = _KVSM(cluster_id, node_id)
        user_sms[cluster_id] = sm
        return sm

    def _cfg(cid, node_id, **kw):
        base_kw = dict(
            cluster_id=cid, node_id=node_id, election_rtt=10,
            heartbeat_rtt=1,
            # aggressive: constant snapshot + compaction churn, and a
            # restarted replica far behind catches up via streaming
            # (churn mode relaxes a notch: 6x the groups on one box)
            snapshot_entries=200 if churn else 100,
            compaction_overhead=50 if churn else 20,
        )
        if churn and cid in sample_cids:
            base_kw["check_quorum"] = True
        if churn and cid in lease_cids:
            base_kw["read_lease"] = True
        if churn and hier:
            # hier arm (ISSUE 18): hosts 1+2 form near domain A, hosts
            # 3+4 domain B — the parent's domain-correlated waves then
            # take B down WHOLE, and linearizability is asserted with
            # sub-quorum commits live.  Recycled standbys (nid >= 5)
            # stay unassigned: never in a sub-quorum, always safe.
            base_kw["hier_commit"] = True
            base_kw["hier_domains"] = {1: "A", 2: "A", 3: "B", 4: "B"}
        base_kw.update(kw)
        if base_kw.get("is_witness"):
            # "witness node cannot take snapshot" (config.validate):
            # witnesses replicate metadata only, nothing to snapshot
            base_kw["snapshot_entries"] = 0
        return Config(**base_kw)

    if not churn:
        for cid in cids:
            nh.start_cluster(addrs, False, _mk_sm, _cfg(cid, nid))
    elif rank <= 1:
        # voter on every group; witness groups bootstrap with {1,2} only
        for cid in cids:
            members = (
                {1: addrs[1], 2: addrs[2]} if cid in witness_cids
                else {n: addrs[n] for n in (1, 2, 3)}
            )
            nh.start_cluster(members, False, _mk_sm, _cfg(cid, nid))
    elif rank == 2:
        # voter on plain groups; witness replica on the witness groups,
        # started join-style with an empty config — it sits idle until
        # the SETUP config change registers it and the leader streams
        # state (restart-safe: the saved bootstrap replays the same way)
        for cid in cids:
            if cid in witness_cids:
                nh.start_cluster({}, True, _mk_sm,
                                 _cfg(cid, 3, is_witness=True))
            else:
                nh.start_cluster({n: addrs[n] for n in (1, 2, 3)}, False,
                                 _mk_sm, _cfg(cid, 3))
    else:
        # rank 3 = the standby host: standing observers on the
        # quorum-sample groups (the replicas the recovery plane promotes)
        for cid in sample_cids:
            nh.start_cluster({}, True, _mk_sm,
                             _cfg(cid, 4, is_observer=True))

    hist_path = os.path.join(base, f"history.r{rank}.{os.getpid()}.jsonl")
    hist_f = open(hist_path, "a", buffering=1)
    hist_mu = threading.Lock()

    # WRITE-AHEAD history (Jepsen-style invoke/ret pairs): the invoke
    # line lands on disk BEFORE the operation is issued, so a kill -9
    # between "proposal committed server-side" and "completion recorded"
    # leaves an unmatched invoke that the checker treats as an op with
    # UNKNOWN outcome — not a hole.  (A 32-group soak caught exactly
    # this: a killed rank's committed put vanished from its history and
    # two other ranks' reads of it looked like phantom values.)
    op_seq = [0]

    def record_invoke(client, kind, key, value, t0):
        with hist_mu:
            op_seq[0] += 1
            oid = op_seq[0]
            hist_f.write(json.dumps({
                "ev": "inv", "id": oid, "client": client, "kind": kind,
                "key": key, "value": value, "invoke": t0,
            }) + "\n")
            return oid

    def record_ret(oid, value, t1, ok):
        with hist_mu:
            hist_f.write(json.dumps({
                "ev": "ret", "id": oid, "value": value, "ret": t1,
                "ok": ok,
            }) + "\n")

    paused = threading.Event()
    stopped = threading.Event()
    if churn:
        # churn ranks boot PAUSED so initial elections and the
        # witness/observer SETUP run without client load competing for
        # the single vCPU; the parent RESUMEs every rank once setup
        # lands.  Without this, setup config changes time out and the
        # recovery plane acts on startup transients.
        paused.set()
    # linearizability histories only for SAMPLED groups, written by ONE
    # paced client per rank: the Wing & Gong search cost scales with
    # per-key history length and concurrency, so the recorded stream is
    # deliberately low-rate while the unrecorded load threads provide the
    # actual stress (reference: Drummer checks sampled keys too)
    sampled = cids[: max(1, int(os.environ.get("SOAK_SAMPLE", "4")))]

    # SOAK_SESSIONS=1: history puts use REGISTERED sessions (exactly-once).
    # The payoff under kill -9 churn: an op whose first attempt times out
    # can be RETRIED with the same series id — the dedup store guarantees
    # at-most-once apply, so a successful retry RESOLVES the outcome
    # (committed, cached result) instead of leaving it unknown to the
    # checker.  Noop sessions can never do that (a retry would double-
    # apply).  Reference: client session semantics, session.go.
    use_sessions = os.environ.get("SOAK_SESSIONS") == "1"

    def _get(cid):
        # churn mode: not every rank hosts every group (witness/observer
        # layout, recycled nids) — absent is normal, not an error
        try:
            return nh.get_node(cid)
        except Exception:  # noqa: BLE001 — ClusterNotFoundError
            return None

    def history_client():
        client = rank
        rng = random.Random(client * 7919 + os.getpid())
        session = {}
        while not stopped.is_set():
            if paused.is_set():
                time.sleep(0.05)
                continue
            cid = rng.choice(sampled)
            node = _get(cid)
            if node is None:
                time.sleep(0.05)
                continue
            is_put = rng.random() < 0.6
            # puts go to the leader; linearizable GETs run at ANY replica
            # (follower-forwarded native ReadIndex) — history checking
            # then covers cross-replica read consistency, not just the
            # leader's own view
            if is_put and not node.is_leader():
                time.sleep(0.05)
                continue
            key = f"g{cid}:x{rng.randrange(2)}"
            t0 = time.time()
            if is_put:
                val = f"r{rank}n{rng.randrange(1 << 30)}"
                oid = record_invoke(client, "put", key, val, t0)
            else:
                val = None
                oid = record_invoke(client, "get", key, None, t0)
            try:
                if is_put:
                    s = session.get(cid)
                    if s is None:
                        if use_sessions:
                            s = nh.sync_get_session(cid, timeout=5.0)
                        else:
                            s = nh.get_noop_session(cid)
                        session[cid] = s
                    cmd = f"{key}={val}".encode()
                    attempts = 3 if not s.is_noop_session() else 1
                    done = False
                    for a in range(attempts):
                        try:
                            r = nh.propose(s, cmd, timeout=5.0).wait(5.0)
                        except Exception:
                            if a + 1 == attempts:
                                raise
                            continue
                        if r.completed:
                            done = True
                            break
                        # rejected/dropped with a session: the series was
                        # never applied under this id — safe to re-propose
                    if done and not s.is_noop_session():
                        s.proposal_completed()
                    record_ret(oid, val, time.time() if done else None, done)
                    if not done and not s.is_noop_session():
                        # unknown outcome on a session: the series id is
                        # burned (a later reuse could dedup against a
                        # quietly-committed first attempt and break the
                        # exactly-once bookkeeping) — re-register
                        session.pop(cid, None)
                else:
                    v = nh.sync_read(cid, key, timeout=5.0)
                    record_ret(oid, v, time.time(), True)
            except Exception:
                # timeout/dropped: outcome unknown — the checker treats a
                # None ret as an op concurrent with everything after it
                record_ret(oid, val, None, False)
                if is_put:
                    session.pop(cid, None)
            time.sleep(0.4)  # pace: bounded per-key history length

    def load(tid):
        rng = random.Random((rank * 100 + tid) * 104729 + os.getpid())
        session = {}
        while not stopped.is_set():
            if paused.is_set():
                time.sleep(0.05)
                continue
            cid = rng.choice(cids)
            node = _get(cid)
            if node is None or not node.is_leader():
                time.sleep(0.002)
                continue
            try:
                s = session.get(cid)
                if s is None:
                    s = session[cid] = nh.get_noop_session(cid)
                k = f"w{rng.randrange(64)}"
                rs = nh.propose(
                    s, f"{k}=t{tid}n{rng.randrange(1 << 30)}".encode(),
                    timeout=5.0,
                )
                rs.wait(5.0)
                if rng.random() < 0.1:
                    nh.sync_read(cid, k, timeout=5.0)
            except Exception:
                time.sleep(0.02)

    threading.Thread(target=history_client, daemon=True).start()
    for tid in range(threads):
        threading.Thread(target=load, args=(tid,), daemon=True).start()

    def emit(tag, obj=None):
        sys.stdout.write(tag + (" " + json.dumps(obj) if obj else "") + "\n")
        sys.stdout.flush()

    emit("READY", {"rank": rank, "pid": os.getpid()})
    try:
        for line in sys.stdin:
            cmd = line.strip()
            if cmd == "PAUSE":
                paused.set()
                time.sleep(0.3)  # let in-flight ops drain
                emit("PAUSED")
            elif cmd == "RESUME":
                paused.clear()
                if nh.recovery is not None:
                    nh.recovery.dry_run = False  # arm post-bootstrap
                emit("RESUMED")
            elif cmd == "HASHES":
                import zlib

                out = {}
                for cid in cids:
                    node = _get(cid)
                    if node is None:
                        continue  # churn: not every rank hosts every group
                    sm = node.sm
                    # manager hash (sessions+applied+membership) PLUS the
                    # user SM content hash — the manager hash alone would
                    # miss divergent KV state at equal applied indices
                    # (kvtest.go GetHash role)
                    user = user_sms.get(cid)
                    if user is None:
                        kv_hash = 0
                    elif native_sm:
                        kv_hash = user.get_hash()
                    else:
                        kv_hash = zlib.crc32(
                            repr(sorted(user.kv.items())).encode()
                        )
                    r = node.peer.raft if node.peer is not None else None
                    member = 1
                    if r is not None and node.node_id not in (
                        set(r.remotes) | set(r.observers) | set(r.witnesses)
                    ):
                        member = 0
                    out[cid] = [
                        sm.get_last_applied(), sm.get_hash(), kv_hash,
                        # exactly-once session store (compared too: a
                        # diverging dedup history is a consistency bug
                        # even while the KV content still agrees)
                        sm.get_session_hash(),
                        # diagnostics (not compared): raft view + lane state
                        r.log.committed if r else -1,
                        r.state.name if r else "?",
                        int(node.fast_lane),
                        # churn-mode comparison guards: witness replicas
                        # hold no user state; a replica whose own view says
                        # it left the membership (evicted/recycled) is
                        # excused from convergence (the lin gate covers it)
                        int(node.config.is_witness),
                        member,
                        # settle targeting: this replica's node id and its
                        # membership view — the parent trusts the
                        # MAX-applied cell's view (zombies replaying a
                        # pre-eviction bootstrap sit strictly below it)
                        node.node_id,
                        sorted(
                            set(r.remotes) | set(r.observers)
                            | set(r.witnesses)
                        ) if r else [],
                    ]
                fl = nh.fastlane
                emit("HASHES", {
                    "rank": rank, "groups": out,
                    "dropped_spans": fl.dropped_spans if fl else 0,
                    "enrolled": (
                        fl.stats().get("enrolled_replicas", 0) if fl else 0
                    ),
                })
            elif cmd.startswith("PART "):
                # "PART <addr> <0|1>": (un)block the remote at the native
                # transport — a true netsplit over TCP (both planes ride
                # the native streams; see fastlane.set_partition).  A rank
                # without a fast lane must NOT ack success: the parent
                # would count a netsplit that was never injected.
                _, part_addr, on = cmd.split()
                # the reply echoes the command so the parent can match
                # acks to requests (a timed-out attempt's late ack must
                # not satisfy a LATER command's wait)
                if nh.fastlane is not None:
                    nh.fastlane.set_partition(part_addr, on == "1")
                    emit("PART", {"ok": True, "addr": part_addr, "on": on})
                else:
                    emit("PART", {"ok": False, "addr": part_addr, "on": on})
            elif cmd == "SETUP":
                # churn setup (issued to rank 0 once): runtime config
                # changes — witness node 3 onto the witness groups, the
                # standing observer node 4 onto the quorum-sample groups.
                # Proposals forward to the leader, so one rank drives all
                # of them; a change that timed out but actually committed
                # is detected via the membership view and not retried.
                errs = []

                def _ensure(cid, want_nid, fn, field):
                    stop_at = time.time() + 240.0
                    while True:
                        try:
                            fn()
                            return
                        except Exception as e:  # noqa: BLE001
                            try:
                                m = nh.sync_get_cluster_membership(
                                    cid, timeout=5.0
                                )
                                if want_nid in getattr(m, field):
                                    return
                            except Exception:
                                pass
                            if time.time() > stop_at:
                                errs.append(
                                    f"{field}:{cid}:{type(e).__name__}"
                                )
                                return
                            time.sleep(0.5)

                for cid in witness_cids:
                    _ensure(
                        cid, 3,
                        lambda cid=cid: nh.sync_request_add_witness(
                            cid, 3, addrs[3], timeout=10.0
                        ),
                        "witnesses",
                    )
                for cid in sample_cids:
                    _ensure(
                        cid, 4,
                        lambda cid=cid: nh.sync_request_add_observer(
                            cid, 4, addrs[4], timeout=10.0
                        ),
                        "observers",
                    )
                emit("SETUP", {"ok": not errs, "errors": errs[:8]})
            elif cmd.startswith("XFER "):
                # drive a leader transfer if THIS host currently leads
                # the group (the parent's flap storm sends these to the
                # flapping pair only — once the recovery plane lands
                # leadership outside the pair they all no-op)
                _, c, t = cmd.split()
                c, t = int(c), int(t)
                node = _get(c)
                issued = False
                if node is not None and node.is_leader():
                    try:
                        nh.request_leader_transfer(c, t)
                        issued = True
                    except Exception:  # noqa: BLE001
                        pass
                emit("XFER", {"cid": c, "target": t, "issued": issued})
            elif cmd.startswith("RECYCLE "):
                # membership recycle (rank 0): retire the group's standby
                # nid and register a fresh one at the standby host — node
                # ids never rejoin after removal, so the recycle always
                # moves forward
                _, c, old, new = cmd.split()
                c, old, new = int(c), int(old), int(new)
                err = None
                try:
                    nh.sync_request_delete_node(c, old, timeout=15.0)
                    nh.sync_request_add_observer(
                        c, new, addrs[4], timeout=15.0
                    )
                except Exception as e:  # noqa: BLE001
                    err = f"{type(e).__name__}: {e}"[:160]
                emit("RECYCLE", {"cid": c, "ok": err is None, "error": err})
            elif cmd.startswith("REJOIN "):
                # rank 3: drop the retired observer replica and join the
                # fresh nid that RECYCLE just registered
                _, c, new = cmd.split()
                c, new = int(c), int(new)
                err = None
                try:
                    try:
                        nh.stop_cluster(c)
                    except Exception:  # noqa: BLE001
                        pass
                    nh.start_cluster({}, True, _mk_sm,
                                     _cfg(c, new, is_observer=True))
                except Exception as e:  # noqa: BLE001
                    err = f"{type(e).__name__}: {e}"[:160]
                emit("REJOIN", {"cid": c, "ok": err is None, "error": err})
            elif cmd == "RECOV":
                # MTTR collection: raw per-detector open→close durations
                # (the parent merges across hosts and recomputes fleet
                # percentiles), ages of still-open events (censored lower
                # bounds), and the recovery plane's action report
                h = nh.health
                open_ages = {}
                if h is not None:
                    for e in h.open_events():
                        open_ages.setdefault(e["detector"], []).append(
                            round(time.monotonic() - e["opened_mono"], 3)
                        )
                emit("RECOV", {
                    "rank": rank,
                    "durations": h.recovery_durations() if h else {},
                    "open_ages": open_ages,
                    "opened": dict(h.opened) if h else {},
                    "recovery": nh.recovery_report(),
                })
            elif cmd == "EXIT":
                break
    finally:
        stopped.set()
        hist_f.close()
        try:
            nh.stop()
        except Exception:
            pass
    return 0


# ------------------------------------------------------------------- parent


def _ports(n):
    out = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        out.append(s.getsockname()[1])
        s.close()
    return out


class Rank:
    def __init__(self, idx, env, logdir):
        self.idx = idx
        self.env = env
        self.logdir = logdir
        self.proc = None
        self.log = None
        self.lines = None

    def start(self):
        import queue as _q

        self.log = open(
            os.path.join(self.logdir, f"rank{self.idx}.{int(time.time())}.log"),
            "w",
        )
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--rank"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.log, env=self.env, text=True,
        )
        self.lines = _q.Queue()

        def _reader(p, q):
            for ln in p.stdout:
                q.put(ln)
            q.put(None)

        threading.Thread(
            target=_reader, args=(self.proc, self.lines), daemon=True
        ).start()

    def expect(self, tag, timeout):
        import queue as _q

        deadline = time.time() + timeout
        while True:
            left = deadline - time.time()
            if left <= 0:
                raise TimeoutError(f"rank{self.idx}: no {tag} in {timeout}s")
            try:
                ln = self.lines.get(timeout=min(left, 1.0))
            except _q.Empty:
                continue
            if ln is None:
                raise RuntimeError(f"rank{self.idx} died waiting for {tag}")
            if ln.startswith(tag):
                rest = ln[len(tag):].strip()
                return json.loads(rest) if rest else None

    def send(self, cmd):
        self.proc.stdin.write(cmd + "\n")
        self.proc.stdin.flush()

    def kill9(self):
        self.proc.kill()  # SIGKILL
        self.proc.wait()
        self.log.close()

    def pause(self):
        """SIGSTOP: the partition analog — the rank goes silent without
        dying (peers see timeouts; its own threads freeze mid-state)."""
        self.proc.send_signal(signal.SIGSTOP)

    def resume(self):
        self.proc.send_signal(signal.SIGCONT)

    def alive(self):
        return self.proc is not None and self.proc.poll() is None


def _set_split(ranks, addr_list, victim, on):
    """Symmetric netsplit {victim} | {others} at the native wire
    (the reference monkey's partitionTests shape).  Returns True
    when every live rank confirmed the change.  A rank that fails
    to HEAL is kill -9'd and restarted: its blocks live in process
    memory, so the restart clears them — a stale block would
    otherwise fail every later converge check with a misleading
    divergence report."""
    flag = "1" if on else "0"
    ok = True

    def apply_one(r):
        cmds = (
            [a for j, a in enumerate(addr_list) if j != victim.idx]
            if r is victim
            else [addr_list[victim.idx]]
        )
        for a in cmds:
            r.send(f"PART {a} {flag}")
            # match the echoed command: a late ack from a timed-out
            # earlier attempt must not satisfy this wait
            deadline_ack = time.time() + 10
            while True:
                rep = r.expect("PART", max(0.1, deadline_ack - time.time()))
                if rep and rep.get("addr") == a and rep.get("on") == flag:
                    break
            if not rep.get("ok"):
                raise RuntimeError("partition injection refused")

    for r in ranks:
        if not r.alive():
            continue  # a killed rank holds no blocks
        for attempt in (1, 2):
            try:
                apply_one(r)
                break
            except Exception:
                if attempt == 2:
                    ok = False
                    if not on and r.alive():
                        print(
                            f"# rank{r.idx} failed to heal; "
                            "kill -9 to clear its blocks",
                            file=sys.stderr,
                        )
                        r.kill9()
                        time.sleep(1.0)
                        r.start()
                        r.expect("READY", 180)
    return ok


def _converge_check(ranks, groups, timeout=90.0):
    """Pause load everywhere, wait for equal applied indices per group on
    every live rank, compare state hashes.  Returns the hash map or raises."""
    live = [r for r in ranks if r.alive()]
    for r in live:
        r.send("PAUSE")
    for r in live:
        r.expect("PAUSED", 30)
    deadline = time.time() + timeout
    last = None
    try:
        while True:
            reports = []
            for r in live:
                r.send("HASHES")
                reports.append(r.expect("HASHES", 30))
            for rep in reports:
                assert rep["dropped_spans"] == 0, (
                    f"rank{rep['rank']} dropped apply spans"
                )
            bad = []
            for cid in range(1, groups + 1):
                cells = [rep["groups"][str(cid)] for rep in reports]
                applied = {c[0] for c in cells}
                # manager + user SM + session store
                hashes = {tuple(c[1:4]) for c in cells}
                if len(applied) != 1 or len(hashes) != 1:
                    bad.append((cid, cells))
            last = bad
            if not bad:
                return reports
            if time.time() > deadline:
                for r in live:  # stack dumps into the rank logs
                    try:
                        r.proc.send_signal(signal.SIGUSR2)
                    except Exception:
                        pass
                time.sleep(1.0)
                raise AssertionError(
                    f"replicas diverged after {timeout}s settle: "
                    f"{len(bad)} groups, sample {bad[:3]}"
                )
            time.sleep(1.0)
    finally:
        for r in live:
            if r.alive():
                r.send("RESUME")
                r.expect("RESUMED", 30)


def _check_histories(base, groups):
    from dragonboat_tpu.linearizability import Op, check_linearizable

    INF = float("inf")
    ops = []
    for fn in sorted(os.listdir(base)):
        if not fn.startswith("history."):
            continue
        # write-ahead pairs: "inv" lines land BEFORE the op is issued,
        # "ret" lines after.  An inv with no ret (the rank was killed
        # mid-op, or its ret line was torn) is an op with UNKNOWN
        # outcome — a killed rank's committed-but-unrecorded put must
        # stay representable or other ranks' reads of it look phantom.
        pend = {}
        with open(os.path.join(base, fn)) as f:
            lines = f.readlines()
        for ln in lines:
            try:
                d = json.loads(ln)
            except json.JSONDecodeError:
                continue  # torn final line from a kill -9
            if d.get("ev") == "inv":
                pend[d["id"]] = d
            elif d.get("ev") == "ret":
                inv = pend.pop(d["id"], None)
                if inv is None:
                    continue  # ret whose inv line was torn: drop
                ops.append(Op(
                    client=inv["client"], kind=inv["kind"],
                    key=inv["key"], value=d["value"],
                    invoke=inv["invoke"],
                    ret=d["ret"] if d["ret"] is not None else INF,
                    ok=bool(d["ok"]),
                ))
        for inv in pend.values():  # unmatched: unknown outcome
            ops.append(Op(
                client=inv["client"], kind=inv["kind"], key=inv["key"],
                value=inv["value"], invoke=inv["invoke"],
                ret=INF, ok=False,
            ))
    ok, bad = check_linearizable(ops)
    return ok, bad, len(ops)


# -------------------------------------------------------------- churn parent


def _pct(durs, p):
    s = sorted(durs)
    if not s:
        return None
    i = min(len(s) - 1, max(0, int(round((p / 100.0) * (len(s) - 1)))))
    return s[i]


def _mttr_stats(durs_by_det, open_by_det):
    """Fleet-level MTTR per detector: closed open→close durations merged
    across hosts plus the ages of still-open events (censored LOWER
    bounds — counting them can only make MTTR look worse, never
    better)."""
    out = {}
    for det in sorted(set(durs_by_det) | set(open_by_det)):
        closed = list(durs_by_det.get(det, ()))
        censored = list(open_by_det.get(det, ()))
        all_d = closed + censored
        if not all_d:
            continue
        out[det] = {
            "n": len(all_d),
            "unclosed": len(censored),
            "p50_s": round(_pct(all_d, 50), 3),
            "p99_s": round(_pct(all_d, 99), 3),
            "max_s": round(max(all_d), 3),
        }
    return out


def _collect_recov(ranks):
    """Merge every live rank's RECOV report: raw durations, open-event
    ages, detector open counts and (ON arm) recovery action counts."""
    durs, open_ages, opened, actions = {}, {}, {}, {}
    for r in ranks:
        if not r.alive():
            continue
        r.send("RECOV")
        rep = r.expect("RECOV", 30)
        for det, d in (rep.get("durations") or {}).items():
            durs.setdefault(det, []).extend(d)
        for det, ages in (rep.get("open_ages") or {}).items():
            open_ages.setdefault(det, []).extend(ages)
        for det, n in (rep.get("opened") or {}).items():
            if n:
                opened[det] = opened.get(det, 0) + n
        rec = rep.get("recovery") or {}
        if rec.get("enabled"):
            for k, v in (rec.get("actions") or {}).items():
                actions[k] = actions.get(k, 0) + v
    return durs, open_ages, opened, actions


def _churn_converge(ranks, groups, timeout=150.0, settle=False):
    """Relaxed churn-mode convergence.  Membership is deliberately in
    motion (witness adds, observer promotions, evictions, recycles), so
    equal-applied-everywhere is not a reachable fixpoint mid-run.  The
    invariant that IS checked continuously: two member (non-witness)
    replicas at the SAME applied index must have identical state —
    divergence, never lag.  With ``settle=True`` (final check) it also
    waits until, per group, every replica that the MAX-applied cell's
    membership view still lists matches that cell's applied index and
    hashes.  Replicas that replayed a pre-eviction bootstrap (zombies)
    sit strictly below the max — the eviction entry itself separates
    them — and are not in the reference view, so they are excused; the
    linearizability gate covers their reads."""
    live = [r for r in ranks if r.alive()]
    for r in live:
        r.send("PAUSE")
    for r in live:
        r.expect("PAUSED", 30)
    deadline = time.time() + timeout
    try:
        while True:
            reports = []
            for r in live:
                r.send("HASHES")
                reports.append(r.expect("HASHES", 60))
            for rep in reports:
                assert rep["dropped_spans"] == 0, (
                    f"rank{rep['rank']} dropped apply spans"
                )
            diverged, lagging = [], []
            for cid in range(1, groups + 1):
                cells = []
                for rep in reports:
                    c = rep["groups"].get(str(cid))
                    if c is not None and len(c) >= 9 and c[7] == 0 \
                            and c[8] == 1:
                        cells.append(c)
                if not cells:
                    continue
                byapp = {}
                for c in cells:
                    byapp.setdefault(c[0], set()).add(tuple(c[1:4]))
                if any(len(h) > 1 for h in byapp.values()):
                    diverged.append((cid, cells))
                    continue
                if settle and len(cells) >= 2:
                    ref = max(cells, key=lambda c: c[0])
                    mset = set(ref[10]) if len(ref) >= 11 else set()
                    for c in cells:
                        if c is ref or len(c) < 11 or c[9] not in mset:
                            continue
                        if c[5] == "OBSERVER":
                            # non-voting: an observer a couple of
                            # entries behind the commit frontier is
                            # eventual-consistency, not divergence (the
                            # same-applied hash check above still
                            # covers it; reads forward to the leader)
                            continue
                        if c[0] != ref[0] or c[1:4] != ref[1:4]:
                            lagging.append((cid, cells))
                            break
            if not diverged and not lagging:
                return reports
            if time.time() > deadline:
                for r in live:  # stack dumps into the rank logs
                    try:
                        r.proc.send_signal(signal.SIGUSR2)
                    except Exception:
                        pass
                time.sleep(1.0)
                kind = "diverged" if diverged else "failed to settle"
                raise AssertionError(
                    f"churn converge {kind} after {timeout}s: "
                    f"{len(diverged)} diverged / {len(lagging)} lagging, "
                    f"sample {(diverged or lagging)[:3]}"
                )
            time.sleep(2.0)
    finally:
        for r in live:
            if r.alive():
                r.send("RESUME")
                r.expect("RESUMED", 30)


def churn_main(args) -> int:
    """BlackWater churn soak (ISSUE 17).  Four hosts — three voters plus
    a standby host carrying standing observers — run ``--groups`` Raft
    groups through a seeded round schedule: leader-flap storm → settle →
    netsplit the third voter host → heal → SIGSTOP freeze → membership
    recycle (odd rounds) or kill -9 + restart (even rounds) → converge
    check.  Detectors run in both arms; ``--recover`` arms the recovery
    plane.  Scored by fleet-merged per-detector MTTR; gated on
    linearizable histories, zero same-applied divergence and zero
    dropped fast-lane spans."""
    seed = args.seed or int(time.time())
    rng = random.Random(seed)
    groups = args.groups
    base = tempfile.mkdtemp(prefix="dbtpu-churn-")
    ports = _ports(4)
    addr_list = [f"127.0.0.1:{p}" for p in ports]
    addrs = ",".join(addr_list)
    arm = "on" if args.recover else "off"
    print(
        f"# churn soak: {args.minutes} min, {groups} groups, "
        f"recover={arm}, seed {seed}, dir {base}",
        file=sys.stderr,
    )

    _, witness_cids, sample_cids, _, flap_cids = _churn_layout(groups)
    ranks = []
    for i in range(4):
        env = dict(os.environ)
        env.update({
            "SOAK_RANK": str(i), "SOAK_GROUPS": str(groups),
            "SOAK_ADDRS": addrs, "SOAK_DIR": base,
            "SOAK_CHURN": "1",
            "SOAK_RECOVER": "1" if args.recover else "0",
            "SOAK_HIER": "1" if getattr(args, "hier", False) else "0",
            "SOAK_THREADS": os.environ.get("SOAK_THREADS", "2"),
            "SOAK_SAMPLE": "8",
            # at 100+ groups the 100ms sampler pass itself is load on
            # the 1-vCPU box; 250ms keeps detection an order of
            # magnitude under the 12s netsplit hold while widening the
            # debounce window (quorum_risk_samples x cadence) enough to
            # ride out CPU-starvation heartbeat lapses
            "SOAK_HEALTH_MS": os.environ.get(
                "SOAK_HEALTH_MS", "100" if args.groups <= 32 else "250"
            ),
        })
        ranks.append(Rank(i, env, base))

    counts = {
        "rounds": 0, "kills": 0, "sigstops": 0, "netsplits": 0,
        "recycles": 0, "xfers": 0, "converges": 0,
    }
    failure = None
    mttr, recovery_actions, opened = {}, {}, {}
    n_ops = 0
    lin_ok = True
    obs_nid = {cid: 4 for cid in sample_cids}
    next_nid = 5
    recycle_i = 0
    t0 = time.time()
    deadline = t0 + args.minutes * 60
    try:
        for r in ranks:
            r.start()
        for r in ranks:
            r.expect("READY", 240)
        # initial elections across all groups (load is paused until
        # after SETUP) — 100 groups x 3-4 replicas on one vCPU elect
        # much slower than the smoke shape
        time.sleep(10.0 if groups <= 32 else 25.0)
        ranks[0].send("SETUP")
        setup = ranks[0].expect("SETUP", 900)
        if not setup.get("ok"):
            raise RuntimeError(
                f"churn setup incomplete: {setup.get('errors')}"
            )
        for r in ranks:
            r.send("RESUME")
            r.expect("RESUMED", 30)
        time.sleep(5.0)  # witness/observer catch-up under load

        def _xfer(rk, cid, target):
            rk.send(f"XFER {cid} {target}")
            rep = rk.expect("XFER", 20)
            if rep.get("issued"):
                counts["xfers"] += 1

        while counts["rounds"] < 2 or time.time() < deadline:
            rnd = counts["rounds"] + 1
            # ---- leader-flap storm: bounce the flap groups 1<->2.  The
            # drive goes only to the flapping pair's hosts — once the
            # recovery plane transfers leadership OUT of the pair the
            # remaining drive no-ops and the flap dies; with recovery
            # off it churns for the whole phase.
            print(f"# t+{time.time() - t0:.0f}s round {rnd}: flap storm",
                  file=sys.stderr)
            for cid in flap_cids:  # land leadership inside the pair first
                for rk in ranks[:3]:
                    _xfer(rk, cid, 1)
            time.sleep(1.5)
            # 24 ticks ≈ 19s: long enough that an OFF-arm event must
            # outlast the storm while the ON arm's escape transfer
            # (plus one cooldown-spaced retry if the first fails to
            # land) kills it mid-phase — the measured MTTR gap IS this
            # difference
            for tick in range(24):
                target = 2 if tick % 2 == 0 else 1
                rk = ranks[0] if target == 2 else ranks[1]
                for cid in flap_cids:
                    _xfer(rk, cid, target)
                time.sleep(0.8)
            time.sleep(10.0)  # settle: flap windows slide shut
            # ---- netsplit the third voter host (the quorum_at_risk arm:
            # recovery evicts the dead voter and promotes the observer).
            # hier arm: the wave is domain-CORRELATED — rank3 (the other
            # domain-B host) goes down with it, so every commit closed
            # during the hold closed through domain A's sub-quorum and
            # the final linearizability gate scores exactly those
            split_victims = [ranks[2]]
            if getattr(args, "hier", False):
                split_victims.append(ranks[3])
            print(
                f"# t+{time.time() - t0:.0f}s round {rnd}: netsplit "
                f"rank{'2+3' if len(split_victims) > 1 else '2'} for 12s",
                file=sys.stderr,
            )
            if any(
                _set_split(ranks, addr_list, v, True)
                for v in split_victims
            ):
                counts["netsplits"] += 1
            time.sleep(12.0)
            for v in split_victims:
                _set_split(ranks, addr_list, v, False)
            time.sleep(6.0)
            # ---- SIGSTOP freeze: silence without death
            print(
                f"# t+{time.time() - t0:.0f}s round {rnd}: SIGSTOP "
                "rank1 for 4s", file=sys.stderr,
            )
            if ranks[1].alive():
                ranks[1].pause()
                time.sleep(4.0)
                ranks[1].resume()
                counts["sigstops"] += 1
            time.sleep(3.0)
            if rnd % 2 == 1:
                # ---- membership recycle: retire + re-register standbys
                for k in range(2):
                    cid = sample_cids[
                        (recycle_i + k) % len(sample_cids)
                    ]
                    old, new = obs_nid[cid], next_nid
                    ranks[0].send(f"RECYCLE {cid} {old} {new}")
                    rep = ranks[0].expect("RECYCLE", 60)
                    if rep.get("ok"):
                        ranks[3].send(f"REJOIN {cid} {new}")
                        rep2 = ranks[3].expect("REJOIN", 60)
                        if rep2.get("ok"):
                            obs_nid[cid] = new
                            next_nid += 1
                            counts["recycles"] += 1
                    else:
                        print(
                            f"# recycle {cid} skipped: {rep.get('error')}",
                            file=sys.stderr,
                        )
                recycle_i += 2
            else:
                # ---- kill -9 + restart: WAL replay under churn
                print(
                    f"# t+{time.time() - t0:.0f}s round {rnd}: "
                    "kill -9 rank1", file=sys.stderr,
                )
                ranks[1].kill9()
                counts["kills"] += 1
                time.sleep(rng.uniform(3, 6))
                ranks[1].start()
                ranks[1].expect("READY", 240)
                ranks[1].send("RESUME")  # churn ranks boot paused
                ranks[1].expect("RESUMED", 30)
                time.sleep(3.0)
            _churn_converge(ranks, groups)
            counts["converges"] += 1
            counts["rounds"] = rnd

        # final: quiet long enough for open windows to close, settle
        # strictly among max-applied members, score, stop, lin-check
        print("# final settle + converge", file=sys.stderr)
        time.sleep(10.0)
        _churn_converge(ranks, groups, timeout=240.0, settle=True)
        counts["converges"] += 1
        durs, open_ages, opened, recovery_actions = _collect_recov(ranks)
        mttr = _mttr_stats(durs, open_ages)
        for r in ranks:
            if r.alive():
                r.send("EXIT")
        for r in ranks:
            try:
                r.proc.wait(timeout=30)
            except Exception:
                r.proc.kill()
        lin_ok, bad, n_ops = _check_histories(base, groups)
        if not lin_ok:
            failure = f"history not linearizable on keys {bad[:8]}"
    except Exception as e:  # noqa: BLE001 — summarize, keep artifacts
        failure = f"{type(e).__name__}: {e}"
        lin_ok = False
    finally:
        for r in ranks:
            try:
                if r.alive():
                    r.proc.kill()
            except Exception:
                pass

    summary = {
        "churn_ok": failure is None,
        "recover": bool(args.recover),
        "hier": bool(getattr(args, "hier", False)),
        "seed": seed,
        "minutes": args.minutes,
        "groups": groups,
        "witness_groups": len(witness_cids),
        **counts,
        "history_ops": n_ops,
        "linearizable": bool(lin_ok) and failure is None,
        "detectors_opened": opened,
        "recovery_actions": recovery_actions,
        "mttr": mttr,
        "error": failure,
        "artifacts": base if (failure or args.keep) else None,
    }
    if failure is None and not args.keep:
        import shutil

        shutil.rmtree(base, ignore_errors=True)
    print(json.dumps(summary))
    return 0 if failure is None else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=10.0)
    ap.add_argument("--groups", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keep", action="store_true",
                    help="keep the run dir even on success")
    ap.add_argument("--churn", action="store_true",
                    help="BlackWater churn soak (ISSUE 17): 4 hosts, "
                         "witness quorums, MTTR-scored round schedule")
    ap.add_argument("--recover", action="store_true",
                    help="churn mode: arm the closed-loop recovery plane "
                         "(the A/B ON arm)")
    ap.add_argument("--hier", action="store_true",
                    help="churn mode: hierarchical commit plane ON "
                         "(ISSUE 18) with 2+2 domains and the netsplit "
                         "wave taking domain B down whole")
    args = ap.parse_args()
    if args.churn:
        return churn_main(args)

    rng = random.Random(args.seed or int(time.time()))
    base = tempfile.mkdtemp(prefix="dbtpu-soak-")
    ports = _ports(3)
    addrs = ",".join(f"127.0.0.1:{p}" for p in ports)
    print(f"# soak: {args.minutes} min, {args.groups} groups, dir {base}",
          file=sys.stderr)

    ranks = []
    for i in range(3):
        env = dict(os.environ)
        env.update({
            "SOAK_RANK": str(i), "SOAK_GROUPS": str(args.groups),
            "SOAK_ADDRS": addrs, "SOAK_DIR": base,
        })
        ranks.append(Rank(i, env, base))
    t0 = time.time()
    deadline = t0 + args.minutes * 60
    kills = 0
    pauses = 0
    splits = 0
    converges = 0
    failure = None
    try:
        for r in ranks:
            r.start()
        for r in ranks:
            r.expect("READY", 120)
        time.sleep(5.0)  # initial elections + load ramp

        next_kill = time.time() + rng.uniform(10, 25)
        next_pause = time.time() + rng.uniform(20, 35)
        next_split = time.time() + rng.uniform(25, 40)
        next_converge = time.time() + 30.0
        addr_list = addrs.split(",")

        def set_split(victim, on):
            return _set_split(ranks, addr_list, victim, on)
        while time.time() < deadline:
            time.sleep(1.0)
            now = time.time()
            if now >= next_pause:
                # partition-freeze fault: SIGSTOP a rank for 2-6s (long
                # enough to cross election timeouts sometimes), then wake
                # it into a world that moved on — exercises check-quorum,
                # elections without a crash, post-wake stale-term traffic
                # and fast-lane eject/re-enroll on both sides
                victim = rng.choice(ranks)
                dur = rng.uniform(2, 6)
                print(f"# t+{now - t0:.0f}s SIGSTOP rank{victim.idx} "
                      f"for {dur:.1f}s", file=sys.stderr)
                victim.pause()
                time.sleep(dur)
                victim.resume()
                pauses += 1
                next_pause = time.time() + rng.uniform(20, 45)
            if now >= next_split:
                victim = rng.choice(ranks)
                dur = rng.uniform(2, 8)
                print(f"# t+{now - t0:.0f}s netsplit rank{victim.idx} "
                      f"for {dur:.1f}s", file=sys.stderr)
                injected = set_split(victim, True)
                time.sleep(dur)
                set_split(victim, False)
                if injected:  # only count splits that actually happened
                    splits += 1
                next_split = time.time() + rng.uniform(25, 50)
            if now >= next_kill:
                victim = rng.choice(ranks)
                print(f"# t+{now - t0:.0f}s kill -9 rank{victim.idx}",
                      file=sys.stderr)
                victim.kill9()
                kills += 1
                time.sleep(rng.uniform(2, 8))
                victim.start()
                victim.expect("READY", 180)
                next_kill = time.time() + rng.uniform(15, 40)
            if now >= next_converge:
                print(f"# t+{now - t0:.0f}s converge check", file=sys.stderr)
                _converge_check(ranks, args.groups)
                converges += 1
                next_converge = time.time() + rng.uniform(30, 60)

        # final: settle, converge, stop cleanly, check histories
        print("# final converge", file=sys.stderr)
        reports = _converge_check(ranks, args.groups, timeout=120.0)
        converges += 1
        enrolled = [rep.get("enrolled", 0) for rep in reports]
        for r in ranks:
            if r.alive():
                r.send("EXIT")
        for r in ranks:
            try:
                r.proc.wait(timeout=20)
            except Exception:
                r.proc.kill()
        ok, bad, n_ops = _check_histories(base, args.groups)
        if not ok:
            failure = f"history not linearizable on keys {bad[:8]}"
    except Exception as e:  # noqa: BLE001 — summarize, keep artifacts
        failure = f"{type(e).__name__}: {e}"
        ok = False
        n_ops = 0
        enrolled = []
    finally:
        for r in ranks:
            try:
                if r.alive():
                    r.proc.kill()
            except Exception:
                pass

    summary = {
        "soak_ok": failure is None,
        "minutes": args.minutes,
        "groups": args.groups,
        "kills": kills,
        "pauses": pauses,
        "netsplits": splits,
        "converge_checks": converges,
        "history_ops": n_ops,
        "enrolled_final": enrolled,
        "error": failure,
        "artifacts": base if (failure or args.keep) else None,
    }
    if failure is None and not args.keep:
        import shutil

        shutil.rmtree(base, ignore_errors=True)
    print(json.dumps(summary))
    return 0 if failure is None else 1


if __name__ == "__main__":
    if "--rank" in sys.argv:
        sys.exit(rank_main())
    sys.exit(main())
