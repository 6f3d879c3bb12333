"""Control plane for the multi-process host tier: spawn, handshake,
heartbeat, crash detection/restart, drain-and-stop.

The :class:`HostProcPlane` owns every shared-memory ring and every
worker process.  Topology: ``encode`` lanes (one per ingress staging
shard) and one ``wal`` lane ride the first workers round-robin; every
worker additionally serves one ``apply`` lane (state-machine proxies
shard onto them by cluster id).  All lanes are request/response ring
PAIRS; the host side of a pair is a :class:`RingClient` whose per-call
lock makes it the ring's single logical producer.

Failure contract (the design's robustness half, not an afterthought):

- a worker that exits — crash, kill -9, OOM — is detected by the
  monitor thread (``Process`` liveness + a shared-memory heartbeat
  stamp); its lanes flip ``alive=False`` and every in-flight waiter is
  woken to raise :class:`WorkerGone`;
- callers FALL BACK IN-PROCESS on ``WorkerGone``: the ingress batcher
  encodes inline, the journal appends+fsyncs on the flush leader's
  thread, and SM proxies rebuild from their snapshot+redo buffer
  (``sm.ProcStateMachine``) — nothing acked-before-fsync is ever
  violated because the ack only happens after SOME fsync returned, and
  an ambiguous worker-side append is simply re-appended (journal replay
  is idempotent);
- the monitor respawns dead workers (bounded by ``MAX_RESTARTS``) after
  RESETTING their rings, so a fresh worker never replays a dead one's
  backlog; lanes re-arm with a bumped ``epoch`` — stateful users (SM
  proxies) observe the epoch change and stay fallen-back, stateless
  users (encode, WAL) simply resume;
- a ring that stays full past the producer's busy window raises
  :class:`dragonboat_tpu.requests.SystemBusyError` — the same
  backpressure surface as a full ingress staging ring;
- ``stop()`` drains deterministically: callers are quiesced first by
  the NodeHost (hostplane stops before hostproc), each worker gets an
  ``OP_STOP`` it answers after finishing its backlog, and only then is
  the process tree joined/terminated and the segments unlinked.
"""
from __future__ import annotations

import multiprocessing
import threading
import time
from typing import Dict, List, Optional

from ..logger import get_logger
from ..requests import SystemBusyError
from . import workers as wp
from .rings import RingClosed, ShmRing

plog = get_logger("hostproc")


class WorkerGone(RuntimeError):
    """The lane's worker is dead/unreachable — fall back in-process."""


class WorkerError(OSError):
    """The worker executed the op and reported a failure (e.g. a real —
    or injected — fsync error).  NOT a fallback signal: the op genuinely
    failed, exactly as it would have in-process."""


class RingClient:
    """Host-side endpoint of one lane (request ring + response ring +
    doorbells).  ``call`` is one synchronous round trip; the internal
    lock admits one outstanding request per lane, which keeps seq
    correlation FIFO and the shared-memory side strictly SPSC."""

    __slots__ = ("plane", "role", "req", "resp", "worker_id", "alive",
                 "epoch", "_mu", "_seq", "calls", "wall_us_total")

    def __init__(self, plane, role: str, req: ShmRing, resp: ShmRing,
                 worker_id: int):
        self.plane = plane
        self.role = role
        self.req = req
        self.resp = resp
        self.worker_id = worker_id
        self.alive = False
        self.epoch = 0
        self._mu = threading.Lock()
        self._seq = 0
        self.calls = 0
        self.wall_us_total = 0

    def call(self, op: int, body: bytes = b"", timeout: float = 10.0,
             busy_timeout: float = 0.05) -> bytes:
        """One round trip.  Raises :class:`SystemBusyError` when the
        request ring stays full past ``busy_timeout`` (sustained-full
        backpressure), :class:`WorkerGone` when the worker is dead or
        unresponsive past ``timeout``, :class:`WorkerError` when the
        worker reports the op failed."""
        try:
            return self._call_locked(op, body, timeout, busy_timeout)
        except RingClosed as e:
            # plane stopped underneath the caller: same fallback
            # surface as a dead worker
            raise WorkerGone(str(e)) from e

    def _call_locked(self, op: int, body: bytes, timeout: float,
                     busy_timeout: float) -> bytes:
        with self._mu:
            if not self.alive:
                raise WorkerGone(f"{self.role} worker {self.worker_id} down")
            self._seq = seq = (self._seq + 1) & 0xFFFFFFFF
            rec = wp.pack_req(op, seq, body)
            if 4 + len(rec) > self.req.cap:
                # an oversized payload can never fit this ring: surface
                # the in-process fallback path, not a crash (a journal
                # cycle or SM snapshot larger than the ring is legal)
                raise WorkerGone(
                    f"{self.role} request of {len(rec)}B exceeds ring "
                    f"capacity {self.req.cap}"
                )
            deadline = time.perf_counter() + busy_timeout
            spins = 0
            while not self.req.push(rec):
                if not self.alive:
                    # checked INSIDE the loop so the monitor can safely
                    # reset a dead worker's rings: it takes _mu first,
                    # and any in-flight producer drains out through
                    # this check instead of writing over the reset
                    raise WorkerGone(
                        f"{self.role} worker {self.worker_id} died mid-push"
                    )
                if time.perf_counter() > deadline:
                    self.plane._count_busy(self.role)
                    raise SystemBusyError()
                spins += 1
                time.sleep(0 if spins < 100 else 0.0005)
            deadline = time.perf_counter() + timeout
            spins = 0
            while True:
                blob = self.resp.pop()
                if blob is not None:
                    _op, rseq, status, wall_us, rbody = wp.unpack_resp(blob)
                    if rseq != seq:
                        # stale response from a timed-out earlier call on
                        # this lane — discard and keep draining (seqs are
                        # FIFO, ours is still ahead)
                        continue
                    break
                if not self.alive:
                    raise WorkerGone(
                        f"{self.role} worker {self.worker_id} died mid-call"
                    )
                if time.perf_counter() > deadline:
                    raise WorkerGone(
                        f"{self.role} worker {self.worker_id} unresponsive"
                    )
                spins += 1
                if spins < 200:
                    time.sleep(0)
                else:
                    # tiered sleep-poll, NOT a semaphore doorbell: a
                    # kill -9'd worker can die holding a posix-sem
                    # event's lock and deadlock every later set()/wait()
                    time.sleep(0.0002 if spins < 1000 else 0.001)
            self.calls += 1
            self.wall_us_total += wall_us
        obs = self.plane._obs
        if obs is not None:
            obs.call(self.role, wall_us / 1e3)
        if status != wp.ST_OK:
            raise WorkerError(rbody.decode("utf-8", "replace"))
        return rbody

    def depth(self) -> int:
        try:
            return self.req.depth() + self.resp.depth()
        except Exception:
            return 0


class EncodeLane:
    """Ingress-batcher facing wrapper: encode one command burst on the
    worker; ``None`` means fall back to the inline encode (worker gone
    or ring busy — the staging-ring cap stays the client-visible
    backpressure surface)."""

    __slots__ = ("_c",)

    def __init__(self, client: RingClient):
        self._c = client

    def encode(self, ct: int, cmds) -> Optional[list]:
        c = self._c
        if not c.alive:
            return None
        try:
            out = c.call(
                wp.OP_ENCODE, bytes([ct]) + wp.pack_cmds(cmds),
                timeout=5.0, busy_timeout=0.01,
            )
        except (WorkerGone, SystemBusyError):
            c.plane._count_fallback("encode")
            return None
        except WorkerError:
            c.plane._count_fallback("encode")
            return None
        encs, _ = wp.unpack_cmds(out)
        return encs


class WalSink:
    """Journal-facing wrapper (see ``logdb.journal.HostJournal.sink``):
    ``append``/``truncate`` return True when the worker performed the
    durable op, False when the worker tier is unavailable (the journal
    falls back to its own in-process write+fsync), and raise
    :class:`WorkerError` (an ``OSError``) when the worker REALLY failed
    the op — that failure propagates to the flush cycle exactly like an
    in-process fsync error, so nothing is acked."""

    __slots__ = ("_c", "_opened_epoch")

    def __init__(self, client: RingClient):
        self._c = client
        self._opened_epoch = -1

    def _ensure_open(self, path: str) -> bool:
        c = self._c
        if self._opened_epoch == c.epoch:
            return True
        c.call(wp.OP_WAL_OPEN, path.encode("utf-8"), timeout=10.0)
        self._opened_epoch = c.epoch
        return True

    def append(self, path: str, rec: bytes) -> bool:
        c = self._c
        if not c.alive:
            return False
        try:
            self._ensure_open(path)
            c.call(wp.OP_WAL_APPEND, rec, timeout=30.0, busy_timeout=0.25)
            return True
        except (WorkerGone, SystemBusyError):
            c.plane._count_fallback("wal")
            return False
        # WorkerError propagates: the op ran and failed (real or
        # injected fsync error) — the flush cycle must fail, not ack

    def truncate(self, path: str, expected_bytes: int = 0) -> bool:
        """Size-guarded: the worker refuses when the file is not exactly
        ``expected_bytes`` long (a stale abandoned truncate executing
        late would otherwise wipe acked records) — the refusal comes
        back as WorkerError and the journal falls back to its own
        in-process truncate."""
        c = self._c
        if not c.alive:
            return False
        try:
            self._ensure_open(path)
            c.call(
                wp.OP_WAL_TRUNC,
                wp._U64.pack(max(0, expected_bytes)),
                timeout=30.0, busy_timeout=0.25,
            )
            return True
        except (WorkerGone, SystemBusyError):
            c.plane._count_fallback("wal")
            return False
        except WorkerError:
            c.plane._count_fallback("wal")
            return False

    @property
    def attached(self) -> bool:
        return self._c.alive


class _WorkerRec:
    __slots__ = ("wid", "proc", "hb", "pairs", "restarts", "down")

    def __init__(self, wid):
        self.wid = wid
        self.proc = None
        self.hb = None
        self.pairs: List[RingClient] = []
        self.restarts = 0
        self.down = False


class HostProcPlane:
    """Spawn + own the worker tier.  Built by NodeHost when
    ``ExpertConfig.host_workers > 0``; everything here is absent at the
    default 0 (the in-process host plane is structurally untouched)."""

    #: bounded respawns per worker — a crash-looping worker devolves to
    #: the in-process path instead of burning cores on restarts
    MAX_RESTARTS = 3
    #: heartbeat staleness that earns a warning (NOT a kill: a worker
    #: blocked in a long fsync is slow, not dead — Process liveness is
    #: the authoritative death signal)
    HB_STALE_S = 15.0

    def __init__(self, workers: int = 1, encode_lanes: int = 2,
                 ring_bytes: int = 1 << 20, spawn_timeout: float = 60.0):
        import os as _os

        self.nworkers = max(1, int(workers))
        # topology-adaptive engagement: a cross-process round trip costs
        # 1-2 scheduling quanta, so stage offload pays only when spare
        # cores can hide it — on a single-core box every tier would
        # time-slice the serving process and LOSE throughput (measured
        # ~0.2x on the sessions axis), so the default there is
        # spawn-but-idle (crash-safe plumbing stays testable, the ledger
        # records the limitation).  DBTPU_HOSTPROC_OFFLOAD=1 forces full
        # engagement (differential tests, perf experiments); the WAL
        # sink additionally self-engages when the durability barrier
        # dwarfs the handoff (see GroupCommitWAL).
        self.offload_default = (
            (_os.cpu_count() or 1) > 1
            or _os.environ.get("DBTPU_HOSTPROC_OFFLOAD") == "1"
        )
        self._ctx = multiprocessing.get_context("spawn")
        self._obs = None
        self._stopping = False
        self._mu = threading.Lock()
        self._busy: Dict[str, int] = {}
        self._fallbacks: Dict[str, int] = {}
        self._monitor: Optional[threading.Thread] = None
        self.restarts_total = 0
        self._workers = [_WorkerRec(i) for i in range(self.nworkers)]
        self.encode_lanes: List[RingClient] = []
        self.wal_lane: Optional[RingClient] = None
        self.apply_lanes: List[RingClient] = []
        # ---- lanes ----
        def mk_lane(role, wid):
            c = RingClient(
                self, role,
                ShmRing(capacity=ring_bytes),
                ShmRing(capacity=ring_bytes),
                wid,
            )
            self._workers[wid].pairs.append(c)
            return c

        for i in range(max(1, encode_lanes)):
            self.encode_lanes.append(mk_lane("encode", i % self.nworkers))
        self.wal_lane = mk_lane("wal", 0)
        for i in range(self.nworkers):
            self.apply_lanes.append(mk_lane("apply", i))
        # ---- spawn + handshake ----
        for rec in self._workers:
            self._spawn(rec)
        deadline = time.monotonic() + spawn_timeout
        for rec in self._workers:
            while rec.hb.value == 0.0 and rec.proc.exitcode is None:
                if time.monotonic() > deadline:
                    break
                time.sleep(0.005)
            if rec.hb.value == 0.0:
                self.stop()
                raise RuntimeError(
                    f"hostproc worker {rec.wid} failed its spawn handshake"
                )
            for c in rec.pairs:
                c.alive = True
        self._monitor = threading.Thread(
            target=self._monitor_main, name="hostproc-monitor", daemon=True
        )
        self._monitor.start()
        plog.info(
            "hostproc plane up: %d workers, %d encode lanes, 1 wal lane, "
            "%d apply lanes", self.nworkers, len(self.encode_lanes),
            len(self.apply_lanes),
        )

    # ---- spawn / respawn ----

    def _spawn(self, rec: _WorkerRec) -> None:
        # the heartbeat is a LOCKLESS shared double (raw shared memory):
        # nothing here is semaphore-backed, so a kill -9'd worker cannot
        # strand a lock the host would later block on.  Its first stamp
        # doubles as the spawn handshake.
        rec.hb = self._ctx.Value("d", 0.0, lock=False)
        specs = [(c.req.name, c.resp.name) for c in rec.pairs]
        proc = self._ctx.Process(
            target=wp.worker_main,
            args=(rec.wid, specs, rec.hb),
            name=f"hostproc-worker-{rec.wid}",
            daemon=True,
        )
        proc.start()
        # published only once started: stop() must never join a Process
        # the monitor's respawn has made and not yet started
        rec.proc = proc

    def _monitor_main(self) -> None:
        warned_stale = set()
        while not self._stopping:
            time.sleep(0.15)
            if self._stopping:
                return
            try:
                self._monitor_tick(warned_stale)
            except Exception:
                # the monitor IS the crash detector — it must survive
                # its own failures (spawn OSError under fd pressure, a
                # segment closed by a concurrent stop) or dead workers
                # stop being detected and every call eats its full
                # timeout instead of failing fast to the fallback
                plog.exception("hostproc monitor tick failed")

    def _monitor_tick(self, warned_stale) -> None:
            for rec in self._workers:
                p = rec.proc
                if p is None:
                    continue
                if p.exitcode is not None and not rec.down:
                    # death: poison the lanes FIRST (wake any in-flight
                    # waiter into WorkerGone), then decide on respawn
                    rec.down = True
                    for c in rec.pairs:
                        c.alive = False  # in-flight waiters poll this
                    plog.warning(
                        "hostproc worker %d exited (code %s); lanes fell "
                        "back in-process", rec.wid, p.exitcode,
                    )
                    obs = self._obs
                    if obs is not None:
                        # the dead lane's rings still hold its ghost
                        # backlog until the respawn resets them —
                        # ring_depth() excludes down lanes, so republish
                        # NOW or a scrape between death and respawn
                        # (forever, when MAX_RESTARTS is exhausted)
                        # keeps showing the dead epoch's bytes
                        obs.workers_alive(self.alive_count())
                        obs.ring_depth(self.ring_depth())
                    if self._stopping or rec.restarts >= self.MAX_RESTARTS:
                        continue
                    rec.restarts += 1
                    self.restarts_total += 1
                    if obs is not None:
                        obs.restart()
                    # a fresh worker must not replay the dead one's
                    # backlog: reset ring cursors while nothing is
                    # attached — under each client's call lock, so an
                    # in-flight producer (which re-checks ``alive``
                    # every push/pop iteration) has fully drained out
                    # before the cursors move
                    for c in rec.pairs:
                        with c._mu:
                            c.req.reset()
                            c.resp.reset()
                    self._spawn(rec)
                    hs = time.monotonic() + 30.0
                    while (rec.hb.value == 0.0
                           and rec.proc.exitcode is None
                           and not self._stopping
                           and time.monotonic() < hs):
                        time.sleep(0.01)
                    if rec.hb.value:
                        rec.down = False
                        for c in rec.pairs:
                            c.epoch += 1   # stateful users stay fallen-back
                            c.alive = True
                        plog.info("hostproc worker %d respawned", rec.wid)
                        if obs is not None:
                            # epoch bump: fresh rings, fresh epoch —
                            # republish both gauges so the scrape flips
                            # with the lane, not a monitor period later
                            obs.workers_alive(self.alive_count())
                            obs.ring_depth(self.ring_depth())
                    elif not self._stopping:
                        plog.error(
                            "hostproc worker %d respawn handshake failed",
                            rec.wid,
                        )
                elif p.exitcode is None and rec.hb.value:
                    stale = time.monotonic() - rec.hb.value
                    if stale > self.HB_STALE_S and rec.wid not in warned_stale:
                        warned_stale.add(rec.wid)
                        plog.warning(
                            "hostproc worker %d heartbeat stale %.1fs "
                            "(blocked in a long op?)", rec.wid, stale,
                        )
                    elif stale < self.HB_STALE_S:
                        warned_stale.discard(rec.wid)
            obs = self._obs
            if obs is not None:
                obs.ring_depth(self.ring_depth())

    # ---- lane accessors ----

    def encode_lane(self, shard_idx: int) -> EncodeLane:
        return EncodeLane(self.encode_lanes[shard_idx % len(self.encode_lanes)])

    def wal_sink(self) -> WalSink:
        return WalSink(self.wal_lane)

    def apply_client(self, cluster_id: int) -> RingClient:
        return self.apply_lanes[cluster_id % len(self.apply_lanes)]

    # ---- counters / obs ----

    def _count_busy(self, role: str) -> None:
        with self._mu:
            self._busy[role] = self._busy.get(role, 0) + 1
        obs = self._obs
        if obs is not None:
            obs.ring_full(role)

    def _count_fallback(self, role: str) -> None:
        with self._mu:
            self._fallbacks[role] = self._fallbacks.get(role, 0) + 1
        obs = self._obs
        if obs is not None:
            obs.fallback(role)

    def enable_obs(self, registry=None):
        from ..obs.instruments import HostProcObs

        if self._obs is None or registry is not None:
            self._obs = HostProcObs(registry=registry)
            self._obs.workers_alive(self.alive_count())
        return self._obs

    def alive_count(self) -> int:
        return sum(
            1 for r in self._workers
            if r.proc is not None and r.proc.exitcode is None and not r.down
        )

    def ring_depth(self) -> int:
        """Bytes staged across LIVE lanes' shared-memory rings.  Dead
        lanes are excluded (ISSUE 13 satellite): their rings hold the
        dead epoch's ghost backlog until the respawn resets the
        cursors — or forever when the restart budget is exhausted —
        and a scrape must never read that as live depth."""
        total = 0
        for r in self._workers:
            p = r.proc
            if r.down or p is None or p.exitcode is not None:
                continue
            total += sum(c.depth() for c in r.pairs)
        return total

    def health_snapshot(self) -> dict:
        """Worker-tier health for the cluster health sampler (ISSUE 13):
        liveness, restart counts and per-worker heartbeat age (the
        lockless shared-double the monitor already watches)."""
        now = time.monotonic()
        per_worker = []
        for r in self._workers:
            p = r.proc
            alive = p is not None and p.exitcode is None and not r.down
            hb = r.hb.value
            per_worker.append({
                "wid": r.wid,
                "alive": alive,
                "restarts": r.restarts,
                "hb_age_s": round(now - hb, 3) if (alive and hb) else None,
            })
        return {
            "workers": self.nworkers,
            "alive": self.alive_count(),
            "restarts": self.restarts_total,
            "ring_depth": self.ring_depth(),
            "per_worker": per_worker,
        }

    def worker_pid(self, wid: int) -> Optional[int]:
        p = self._workers[wid].proc
        return p.pid if p is not None else None

    def inject(self, wid: int, faults: dict) -> None:
        """Test hook: ship an OP_INJECT fault dict to one worker (e.g.
        ``{"wal_fail_fsyncs": 2}`` or ``{"die": True}``)."""
        import json

        self._workers[wid].pairs[0].call(
            wp.OP_INJECT, json.dumps(faults).encode("utf-8"), timeout=10.0
        )

    def stats(self) -> dict:
        lanes = {}
        for role, cs in (
            ("encode", self.encode_lanes),
            ("wal", [self.wal_lane]),
            ("apply", self.apply_lanes),
        ):
            lanes[role] = {
                "calls": sum(c.calls for c in cs),
                "wall_ms": round(sum(c.wall_us_total for c in cs) / 1e3, 3),
            }
        with self._mu:
            busy = dict(self._busy)
            fallbacks = dict(self._fallbacks)
        return {
            "workers": self.nworkers,
            "alive": self.alive_count(),
            "restarts": self.restarts_total,
            "ring_depth": self.ring_depth(),
            "busy": busy,
            "fallbacks": fallbacks,
            "lanes": lanes,
        }

    # ---- lifecycle ----

    def stop(self) -> None:
        """Drain-and-stop: callers were quiesced by the NodeHost (the
        in-process host plane stops first), so each worker's backlog is
        at most what it is already draining; OP_STOP makes it finish
        that backlog, answer, and exit before we join/terminate."""
        if self._stopping:
            return
        self._stopping = True
        # the monitor first: a respawn it has in flight ends (its
        # handshake wait sees _stopping) before the workers are stopped,
        # so the one stopped below is the one that exists
        if self._monitor is not None and self._monitor.is_alive():
            self._monitor.join(timeout=5.0)
        for rec in self._workers:
            p = rec.proc
            if p is None:
                continue
            if p.exitcode is None:
                try:
                    rec.pairs[0].call(
                        wp.OP_STOP, timeout=2.0, busy_timeout=0.1
                    )
                except Exception:
                    pass
                p.join(2.0)
            if p.exitcode is None:
                p.terminate()
                p.join(1.0)
            if p.exitcode is None:
                p.kill()
                p.join(1.0)
            for c in rec.pairs:
                c.alive = False
        for rec in self._workers:
            for c in rec.pairs:
                c.req.close()
                c.resp.close()
