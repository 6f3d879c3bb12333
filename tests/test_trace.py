"""Cross-plane request tracing differential suite (ISSUE 9).

Contracts under test:

- trace-OFF structural identity: with ``trace_sample_every=0`` nothing
  is constructed — no tracer on the NodeHost/engine/node/coordinator,
  ``RequestState.trace`` stays None — with ``host_compartments`` both
  off and on;
- trace completeness: every sampled proposal's completed trace carries
  the full stage chain (propose → ingress → raft_step → wal → apply →
  egress, plus device_round on the tpu engine), including proposals
  committed by a FUSED K-batched round (linked recorder span is the
  fused dispatch) and proposals interleaved with a membership change
  (engine row recycle) mid-trace;
- the stage-level stall watchdog: a sampled request stuck in a stage by
  an injected WAL fsync failure (vfs.ErrorFS) auto-dumps its partial
  trace — plus the flight-recorder ring when one is attached;
- the Perfetto/Chrome export renders one request as ONE flow (s/t/f
  events sharing the trace id) across the stage slices.
"""
import json
import time

import pytest

from dragonboat_tpu import Config, NodeHostConfig, Result
from dragonboat_tpu import vfs
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.logdb import open_logdb
from dragonboat_tpu.logdb.kv import WalKV
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.obs import FlightRecorder
from dragonboat_tpu.obs.trace import Trace, Tracer
from dragonboat_tpu.events import MetricsRegistry
from dragonboat_tpu.requests import RequestState
from dragonboat_tpu.transport import ChanRouter, ChanTransport

from tests.loadwait import wait_until

RTT_MS = 5
CID = 910

WRITE_STAGES = {"ingress", "raft_step", "wal", "apply", "egress"}


class CounterSM:
    def __init__(self, cluster_id, node_id):
        self.count = 0

    def update(self, cmd):
        self.count += 1
        return Result(value=self.count)

    def lookup(self, query):
        return self.count

    def save_snapshot(self, w, files, done):
        w.write(self.count.to_bytes(8, "little"))

    def recover_from_snapshot(self, r, files, done):
        self.count = int.from_bytes(r.read(8), "little")

    def close(self):
        pass


def _mk_host(addr="tr:1", trace=0, engine="scalar", compartments=False,
             metrics=False, tmpdir=None, logdb_factory=None, fs=None,
             warm_fused=True):
    router = ChanRouter()
    return NodeHost(
        NodeHostConfig(
            node_host_dir=tmpdir or ":memory:",
            rtt_millisecond=RTT_MS,
            raft_address=addr,
            raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                s, rh, ch, router=router
            ),
            enable_metrics=metrics,
            trace_sample_every=trace,
            logdb_factory=logdb_factory,
            expert=ExpertConfig(
                quorum_engine=engine,
                engine_block_groups=64,
                engine_warm_fused=warm_fused,
                host_compartments=compartments,
                fs=fs,
            ),
        )
    )


def _start(nh, cid=CID):
    nh.start_cluster(
        {1: nh.raft_address()}, False, CounterSM,
        Config(cluster_id=cid, node_id=1, election_rtt=10, heartbeat_rtt=1),
    )
    wait_until(
        lambda: nh.get_leader_id(cid)[1], timeout=10.0, what="leader"
    )


def _stages(trace):
    return {e["stage"] for e in trace.to_dict()["events"]}


# ----------------------------------------------------------------------
# trace OFF: structural identity (compartments off AND on)
# ----------------------------------------------------------------------


def _assert_trace_off(nh):
    assert nh.tracer is None
    assert nh.engine.tracer is None
    node = nh.get_node(CID)
    assert node.tracer is None
    assert node.pending_reads._tracer is None
    if nh.quorum_coordinator is not None:
        assert nh.quorum_coordinator.tracer is None
    s = nh.get_noop_session(CID)
    states = [nh.propose(s, b"x", timeout=10.0)]
    states += nh.propose_batch(s, [b"y", b"y"], timeout=10.0)
    rrs = node.read(10.0)
    for rs in states + [rrs]:
        assert rs.wait(10.0).completed
        assert rs.trace is None  # the bit-identical latch


def test_trace_off_identity_compartments_off():
    nh = _mk_host(trace=0, compartments=False)
    try:
        _start(nh)
        _assert_trace_off(nh)
    finally:
        nh.stop()


def test_trace_off_identity_compartments_on():
    nh = _mk_host(trace=0, compartments=True)
    try:
        _start(nh)
        _assert_trace_off(nh)
    finally:
        nh.stop()


# ----------------------------------------------------------------------
# completeness: every sampled proposal carries the full stage chain
# ----------------------------------------------------------------------


def _drive_and_collect(nh, n=6):
    s = nh.get_noop_session(CID)
    states = [nh.propose(s, b"w", timeout=10.0) for _ in range(n // 2)]
    states += nh.propose_batch(s, [b"b"] * (n - n // 2), timeout=10.0)
    for rs in states:
        assert rs.wait(10.0).completed
    # egress stamps land inside notify, before wait() returns; finish()
    # moved each trace to the completed ring synchronously
    return [rs.trace for rs in states]


def test_completeness_scalar_engine():
    nh = _mk_host(trace=1)
    try:
        _start(nh)
        traces = _drive_and_collect(nh)
        for t in traces:
            assert type(t) is Trace and t.done
            assert _stages(t) >= WRITE_STAGES, t.to_dict()
        # reads: ingress -> raft_step -> read_confirm -> apply -> egress
        rrs = nh.get_node(CID).read(10.0)
        assert rrs.wait(10.0).completed
        assert _stages(rrs.trace) >= {
            "ingress", "read_confirm", "apply", "egress"
        }, rrs.trace.to_dict()
        # stage histograms published per stage
        reg = nh.metrics_registry
        for stage in WRITE_STAGES:
            h = reg.histogram_value(
                "dragonboat_trace_stage_seconds", {"stage": stage}
            )
            assert h is not None and h[3] > 0, stage
        assert reg.histogram_value("dragonboat_trace_e2e_seconds")[3] > 0
    finally:
        nh.stop()


def test_system_busy_reject_discards_contexts():
    """Regression (code review): a full ingress ring raises SystemBusy
    AFTER contexts attach but before the futures reach any tracker — no
    notify will ever finish them, so the tracer must discard them or
    they leak in flight forever (and trip the stall watchdog)."""
    router = ChanRouter()
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=":memory:",
            rtt_millisecond=RTT_MS,
            raft_address="tr:1",
            raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                s, rh, ch, router=router
            ),
            trace_sample_every=1,
            expert=ExpertConfig(
                host_compartments=True, host_ingress_ring=4,
            ),
        )
    )
    try:
        _start(nh)
        from dragonboat_tpu.requests import SystemBusyError

        s = nh.get_noop_session(CID)
        ing = nh.hostplane.ingress
        ing.pause()
        staged = []
        try:
            import pytest

            with pytest.raises(SystemBusyError):
                for _ in range(64):
                    staged.extend(nh.propose_batch(s, [b"x"], timeout=10.0))
        finally:
            ing.resume()
        for rs in staged:
            assert rs.wait(10.0).completed
        wait_until(
            lambda: not nh.tracer.inflight(), timeout=10.0,
            what="discarded/completed trace contexts",
        )
        assert nh.tracer.check_stalls() == 0
    finally:
        nh.stop()


def test_completeness_lease_read_short_path():
    """ISSUE 10: a read served under a leader lease shows the SHORT path
    — a ``lease_read`` stage in place of ``read_confirm`` (no echo-quorum
    round ran) — while a lease-off replica on the same build keeps the
    confirmed chain."""
    router = ChanRouter()

    def mk(i, trace):
        return NodeHost(
            NodeHostConfig(
                node_host_dir=":memory:",
                rtt_millisecond=RTT_MS,
                raft_address=f"lr{i}:1",
                raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                    s, rh, ch, router=router
                ),
                trace_sample_every=trace,
                expert=ExpertConfig(quorum_engine="scalar"),
            )
        )

    nhs = [mk(i, 1 if i == 1 else 0) for i in (1, 2, 3)]
    try:
        addrs = {i: f"lr{i}:1" for i in (1, 2, 3)}
        for i, nh in enumerate(nhs, start=1):
            nh.start_cluster(
                addrs, False, CounterSM,
                Config(
                    cluster_id=CID, node_id=i, election_rtt=10,
                    heartbeat_rtt=1, check_quorum=True, read_lease=True,
                ),
            )
        node1 = nhs[0].get_node(CID)

        def _drive_leader1():
            if node1.is_leader():
                return True
            lid, ok = node1.get_leader_id()
            if ok and lid != 1 and 1 <= lid <= 3:
                try:
                    nhs[lid - 1].request_leader_transfer(CID, 1)
                except Exception:
                    pass
            else:
                node1.request_campaign()
            return False

        wait_until(
            _drive_leader1, timeout=20.0, interval=0.2,
            what="leader on host 1",
        )
        s = nhs[0].get_noop_session(CID)
        rs = nhs[0].propose(s, b"x", timeout=10.0)
        assert rs.wait(10.0).completed
        wait_until(
            lambda: (nhs[0].lease_status(CID) or {}).get("held"),
            timeout=10.0, what="lease armed",
        )
        rrs = node1.read(10.0)
        assert rrs.wait(10.0).completed
        stages = _stages(rrs.trace)
        assert stages >= {"ingress", "lease_read", "apply", "egress"}, (
            rrs.trace.to_dict()
        )
        assert "read_confirm" not in stages
        # the stage histogram carries the new stage label (observations
        # flush to the registry once per tick — wait one out)
        wait_until(
            lambda: (
                nhs[0].metrics_registry.histogram_value(
                    "dragonboat_trace_stage_seconds",
                    {"stage": "lease_read"},
                )
                or (None,) * 4
            )[3],
            timeout=10.0, what="lease_read stage histogram flushed",
        )
    finally:
        for nh in nhs:
            nh.stop()


def test_completeness_compartments_ingress_path():
    """The compartmentalized path: bursts ride the ingress ring, the WAL
    stage lands at the group-commit flusher — the same stage chain must
    close."""
    nh = _mk_host(trace=1, compartments=True)
    try:
        _start(nh)
        for t in _drive_and_collect(nh):
            assert _stages(t) >= WRITE_STAGES, t.to_dict()
    finally:
        nh.stop()


def test_completeness_tpu_engine_device_round_and_recycle():
    """tpu engine: writes additionally carry the device_round stage with
    a linked recorder span; a membership change (engine row recycle)
    mid-stream must not break trace completeness on either side."""
    nh = _mk_host(trace=1, engine="tpu", metrics=True, warm_fused=False)
    try:
        _start(nh)
        before = _drive_and_collect(nh)
        # membership recycle mid-trace: an observer add commits a config
        # change and resyncs the engine row (tpuquorum membership_changed)
        nh.sync_request_add_observer(CID, 9, "trobs:1", timeout=10.0)
        after = _drive_and_collect(nh)
        for t in before + after:
            assert _stages(t) >= WRITE_STAGES | {"device_round"}, (
                t.to_dict()
            )
            assert t.spans, "device_round must link a recorder span seq"
        rec = nh.flight_recorder
        seqs = {s["seq"] for s in rec.spans()}
        linked = {seq for t in before + after for seq in t.spans}
        # linked seqs are real recorder spans (the ring may have evicted
        # the oldest; at capacity 512 in this test it has not)
        assert linked <= seqs | set(range(min(seqs, default=0))), (
            linked, max(seqs, default=-1)
        )
    finally:
        nh.stop()


def test_fused_round_links_fused_span():
    """Proposals committed by a fused K-batched round: hold the round
    lock, stage a tick backlog plus writes, release — the backlog replays
    as ONE fused dispatch and the traces' linked span is that fused
    span."""
    nh = _mk_host(trace=1, engine="tpu", metrics=True, warm_fused=False)
    try:
        _start(nh)
        qc = nh.quorum_coordinator
        # warm just the K=4 bucket synchronously (the full background
        # warm set is the live default; one bucket keeps the test fast)
        qc.eng.warmup_fused(k_buckets=(4,), background=False)
        assert qc.eng.fused_ready
        s = nh.get_noop_session(CID)
        fused_before = qc.fused_dispatches
        with qc._mu:  # block the round thread mid-loop
            states = nh.propose_batch(s, [b"f"] * 4, timeout=10.0)
            time.sleep(0.05)  # let raft step + ack staging land
            for _ in range(4):  # tick backlog -> deficit > 1
                qc.request_tick()
        for rs in states:
            assert rs.wait(10.0).completed
        wait_until(
            lambda: qc.fused_dispatches > fused_before, timeout=10.0,
            what="a fused dispatch",
        )
        rec = nh.flight_recorder
        by_seq = {sp["seq"]: sp for sp in rec.spans()}
        fused_linked = [
            by_seq[seq]
            for rs in states
            for seq in rs.trace.spans
            if seq in by_seq and by_seq[seq]["kind"] == "fused"
        ]
        assert fused_linked, [rs.trace.to_dict() for rs in states]
        assert any(sp["rounds"] > 1 for sp in fused_linked)
    finally:
        nh.stop()


# ----------------------------------------------------------------------
# stall watchdog: injected WAL stall dumps the partial trace
# ----------------------------------------------------------------------


def test_watchdog_dumps_partial_trace_on_wal_stall(tmp_path):
    """vfs.ErrorFS fails every fsync: a sampled proposal wedges after
    raft_step (its WAL flush cycle keeps failing), and the stage-level
    watchdog — driven by the NodeHost tick worker — auto-dumps the
    partial trace naming the stuck stage."""
    failing = [False]
    inj = vfs.Injector(lambda op, path: failing[0] and op == "fsync")
    efs = vfs.ErrorFS(vfs.OSFS(), inj)
    ldb_dir = str(tmp_path / "wal")

    def logdb_factory(nhc):
        return open_logdb(
            ldb_dir, shards=2,
            kv_factory=lambda d: WalKV(d, fsync=True, fs=efs),
        )

    nh = _mk_host(
        trace=1, compartments=True, tmpdir=str(tmp_path / "nh"),
        logdb_factory=logdb_factory, fs=efs,
    )
    try:
        _start(nh)
        s = nh.get_noop_session(CID)
        assert nh.sync_propose(s, b"pre", timeout=10.0).value == 1
        nh.tracer.stall_ms = 50.0
        failing[0] = True
        rs = nh.propose(s, b"stuck", timeout=60.0)
        assert not rs.wait(0.5).completed
        wait_until(
            lambda: nh.tracer.last_stall_dump is not None, timeout=10.0,
            what="trace stall auto-dump (tick worker)",
        )
        dump = nh.tracer.last_stall_dump
        assert "trace-stall" in dump["reason"]
        stuck = dump["trace"]
        stages = [e["stage"] for e in stuck["events"]]
        assert "wal" not in stages and "apply" not in stages, stages
        assert stuck["stalled"] in ("ingress", "raft_step"), stuck
        assert not stuck["done"]
        assert nh.metrics_registry.counter_value(
            "dragonboat_trace_stalls_total"
        ) >= 1
        # heal: the committer retry lands it and the trace completes
        failing[0] = False
        assert rs.wait(10.0).completed
        assert rs.trace.done
    finally:
        nh.stop()


def test_tracer_stall_dump_includes_recorder_ring():
    """Unit-level: when a FlightRecorder is attached the stall dump
    carries the recorder ring next to the partial trace."""
    rec = FlightRecorder(capacity=8, stall_ms=0)
    rec.record("dispatch", gate="acks", rounds=1)
    tr = Tracer(sample_every=1, registry=MetricsRegistry(), recorder=rec,
                stall_ms=5.0)
    try:
        rs = RequestState(key=77)
        tr.attach_one(rs, 3, time.perf_counter())
        tr.mark(rs, "ingress")
        time.sleep(0.02)
        assert tr.check_stalls() == 1
        d = tr.last_stall_dump
        assert d["trace"]["stalled"] == "ingress"
        assert d["recorder"]["spans"][0]["kind"] == "dispatch"
        # trips at most once per trace
        assert tr.check_stalls() == 0
    finally:
        tr.close()


# ----------------------------------------------------------------------
# export + debug dump
# ----------------------------------------------------------------------


def test_dump_trace_one_flow_per_request(tmp_path):
    """Acceptance: the exported Perfetto/Chrome trace renders a sampled
    proposal as one flow — ingress, WAL, device-round, apply and egress
    slices bound by s/t/f flow events sharing the trace id, with linked
    recorder spans on the device-plane track."""
    nh = _mk_host(trace=1, engine="tpu", metrics=True, warm_fused=False)
    try:
        _start(nh)
        traces = _drive_and_collect(nh, n=2)
        path = str(tmp_path / "trace.json")
        d = nh.dump_trace(path=path)
        with open(path) as f:
            assert json.load(f)["traceEvents"]  # valid JSON on disk
        evs = d["traceEvents"]
        tid = traces[0].tid
        slices = [
            e for e in evs
            if e["ph"] == "X" and e.get("args", {}).get("trace_id") == tid
        ]
        names = {e["name"] for e in slices}
        assert names >= WRITE_STAGES | {"device_round"}, names
        flow = [e for e in evs if e["ph"] in "stf" and e.get("id") == tid]
        phs = [e["ph"] for e in flow]
        assert phs[0] == "s" and phs[-1] == "f" and len(flow) >= 3
        # thread metadata names every tid used
        tids_used = {e["tid"] for e in slices}
        named = {
            e["tid"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert tids_used <= named
        # the device-plane track carries the linked recorder spans
        dev = [e for e in evs if e.get("cat") == "device"]
        assert dev and {s["args"]["seq"] for s in dev} >= set(
            traces[0].spans
        )
    finally:
        nh.stop()


def test_debug_dump_writes_recorder_and_traces(tmp_path):
    nh = _mk_host(trace=1, engine="tpu", metrics=True, warm_fused=False)
    try:
        _start(nh)
        _drive_and_collect(nh, n=2)
        path = nh.debug_dump(path=str(tmp_path / "dump.json"))
        with open(path) as f:
            d = json.load(f)
        assert d["recorder"]["spans"]
        assert d["traces"]["completed"] >= 2
        assert d["traces"]["traces"][0]["events"]
    finally:
        nh.stop()


def test_sigusr2_handler_dumps(tmp_path):
    """Opt-in SIGUSR2: raising the signal writes a timestamped dump file
    (and the old handler is restored at stop)."""
    import glob
    import os
    import signal

    old = signal.getsignal(signal.SIGUSR2)
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=str(tmp_path / "nh"),
            rtt_millisecond=RTT_MS,
            raft_address="sig:1",
            raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                s, rh, ch, router=ChanRouter()
            ),
            trace_sample_every=1,
            dump_signal=True,
        )
    )
    try:
        assert nh._dump_sig_old is not None or (
            signal.getsignal(signal.SIGUSR2) is old
        )
        _start(nh)
        s = nh.get_noop_session(CID)
        nh.sync_propose(s, b"x", timeout=10.0)
        os.kill(os.getpid(), signal.SIGUSR2)
        # the handler only flags; the tick worker performs the dump
        # (dumping inline in signal context would re-acquire
        # non-reentrant locks the interrupted frame may hold)
        def _dump():
            for path in glob.glob(str(tmp_path / "nh" / "dbtpu-dump-*.json")):
                with open(path) as f:
                    try:
                        return json.load(f)
                    except ValueError:
                        pass  # the tick worker is still writing it
            return None

        d = wait_until(_dump, timeout=5.0, what="SIGUSR2 dump file")
        assert d["traces"]["sampled"] >= 1
    finally:
        nh.stop()
    assert signal.getsignal(signal.SIGUSR2) is old


# ----------------------------------------------------------------------
# one switch, one clock (ISSUE 26): the tracer attaches the flight
# recorder; a device_round stamp links a span with an interval; attempt
# outcomes are counted by result code
# ----------------------------------------------------------------------


from dragonboat_tpu.obs import trace as trace_mod  # noqa: E402
from dragonboat_tpu.requests import (  # noqa: E402
    RequestResult,
    RequestResultCode,
)


def test_tracer_alone_attaches_ring_and_round_stamp_lies_in_linked_span():
    """``trace_sample_every=8`` and ``enable_metrics=False`` (what the
    benchmark's traced mode sets): the host has a flight recorder, and a
    completed sampled write's ``device_round`` stamp lies inside the
    interval of a span it links, on ``perf_counter``."""
    nh = _mk_host(addr="tr26:1", trace=8, engine="tpu", metrics=False,
                  warm_fused=False)
    try:
        _start(nh)
        rec = nh.flight_recorder
        assert rec is not None and nh.tracer.recorder is rec
        qc = nh.quorum_coordinator
        assert qc._obs is not None and qc.eng._obs is not None
        s = nh.get_noop_session(CID)
        states = [nh.propose(s, b"w", timeout=10.0) for _ in range(24)]
        for rs in states:
            assert rs.wait(10.0).completed
        sampled = [rs.trace for rs in states if rs.trace.__class__ is Trace]
        assert len(sampled) == 3  # 1 in 8
        by_seq = {sp["seq"]: sp for sp in rec.spans()}
        # a write completes out of its round's fan-out; the round's span
        # closes a little later (the arrays the step retired die first)
        wait_until(
            lambda: all(
                "wall_ms" in by_seq[q] for t in sampled for q in t.spans
                if q in by_seq and by_seq[q]["kind"] == "coord_round"),
            timeout=5.0, interval=0.01,
            what="the sampled writes' rounds closed",
        )
        for t in sampled:
            stamp = next(ts for st, ts, _th in t.events
                         if st == "device_round")
            linked = [by_seq[q] for q in t.spans if q in by_seq]
            assert {sp["kind"] for sp in linked} >= {"coord_round"}, linked
            holding = [sp for sp in linked
                       if sp["t0"] <= stamp <= sp["t1"]]
            assert holding, (stamp, linked)
            assert all(sp["host"] == "tr26:1" for sp in linked)
            # the dispatch span the stamp links is a child of that round
            kids = [sp for sp in linked
                    if sp["kind"] in ("dispatch", "fused")]
            assert kids and {k["parent"] for k in kids} <= {
                sp["seq"] for sp in linked if sp["kind"] == "coord_round"
            }
    finally:
        nh.stop()


def test_both_switches_off_leave_coordinator_and_engine_latches_none():
    nh = _mk_host(addr="tr26off:1", trace=0, engine="tpu", metrics=False,
                  warm_fused=False)
    try:
        _start(nh)
        _assert_trace_off(nh)
        qc = nh.quorum_coordinator
        assert qc._obs is None and qc.eng._obs is None
        assert qc.flight_recorder is None and nh.flight_recorder is None
        assert qc._first_at is None  # no wait stamp is taken while off
        # nor is a read's context followed or a round counted (ISSUE 39;
        # the six sites' clocks: tests/test_read_trace.py)
        assert qc._read_traces == {} and qc._rounds_recorded == 0
        assert nh.tracer not in trace_mod.live()
    finally:
        nh.stop()


@pytest.mark.parametrize("code", [c.name for c in RequestResultCode])
@pytest.mark.parametrize("kind", ["write", "read"])
def test_outcomes_count_every_completion_by_kind_and_code(kind, code):
    """Sampled or not, a completion is counted under ``(propose|read,
    CODE)``; the non-COMPLETED ones keep their instant."""
    reg = MetricsRegistry()
    tr = Tracer(sample_every=2, registry=reg)
    try:
        states = [RequestState(key=i + 1, deadline=0) for i in range(4)]
        tr.attach_all(states, CID, time.perf_counter(), kind=kind)
        t_a = time.perf_counter()
        for rs in states:
            rs.notify(RequestResult(code=RequestResultCode[code]))
        t_b = time.perf_counter()
        name = "propose" if kind == "write" else "read"
        out = tr.outcomes()
        assert out["counts"] == {(name, code): 4}
        events = out["events"]
        if code == "COMPLETED":
            assert events == []
        else:
            assert len(events) == 4
            assert all(k == name and c == code and t_a <= t <= t_b
                       for t, k, c in events)
        assert sum(n for sec in out["by_second"].values()
                   for n in sec.values()) == 4
        tr.flush_metrics()
        assert reg.counter_value(
            "dragonboat_trace_requests_done_total",
            {"kind": name, "code": code},
        ) == 4
    finally:
        tr.close()


def test_live_lists_every_open_tracer_of_the_process():
    a = Tracer(sample_every=1, registry=MetricsRegistry())
    b = Tracer(sample_every=1, registry=MetricsRegistry())
    try:
        live = trace_mod.live()
        assert live.index(a) < live.index(b)
        assert trace_mod.active() is b
    finally:
        b.close()
        assert b not in trace_mod.live() and a in trace_mod.live()
        a.close()
    assert a not in trace_mod.live()


def test_chrome_export_places_recorder_spans_by_their_own_interval():
    """The device-plane track is drawn from ``t0``/``t1`` (the stamps'
    clock), not from the wall time a record was written at."""
    rec = FlightRecorder(capacity=8, stall_ms=0)
    tr = Tracer(sample_every=1, registry=MetricsRegistry(), recorder=rec)
    tr.host = "me:1"
    try:
        now = time.perf_counter()
        span = rec.record("coord_round", host="me:1", t0=now - 0.250)
        rec.update(span, t1=now - 0.050, wall_ms=200.0)
        span["ts"] = 12345.0  # a lying wall clock must change nothing
        rec.record("coord_round", host="other:1", t0=now - 0.2)
        dev = [e for e in tr.export_chrome()["traceEvents"]
               if e.get("cat") == "device"]
        assert len(dev) == 1  # the co-hosted NodeHost's span stays out
        assert abs(dev[0]["dur"] - 200000.0) < 1.0
        assert abs(dev[0]["ts"] - tr._wall_us(now - 0.250)) < 1.0
        assert "t0" not in dev[0]["args"] and dev[0]["args"]["seq"] == 0
    finally:
        tr.close()


# ----------------------------------------------------------------------
# ISSUE 39: the tracer's locks, and what a traced window has to fit
# ----------------------------------------------------------------------


def test_completion_hooks_never_starve_the_submitting_thread():
    """Ten threads hammer the completion hooks (``request_done`` of
    unsampled and sampled requests, ``add_repl_leg``, ``check_stalls``)
    while one thread attaches: every attach returns within a bound (the
    unfair ``_mu`` once made a host's generator wait out its attempts'
    timeouts), and the counts equal what was submitted."""
    import threading

    from dragonboat_tpu.wire import ReplTrace

    reg = MetricsRegistry()
    tr = Tracer(sample_every=8, registry=reg, stall_ms=0)
    n_attach, per_attach, n_hooks = 1500, 4, 10
    pending = []          # attached futures, completed by the hook threads
    pend_mu = threading.Lock()
    stop = threading.Event()
    leg = ReplTrace(tid=1, origin="h:1", index=1, t_recv=1.0, t_append=2.0)

    def hook():
        while True:
            with pend_mu:
                rs = pending.pop() if pending else None
            if rs is None:
                if stop.is_set():
                    return
                tr.add_repl_leg(leg)
                tr.check_stalls()
                continue
            rs.notify(RequestResult(code=RequestResultCode.COMPLETED))
            tr.add_repl_leg(leg)

    threads = [threading.Thread(target=hook) for _ in range(n_hooks)]
    try:
        for th in threads:
            th.start()
        worst = 0.0
        for i in range(n_attach):
            states = [RequestState(key=i * per_attach + j + 1, deadline=0)
                      for j in range(per_attach)]
            t0 = time.perf_counter()
            tr.attach_all(states, CID, t0, kind="write" if i % 2 else "read")
            worst = max(worst, time.perf_counter() - t0)
            with pend_mu:
                pending.extend(states)
        stop.set()
        for th in threads:
            th.join(timeout=30.0)
        assert not any(th.is_alive() for th in threads)
        total = n_attach * per_attach
        # (wall time on a shared box: the starved generator waited out
        # its attempts' 5 s timeouts; what no hook may do is wait for a
        # lock, and the next test holds the lock to show that)
        assert worst < 5.0, f"an attach waited {worst:.3f}s"
        out = tr.outcomes()
        assert out["counts"] == {
            ("propose", "COMPLETED"): total // 2,
            ("read", "COMPLETED"): total // 2,
        }
        assert sum(n for sec in out["by_second"].values()
                   for n in sec.values()) == total
        assert tr.sampled == total // 8 == tr.completed
        assert not tr.inflight()
        tr.flush_metrics()
        for kind in ("propose", "read"):
            assert reg.counter_value(
                "dragonboat_trace_requests_done_total",
                {"kind": kind, "code": "COMPLETED"},
            ) == total // 2
        assert reg.counter_value("dragonboat_trace_requests_total") == total
        assert reg.counter_value("dragonboat_trace_completed_total") == (
            total // 8)
        assert len(tr.repl_legs()) == tr._repl_legs.maxlen
    finally:
        stop.set()
        tr.close()


def test_a_traced_windows_worth_of_spans_and_traces_is_kept():
    """The default ring holds a 48 s traced window of the busiest cell
    (three hosts, a 7 ms round, a ``coord_round`` and a ``dispatch`` span
    a round: 43,710-59,165 spans read on the chip, ISSUE 39) with the
    sampled contexts' ``read_ctx`` spans and the drain behind it, and the
    first span written is still read back; the tracer remembers a window's
    sampled requests a host (3,000 ops/s over three hosts, 1 in 8) for the
    readers that cut by the window (``finished()``), while ``traces()``
    stays the newest ``keep``."""
    from dragonboat_tpu.obs.recorder import DEFAULT_CAPACITY
    from dragonboat_tpu.obs.trace import DEFAULT_HISTORY, DEFAULT_KEEP

    window = 59165 + 9 * 48 * 200 // (10 * 8) + 12000  # spans + reads + drain
    assert DEFAULT_CAPACITY >= window
    rec = FlightRecorder(stall_ms=0)
    assert rec.capacity == DEFAULT_CAPACITY
    first = rec.record("coord_round", host="h:1", wall_ms=1.0)
    for i in range(window - 1):
        rec.record("dispatch", host="h:1", parent=first["seq"])
    spans = rec.spans()
    assert len(spans) == window and spans[0] is first
    assert spans[0]["seq"] == 0 and spans[-1]["seq"] == window - 1
    assert rec.to_json(limit=1)["count"] == window  # nothing overwritten

    per_host = 3000 * 48 // (3 * 8)
    assert DEFAULT_HISTORY >= per_host > DEFAULT_KEEP
    tr = Tracer(sample_every=1, registry=MetricsRegistry(), stall_ms=0)
    try:
        states = [RequestState(key=i + 1, deadline=0)
                  for i in range(per_host)]
        tr.attach_all(states, CID, time.perf_counter())
        for rs in states:
            rs.notify(RequestResult(code=RequestResultCode.COMPLETED))
        kept = tr.finished()
        assert len(kept) == per_host and kept[0] is states[0].trace
        assert tr.traces() == kept[-DEFAULT_KEEP:]
    finally:
        tr.close()


def test_per_message_hooks_and_unsampled_attach_take_no_tracer_lock():
    """With ``_mu`` held by someone else (a round thread stamping, a
    flush), a committer's ``add_repl_leg``, an apply worker's unsampled
    ``request_done`` and the generator's attach of a burst that holds no
    sampled slot all return; the completion is counted once the lock is
    free."""
    import threading

    from dragonboat_tpu.wire import ReplTrace

    tr = Tracer(sample_every=8, registry=MetricsRegistry(), stall_ms=0)
    try:
        first = RequestState(key=1, deadline=0)
        tr.attach_one(first, CID, time.perf_counter())  # the sampled slot
        assert first.trace.__class__ is Trace
        states = [RequestState(key=i + 2, deadline=0) for i in range(7)]
        done = threading.Event()

        def hot_path():
            tr.attach_all(states, CID, time.perf_counter(), kind="read")
            for rs in states:
                rs.notify(RequestResult(code=RequestResultCode.COMPLETED))
            tr.add_repl_leg(ReplTrace(tid=1, origin="h:1", index=1))
            done.set()

        with tr._mu:
            th = threading.Thread(target=hot_path)
            th.start()
            assert done.wait(5.0), "a per-message hook waited for _mu"
        th.join()
        assert tr.outcomes()["counts"] == {("read", "COMPLETED"): 7}
        assert len(tr.repl_legs()) == 1
    finally:
        tr.close()


def test_a_request_notified_between_its_context_and_its_registration():
    """``attach_all`` stores a sampled request's context before it takes
    ``_mu`` to register it (the submitting thread must not queue behind
    the hooks): a request the pipeline completes in between is finished
    once and is not left in flight for the stall watchdog to chase."""
    tr = Tracer(sample_every=1, registry=MetricsRegistry(), stall_ms=0)

    class NotifyFirst:
        """``_mu``, with the request notified just before the
        registration takes it."""

        def __init__(self, mu, cb):
            self.mu, self.cb = mu, cb

        def __enter__(self):
            cb, self.cb = self.cb, None
            if cb is not None:
                cb()
            return self.mu.__enter__()

        def __exit__(self, *exc):
            return self.mu.__exit__(*exc)

    try:
        rs = RequestState(key=7, deadline=0)
        tr._mu = NotifyFirst(tr._mu, lambda: rs.notify(
            RequestResult(code=RequestResultCode.COMPLETED)))
        tr.attach_one(rs, CID, time.perf_counter())
        assert rs.trace.__class__ is Trace and rs.trace.done
        assert tr.inflight() == [] and tr._by_key == {}
        assert tr.completed == 1 == len(tr.finished())
        assert tr.outcomes()["counts"] == {("propose", "COMPLETED"): 1}
    finally:
        tr.close()
