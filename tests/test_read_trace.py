"""A sampled ReadIndex context followed from the requester through the
leader's read plane and back (ISSUE 39).

Three hosts on the CPU, ``trace_sample_every=1``: a read at the leader's
host, a read forwarded by a follower, a context refused a device slot
(scalar path) and one dropped by a leader change each leave ONE ``read_ctx``
span on the leader's host whose instants are in order (``a <= eq <= d <= c
<= r``: every leg of the chain is there, none negative, and they add up to
``leader_ms``), with the right ``origin`` / ``path`` and the requester's
``tid``, and nothing left in the coordinator's dict.  With the tracer and the
metrics off no READ_INDEX carries a trace, the message's encoding is the
parent commit's byte for byte, and none of the six sites reads a clock.
"""
import sys
import time

import pytest

from dragonboat_tpu import Config, NodeHostConfig, Result
from dragonboat_tpu import obs
from dragonboat_tpu import tpuquorum
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.obs.trace import Trace
from dragonboat_tpu.statemachine import IStateMachine
from dragonboat_tpu.transport import ChanRouter, ChanTransport
from dragonboat_tpu.wire import Message, MessageType, ReplTrace
from dragonboat_tpu.wire import codec

from tests.loadwait import wait_until

CID = 3901
CHAIN = ("echo_trip_ms", "echo_wait_ms", "confirm_ms", "release_ms")
#: ``Message(READ_INDEX, to=2, from_=3, cluster_id=31, term=7, hint=...,
#: hint_high=...)`` as commit 86e05ff (this PR's parent) encodes it
PARENT_READ_INDEX = bytes.fromhex(
    "130002031f0700000088ef99abc5e88c911181febbefcdf9aed5990100"
)


class KV(IStateMachine):
    def __init__(self, cluster_id, node_id):
        self.kv = {}

    def update(self, cmd):
        k, v = cmd.decode().split("=", 1)
        self.kv[k] = v
        return Result(value=len(self.kv))

    def lookup(self, query):
        return self.kv.get(query)

    def save_snapshot(self, w, files, done):
        w.write(repr(sorted(self.kv.items())).encode())

    def recover_from_snapshot(self, r, files, done):
        import ast

        self.kv = dict(ast.literal_eval(r.read(-1).decode()))


class Cluster:
    """Three NodeHosts on the tpu engine, one group, host 1 leading."""

    def __init__(self, prefix: str, trace: int, rtt: int = 20,
                 rotate: bool = True):
        router = ChanRouter()
        self.addrs = {i: f"{prefix}{i}:1" for i in (1, 2, 3)}
        self.nhs = {
            i: NodeHost(NodeHostConfig(
                node_host_dir=":memory:",
                rtt_millisecond=rtt,
                raft_address=self.addrs[i],
                raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                    src, rh, ch, router=router),
                trace_sample_every=trace,
                expert=ExpertConfig(
                    quorum_engine="tpu", engine_block_groups=64,
                    engine_warm_fused=False),
            ))
            for i in (1, 2, 3)
        }
        try:
            for i, nh in self.nhs.items():
                nh.start_cluster(
                    self.addrs, False, KV,
                    Config(cluster_id=CID, node_id=i, election_rtt=10,
                           heartbeat_rtt=1))
            self.lead(1)
            s = self.nhs[1].get_noop_session(CID)
            self.nhs[1].sync_propose(s, b"k=v", timeout=30.0)
            # the first read of a host compiles the read programs inside
            # a round, which can outlast an election timeout: warm every
            # host's before a test looks
            for i in (1, 2, 3):
                wait_until(
                    lambda: self.nhs[i].get_node(CID).read(5.0)
                    .wait(5.0).completed,
                    timeout=60.0, interval=0.05, what=f"host {i} reads")
            for i in (1, 2, 3) if rotate else ():
                self.lead(i)
                assert self.read(i) is not None
            self.lead(1)
        except BaseException:
            self.stop()
            raise

    def leader(self) -> int:
        lid, ok = self.nhs[1].get_leader_id(CID)
        return lid if ok else 0

    def lead(self, want: int) -> None:
        """Drive leadership to host ``want`` (campaigns race the
        bootstrap: retry)."""
        def there():
            if all(nh.get_leader_id(CID) == (want, True)
                   for nh in self.nhs.values()):
                return True
            lid = self.leader()
            if lid and lid != want:
                try:
                    self.nhs[lid].request_leader_transfer(CID, want)
                except Exception:
                    pass
            elif not lid:
                self.nhs[want].get_node(CID).request_campaign()
            time.sleep(0.1)
            return False

        wait_until(there, timeout=30.0, interval=0.05,
                   what=f"host {want} leads")

    def coord(self, i: int):
        return self.nhs[i].quorum_coordinator

    def read(self, i: int):
        """One ``read_index`` at host ``i``, submitted again, as a client
        does, while a new leader has not committed in its term yet
        (``DROPPED``) or where a leader change lost a forwarded context
        (``TIMEOUT``); the RequestState of the attempt that completed."""
        for _ in range(100):
            rs = self.nhs[i].get_node(CID).read(2.0)
            res = rs.wait(3.0)
            if res.completed:
                return rs
            assert res.code.name in ("DROPPED", "TIMEOUT"), res
            time.sleep(0.05)
        raise AssertionError(f"no read completed at host {i}")

    def stop(self) -> None:
        for nh in self.nhs.values():
            nh.stop()


def _read_ctx_spans(host=None):
    return [s for s in obs.default_recorder().spans()
            if s["kind"] == "read_ctx" and s["cluster_id"] == CID
            and (host is None or s["host"] == host)]


def _span_of(trace: Trace, host: str) -> dict:
    """The one ``read_ctx`` span of the context that covered ``trace``."""
    def found():
        return [s for s in _read_ctx_spans(host)
                if (s["low"], s["high"]) == trace.read_ctx]

    wait_until(found, timeout=10.0, interval=0.01,
               what="the context's read_ctx span")
    spans = found()
    assert len(spans) == 1, spans
    return spans[0]


def _assert_chain_in_order(span: dict) -> None:
    """``a <= eq <= d <= c <= r``: every leg measured, none negative, and
    the chain is the whole of the leader's part."""
    assert all(span[k] >= 0.0 for k in CHAIN), span
    assert sum(span[k] for k in CHAIN) == pytest.approx(
        span["leader_ms"], abs=1e-2)
    assert (span["t1"] - span["t0"]) * 1e3 == pytest.approx(
        span["leader_ms"], abs=1e-2)
    assert 0.0 <= span["stage_wait_ms"] <= span["leader_ms"]
    assert 0.0 <= span["first_echo_ms"] <= span["echo_trip_ms"]
    assert span["echoes"] >= 1 and span["rounds"] >= 1


@pytest.fixture(scope="module")
def traced():
    c = Cluster("rt", trace=1)
    yield c
    c.stop()


def test_a_local_read_leaves_one_span_on_the_device_path(traced):
    c = traced
    c.lead(1)
    t = c.read(1).trace
    assert t.__class__ is Trace and t.read_origin == "local"
    span = _span_of(t, c.addrs[1])
    assert span["origin"] == "local" and span["path"] == "device"
    assert (span["tid"], span["trace_origin"]) == (t.tid, c.addrs[1])
    _assert_chain_in_order(span)
    at = {st: ts for st, ts, _th in t.events}
    # the leader's part lies between the requester's two stamps
    assert at["raft_step"] <= span["t0"] <= span["t1"] <= at["read_confirm"]
    # it names the rounds that staged and confirmed it, by their spans
    by_seq = {s["seq"]: s for s in obs.default_recorder().spans()}
    for key in ("stage_round", "confirm_round"):
        assert by_seq[span[key]]["kind"] == "coord_round"
        assert by_seq[span[key]]["host"] == c.addrs[1]
    assert all(not c.coord(i)._read_traces for i in (1, 2, 3))


#: a tick long enough that a quarter of it is no step worker's turn on any
#: host that runs this suite, and that a release that waits for one shows
TICK_MS = 1000


@pytest.fixture(scope="module")
def slow_tick():
    c = Cluster("rtslow", trace=1, rtt=TICK_MS, rotate=False)
    yield c
    c.stop()


def test_a_release_is_the_woken_turn_not_the_next_tick(slow_tick):
    """ISSUE 40: the fan-out flags a confirmed context and wakes the group,
    and that turn releases it.  On the parent the turn's gate did not see
    ``_off_reads`` and the context waited for the group's next heartbeat
    tick: ``release_ms`` uniform over the tick, a quarter of these twenty
    under a quarter of it."""
    c = slow_tick
    c.lead(1)
    spans = []
    for k in range(20):
        t = c.read((1, 2, 3, 2)[k % 4]).trace
        spans.append(_span_of(t, c.addrs[1]))
    assert {s["origin"] for s in spans} == {"local", "remote"}
    for s in spans:
        _assert_chain_in_order(s)
        assert s["path"] == "device"
        assert s["release_ms"] < TICK_MS / 4, s
    assert all(not c.coord(i)._read_traces for i in (1, 2, 3))


def test_the_scalar_paths_tally_is_the_woken_turn_too(slow_tick, monkeypatch):
    """A context refused a slot is tallied by the step worker
    (``_off_read_echoes``): the wait for that turn is its ``confirm_ms``,
    and on the parent it was the same wait for a tick one leg earlier."""
    c = slow_tick
    c.lead(1)

    def full(cid, **kw):
        raise RuntimeError("every pending-read slot holds a batch")

    monkeypatch.setattr(c.coord(1).eng, "stage_read", full)
    traces = [c.read(host).trace for host in (3, 1, 2, 3, 1, 2, 3, 1)]
    monkeypatch.undo()
    for t in traces:
        span = _span_of(t, c.addrs[1])
        _assert_chain_in_order(span)
        assert span["path"] == "scalar:slot_overflow"
        assert span["confirm_ms"] < TICK_MS / 4, span
        assert span["release_ms"] < TICK_MS / 4, span
    assert all(not c.coord(i)._read_traces for i in (1, 2, 3))


def test_a_forwarded_read_carries_the_requesters_id_to_the_leader(traced):
    c = traced
    c.lead(1)
    t = c.read(2).trace
    assert t.read_origin == "forwarded" and t.tracer.host == c.addrs[2]
    span = _span_of(t, c.addrs[1])  # written where the work happened
    assert span["origin"] == "remote" and span["path"] == "device"
    assert (span["tid"], span["trace_origin"]) == (t.tid, c.addrs[2])
    _assert_chain_in_order(span)
    at = {st: ts for st, ts, _th in t.events}
    assert at["raft_step"] <= span["t0"] <= span["t1"] <= at["read_confirm"]
    # the follower writes none: one span a context, where the work was
    assert not [s for s in _read_ctx_spans(c.addrs[2])
                if (s["low"], s["high"]) == t.read_ctx]
    assert all(not c.coord(i)._read_traces for i in (1, 2, 3))


def test_a_context_refused_a_slot_takes_the_scalar_path(traced, monkeypatch):
    c = traced
    c.lead(1)
    eng = c.coord(1).eng
    refused = []

    def full(cid, **kw):
        refused.append(cid)
        raise RuntimeError("every pending-read slot holds a batch")

    monkeypatch.setattr(eng, "stage_read", full)
    t = c.read(3).trace
    monkeypatch.undo()
    assert refused == [CID]
    span = _span_of(t, c.addrs[1])
    assert span["origin"] == "remote"
    assert span["path"] == "scalar:slot_overflow"
    assert (span["tid"], span["trace_origin"]) == (t.tid, c.addrs[3])
    _assert_chain_in_order(span)
    assert "stage_round" not in span and "confirm_round" not in span
    assert all(not c.coord(i)._read_traces for i in (1, 2, 3))


def test_a_context_dropped_by_a_leader_change_is_closed_there(traced):
    c = traced
    c.lead(1)
    coord = c.coord(1)
    term = c.nhs[1].get_node(CID).peer.raft.term
    # a context only the coordinator knows: no echo ever names it, so it is
    # still pending when leadership moves
    coord.read_stage(CID, 1, low=77, high=78, term=term, remote=True,
                     trace=ReplTrace(tid=4242, origin="elsewhere:1"))
    assert (CID, 77, 78) in coord._read_traces
    c.lead(2)
    wait_until(lambda: not coord._read_traces, timeout=10.0, interval=0.01,
               what="the transition closed the context")
    spans = [s for s in _read_ctx_spans(c.addrs[1]) if s["low"] == 77]
    assert len(spans) == 1
    span = spans[0]
    assert span["path"] == "dropped"
    assert (span["tid"], span["trace_origin"]) == (4242, "elsewhere:1")
    assert "leader_ms" not in span and "release_ms" not in span
    # and the new leader serves traced reads as the old one did
    t = c.read(1).trace
    assert t.read_origin == "forwarded"
    _assert_chain_in_order(_span_of(t, c.addrs[2]))
    assert all(not c.coord(i)._read_traces for i in (1, 2, 3))


def test_the_dict_is_bounded(traced):
    """A context that no release and no transition ever names is closed
    as ``dropped`` by the ``_READ_TRACES_KEEP + 1``-th behind it."""
    c = traced
    lid = c.leader()
    coord = c.coord(lid)
    term = c.nhs[lid].get_node(CID).peer.raft.term
    keep = tpuquorum._READ_TRACES_KEEP
    try:
        tpuquorum._READ_TRACES_KEEP = 4
        for low in range(900, 906):
            coord._read_trace_open(
                CID, low, 1, term, False, ReplTrace(tid=low, origin="x:1"))
        assert len(coord._read_traces) == 4
        assert (CID, 900, 1) not in coord._read_traces
        aged = [s for s in _read_ctx_spans() if s["low"] in (900, 901)]
        assert {s["path"] for s in aged} == {"dropped"} and len(aged) == 2
    finally:
        tpuquorum._READ_TRACES_KEEP = keep
        coord._read_traces_drop(CID)
    assert not coord._read_traces


class _CountingClock:
    """``time`` as ``tpuquorum`` sees it, counting the ``perf_counter``
    reads of the coordinators in ``watch`` (the module's other clusters
    run on)."""

    def __init__(self):
        self.reads = 0
        self.watch = []
        self.monotonic = time.monotonic
        self.time = time.time

    def perf_counter(self):
        if sys._getframe(1).f_locals.get("self") in self.watch:
            self.reads += 1
        return time.perf_counter()


@pytest.mark.parametrize("trace", [0, 1])
def test_tracer_off_no_trace_rides_and_no_clock_is_read(trace, monkeypatch):
    """Tracer and metrics off: ``_obs`` stays None, no READ_INDEX carries a
    trace, and a local and a forwarded read go through accept, stage, echo,
    drain, confirm and release without ``tpuquorum`` reading a clock.  On
    (the control): the same reads carry one and the clock is read."""
    clock = _CountingClock()
    c = Cluster(f"rtoff{trace}", trace=trace)
    try:
        carried = []
        for i in (1, 2, 3):
            coord = c.coord(i)
            assert (coord._obs is None) == (trace == 0)

            def staged(*a, _inner=coord.read_stage, **kw):
                carried.append(kw.get("trace"))
                return _inner(*a, **kw)

            monkeypatch.setattr(coord, "read_stage", staged)
            clock.watch.append(coord)
        monkeypatch.setattr(tpuquorum, "time", clock)
        for host in (1, 2, 3, 1, 2):
            rs = c.read(host)
            assert (rs.trace is None) == (trace == 0)
        assert len(carried) >= 5
        if trace == 0:
            assert carried == [None] * len(carried)
            assert clock.reads == 0
            assert not _read_ctx_spans() or all(
                not s["host"].startswith("rtoff0") for s in _read_ctx_spans())
        else:
            assert all(w is not None for w in carried)
            assert clock.reads > 0
        assert all(not c.coord(i)._read_traces for i in (1, 2, 3))
    finally:
        monkeypatch.undo()
        c.stop()


def test_an_unsampled_read_index_is_the_parents_bytes():
    m = Message(type=MessageType.READ_INDEX, to=2, from_=3, cluster_id=31,
                term=7, hint=0x1122334455667788,
                hint_high=0x99AABBCCDDEEFF01)
    assert m.trace is None
    assert codec.encode_message(m) == PARENT_READ_INDEX
    # a sampled one carries the requester's id and origin and nothing else
    m.trace = ReplTrace(tid=9, origin="h2:1")
    back = codec.decode_message(codec.encode_message(m))
    assert (back.trace.tid, back.trace.origin) == (9, "h2:1")
    assert (back.hint, back.hint_high) == (m.hint, m.hint_high)
    assert len(codec.encode_message(m)) > len(PARENT_READ_INDEX)


def test_the_export_steps_the_requesters_flow_through_the_leaders_span(
        traced):
    """A forwarded read's ``read_ctx`` span, in the LEADER's Perfetto dump,
    is a step of the requester's flow (its trace id, its host as origin),
    so ``tools/trace_merge.py`` binds the two hosts' halves."""
    from tools.trace_merge import merge_dumps

    c = traced
    c.lead(1)
    t = c.read(3).trace
    _span_of(t, c.addrs[1])
    steps = [e for e in c.nhs[1].tracer.export_chrome()["traceEvents"]
             if e.get("cat") == "request" and e.get("ph") == "t"
             and (e.get("args") or {}).get("origin") == c.addrs[3]]
    assert any(e["id"] == t.tid and e["name"] == f"read-{t.tid}"
               for e in steps)
    merged = merge_dumps([c.nhs[i].tracer.export_chrome() for i in (3, 1)])
    flows = {}
    for e in merged["traceEvents"]:
        if e.get("cat") == "request" and e.get("name") == f"read-{t.tid}":
            flows.setdefault(e["id"], set()).add(e["pid"])
    assert any(len(pids) > 1 for pids in flows.values()), flows
