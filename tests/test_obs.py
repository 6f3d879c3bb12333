"""Device-plane observability tests (ISSUE 5).

Covers the ``dragonboat_tpu.obs`` package itself (a real package — the
seed shipped only a stale ``__pycache__`` with no sources, so import
behavior depended on interpreter caching), the flight recorder ring +
stall-watchdog auto-dump, the Prometheus exposition audit (escaping,
one ``# TYPE`` per name, round-trip), engine obs-on/obs-off parity, and
the health-metrics surface end to end through a tpu-engine NodeHost.
"""
import importlib
import io
import json
import os
import pkgutil
import time

import pytest

import dragonboat_tpu
from dragonboat_tpu.events import MetricsRegistry, escape_label_value
from dragonboat_tpu.obs import FlightRecorder
from dragonboat_tpu.obs.instruments import CoordObs, EngineObs
from dragonboat_tpu.ops.engine import BatchedQuorumEngine

RTT_MS = 5


# ---------------------------------------------------------------------------
# packaging (satellite: the stale-__pycache__ bug)
# ---------------------------------------------------------------------------


def test_every_subpackage_imports_as_real_package():
    """Every ``dragonboat_tpu.*`` subpackage must import from real
    sources: a directory holding only a ``__pycache__`` imports as an
    EMPTY namespace package (Python 3 ignores ``__pycache__`` pycs whose
    sources are gone), so ``import dragonboat_tpu.obs`` silently
    succeeded while every attribute access failed."""
    root = os.path.dirname(dragonboat_tpu.__file__)
    found = []
    for entry in sorted(os.listdir(root)):
        d = os.path.join(root, entry)
        if os.path.isdir(d) and entry != "__pycache__":
            mod = importlib.import_module(f"dragonboat_tpu.{entry}")
            # a namespace package has no __file__ — the bug's signature
            assert getattr(mod, "__file__", None), (
                f"dragonboat_tpu.{entry} imported as a namespace package "
                "(missing __init__.py?)"
            )
            found.append(entry)
    assert "obs" in found and "ops" in found
    # and the walkable module tree stays importable (sources, not pycs)
    for info in pkgutil.iter_modules(
        dragonboat_tpu.obs.__path__, "dragonboat_tpu.obs."
    ):
        importlib.import_module(info.name)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_recorder_ring_wrap_order_and_json():
    rec = FlightRecorder(capacity=4, stall_ms=0)
    for i in range(7):
        rec.record("dispatch", rounds=i)
    spans = rec.spans()
    assert len(spans) == 4 == len(rec)
    assert [s["rounds"] for s in spans] == [3, 4, 5, 6]  # oldest -> newest
    assert [s["seq"] for s in spans] == [3, 4, 5, 6]
    d = rec.to_json(limit=2)
    assert d["count"] == 7 and len(d["spans"]) == 2
    json.dumps(d)  # must be serializable as-is


def test_recorder_stall_watchdog_autodump(tmp_path):
    path = str(tmp_path / "dump.json")
    rec = FlightRecorder(capacity=8, stall_ms=10.0, dump_path=path)
    rec.record("dispatch", gate="acks", dispatch_ms=1.0)  # healthy
    assert rec.stalls == 0 and rec.last_dump is None
    span = rec.record("dispatch", gate="tick+acks", dispatch_ms=1.0)
    rec.update(span, egress_ms=25.0)  # trips at finalize (slow egress)
    assert rec.stalls == 1
    assert span["stalled"] == "egress_ms"
    dump = rec.last_dump
    assert dump["trigger"] is span and "stall" in dump["reason"]
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["trigger"]["gate"] == "tick+acks"
    # a span stalls (and dumps) at most once
    rec.update(span, egress_ms=50.0)
    assert rec.stalls == 1


# ---------------------------------------------------------------------------
# Prometheus exposition (satellite audit)
# ---------------------------------------------------------------------------


def _parse_exposition(text):
    """Minimal text-format parser: returns ({name: type}, {(name, labels
    frozenset): value}) with label values UNescaped.  Also asserts the
    ISSUE 9 HELP invariant: every family carries exactly one ``# HELP``
    line immediately before its ``# TYPE``."""
    types, samples, helps = {}, {}, {}
    pending_help = None
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            _, _, name, help_text = line.split(" ", 3)
            assert name not in helps, f"duplicate # HELP for {name}"
            helps[name] = (
                help_text.replace("\\n", "\n").replace("\\\\", "\\")
            )
            pending_help = name
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert name not in types, f"duplicate # TYPE for {name}"
            assert pending_help == name, (
                f"# TYPE {name} not immediately preceded by its # HELP"
            )
            pending_help = None
            types[name] = kind
            continue
        assert not line.startswith("#")
        metric, value = line.rsplit(" ", 1)
        if "{" in metric:
            name, rest = metric.split("{", 1)
            body = rest.rsplit("}", 1)[0]
            labels = []
            # split on '",' boundaries so escaped quotes stay intact
            for part in body.split('",'):
                k, v = part.split("=", 1)
                v = v.strip('"')
                v = (
                    v.replace("\\n", "\n")
                    .replace('\\"', '"')
                    .replace("\\\\", "\\")
                )
                labels.append((k, v))
            samples[(name, frozenset(labels))] = float(value)
        else:
            samples[(metric, frozenset())] = float(value)
    return types, samples


def test_exposition_escaping_and_single_type_roundtrip():
    reg = MetricsRegistry()
    nasty = 'quo"te\\slash\nnewline'
    reg.counter_add("x_total", 3, labels={"a": nasty})
    reg.counter_add("x_total", 2, labels={"a": "plain"})  # same family
    reg.gauge_set("depth", 7.5, labels={"q": "r"})
    reg.histogram_observe("lat_ms", 3.0, buckets=(1.0, 5.0, 10.0))
    reg.histogram_observe("lat_ms", 100.0, buckets=(1.0, 5.0, 10.0))
    reg.describe("x_total", "an x\ncounter with back\\slash")
    out = io.StringIO()
    reg.write_health_metrics(out)
    text = out.getvalue()
    # HELP escaping (backslash + newline only — quotes stay literal per
    # the exposition spec) and presence for EVERY family: described ones
    # carry their text, undescribed ones the deterministic placeholder
    assert "# HELP x_total an x\\ncounter with back\\\\slash\n" in text
    assert "# HELP depth dragonboat_tpu metric depth\n" in text
    assert "# HELP lat_ms dragonboat_tpu metric lat_ms\n" in text
    # escaping: raw specials never appear inside a label value
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    assert "\n" not in text.split('a="')[1].split('"')[0]
    types, samples = _parse_exposition(text)  # asserts one TYPE per name
    assert types == {
        "x_total": "counter", "depth": "gauge", "lat_ms": "histogram",
    }
    # round-trip: parsed values match what was registered
    assert samples[("x_total", frozenset({("a", nasty)}))] == 3
    assert samples[("x_total", frozenset({("a", "plain")}))] == 2
    assert samples[("depth", frozenset({("q", "r")}))] == 7.5
    # histogram: cumulative buckets, +Inf == count, sum preserved
    assert samples[("lat_ms_bucket", frozenset({("le", "5")}))] == 1
    assert samples[("lat_ms_bucket", frozenset({("le", "+Inf")}))] == 2
    assert samples[("lat_ms_sum", frozenset())] == 103.0
    assert samples[("lat_ms_count", frozenset())] == 2
    # stable ordering: a second write is byte-identical
    out2 = io.StringIO()
    reg.write_health_metrics(out2)
    assert out2.getvalue() == text


def test_escape_label_value_order():
    # backslash escapes FIRST: escaping a pre-escaped quote must not
    # double-mangle
    assert escape_label_value('\\"') == '\\\\\\"'
    assert escape_label_value("a\nb") == "a\\nb"


# ---------------------------------------------------------------------------
# engine hooks
# ---------------------------------------------------------------------------


def _drive(eng):
    for cid in (1, 2):
        eng.add_group(cid, node_ids=[1, 2, 3], self_id=1)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
    outs = []
    for r in range(3):
        for cid in (1, 2):
            eng.ack(cid, 1, 2 + r)
            eng.ack(cid, 2, 2 + r)
        eng.begin_round()
        outs.append(dict(eng.step_rounds(do_tick=False).commit))
    eng.ack(1, 2, 10)
    outs.append(dict(eng.step(do_tick=False).commit))  # single-round path
    return outs


def test_engine_obs_off_by_default_and_parity():
    plain = BatchedQuorumEngine(8, 3, device_ticks=False)
    assert plain._obs is None  # obs-off: no instruments, no recorder
    rec = FlightRecorder(capacity=32, stall_ms=0)
    reg = MetricsRegistry()
    instrumented = BatchedQuorumEngine(8, 3, device_ticks=False)
    instrumented.enable_obs(recorder=rec, registry=reg)
    assert _drive(plain) == _drive(instrumented)  # identical egress
    spans = rec.spans()
    assert len(spans) == 4
    fused = spans[0]
    assert fused["kind"] == "fused" and fused["gate"] == "acks"
    assert fused["rounds"] == 1 and fused["acks"] == 4
    assert fused["upload_bytes"] > 0 and "egress_ms" in fused
    assert fused["egress_rows"] == 2  # both groups advanced
    single = spans[-1]
    assert single["kind"] == "dispatch" and single["acks"] == 1
    # counters followed the spans
    assert reg.counter_value("dragonboat_device_dispatch_total") == 4
    assert reg.counter_value("dragonboat_device_acks_staged_total") == 13
    assert reg.histogram_value("dragonboat_device_dispatch_latency_ms")[3] == 4


def test_enable_obs_rebinds_registry_after_latch():
    """A latch-attached engine must not swallow a later explicit wiring:
    NodeHost routes the families into ITS registry after the module latch
    already self-attached the default one."""
    import dragonboat_tpu.obs as obs_mod

    obs_mod.enable(stall_ms=0)
    try:
        eng = BatchedQuorumEngine(4, 3, device_ticks=False)
        assert eng._obs is not None  # latch self-attached
        mine = MetricsRegistry()
        eng.enable_obs(registry=mine)  # the NodeHost-style rebind
        assert eng._obs.registry is mine
        assert "dragonboat_device_dispatch_total" in mine.families()
        same = eng.enable_obs()  # argument-free repeat: no-op
        assert same is eng._obs and same.registry is mine
    finally:
        obs_mod.disable()


def test_engine_obs_recycle_and_gate_reasons():
    rec = FlightRecorder(capacity=32, stall_ms=0)
    eng = BatchedQuorumEngine(8, 3, device_ticks=False)
    eng.enable_obs(recorder=rec, registry=MetricsRegistry())
    eng.add_group(1, node_ids=[1, 2, 3], self_id=1)
    eng.set_leader(1, term=1, term_start=1, last_index=1)
    eng.step(do_tick=False)
    eng.stage_recycle(1, 2, term=1, term_start=1, last_index=1)
    eng.ack(2, 2, 2)
    eng.begin_round()
    eng.step_rounds(do_tick=False)
    last = rec.spans()[-1]
    assert last["recycles"] == 1
    assert "churn" in last["gate"] and "acks" in last["gate"]


def test_engine_stall_autodump_names_blocked_dispatch(monkeypatch, tmp_path):
    """Acceptance: a forced dispatch stall (slow egress) auto-dumps the
    recorder with the stalled span — its kind, gate reason, and staged
    counts name the blocked dispatch."""
    import jax

    path = str(tmp_path / "stall.json")
    rec = FlightRecorder(capacity=16, stall_ms=20.0, dump_path=path)
    reg = MetricsRegistry()
    eng = BatchedQuorumEngine(8, 3, device_ticks=False)
    eng.enable_obs(recorder=rec, registry=reg)
    eng.add_group(1, node_ids=[1, 2, 3], self_id=1)
    eng.set_leader(1, term=1, term_start=1, last_index=1)
    # warmup: compile the fused program so the stall below is attributable
    # to the forced-slow egress, not a first-use jit dispatch (which the
    # watchdog would legitimately flag as a dispatch_ms stall)
    eng.ack(1, 2, 2)
    eng.begin_round()
    eng.step_rounds(do_tick=False)
    assert rec.stalls == 0 or rec.last_dump["trigger"]["stalled"] != "egress_ms"
    rec.stalls = 0

    real_get = jax.device_get

    def slow_get(x):  # a wedged egress (device hang)
        time.sleep(0.05)
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", slow_get)
    eng.ack(1, 2, 3)
    eng.begin_round()
    eng.step_rounds(do_tick=False)
    assert rec.stalls >= 1
    assert reg.counter_value("dragonboat_device_stalls_total") >= 1
    dump = rec.last_dump
    trigger = dump["trigger"]
    assert trigger["stalled"] == "egress_ms"
    assert trigger["kind"] == "fused" and trigger["gate"] == "acks"
    assert trigger["acks"] == 1 and trigger["egress_ms"] >= 20.0
    with open(path) as f:  # the on-demand artifact names it too
        assert json.load(f)["trigger"]["kind"] == "fused"


# ---------------------------------------------------------------------------
# metric families through write_health_metrics
# ---------------------------------------------------------------------------


def test_devsm_apply_kernel_span_and_families():
    """ISSUE 11 satellite: a kv-carrying dispatch opens an
    ``apply_kernel`` span (staged ops/reads at dispatch, applied/served
    at harvest) and the ``dragonboat_devsm_*`` families track the fold's
    work; kv-free engines never record the kind."""
    rec = FlightRecorder(capacity=32, stall_ms=0)
    reg = MetricsRegistry()
    eng = BatchedQuorumEngine(8, 3, device_ticks=False)
    eng.enable_obs(recorder=rec, registry=reg)
    eng.add_group(1, node_ids=[1, 2, 3], self_id=1)
    eng.set_leader(1, term=1, term_start=1, last_index=1)
    eng.ack(1, 1, 3)
    eng.step(do_tick=False)
    assert not [s for s in rec.spans() if s["kind"] == "apply_kernel"]
    # now a kv round: 2 ops commit, 1 read captures — single-round path
    eng.stage_kv_ops(1, [2, 3], [0, 1], [5, 6])
    eng.ack(1, 2, 3)
    eng.stage_kv_read(1, 0)
    eng.step(do_tick=False)
    # ... and a fused block: 1 op + 1 read
    eng.stage_kv_ops(1, [4], [2], [7])
    eng.ack(1, 1, 4)
    eng.ack(1, 2, 4)
    eng.stage_kv_read(1, 2)
    eng.begin_round()
    eng.step_rounds(do_tick=False)
    spans = [s for s in rec.spans() if s["kind"] == "apply_kernel"]
    assert len(spans) == 2
    assert spans[0]["ops"] == 2 and spans[0]["reads"] == 1
    assert spans[0]["applied"] == 2 and spans[0]["reads_served"] == 1
    assert spans[1]["ops"] == 1 and spans[1]["applied"] == 1
    assert reg.counter_value("dragonboat_devsm_ops_staged_total") == 3
    assert reg.counter_value("dragonboat_devsm_applied_total") == 3
    assert reg.counter_value("dragonboat_devsm_reads_staged_total") == 2
    assert reg.counter_value("dragonboat_devsm_reads_served_total") == 2
    # exposition carries the families with their described HELP text
    out = io.StringIO()
    reg.write_health_metrics(out)
    text = out.getvalue()
    for fam in (
        "dragonboat_devsm_ops_staged_total",
        "dragonboat_devsm_applied_total",
        "dragonboat_devsm_reads_staged_total",
        "dragonboat_devsm_reads_served_total",
        "dragonboat_devsm_slot_occupancy",
    ):
        assert f"# TYPE {fam} " in text, fam
        help_line = next(
            l for l in text.splitlines() if l.startswith(f"# HELP {fam} ")
        )
        assert "dragonboat_tpu metric" not in help_line, help_line


def test_device_plane_metric_families_exposed():
    """ISSUE acceptance: with obs enabled, the health exposition carries
    >= 8 device-plane families (engine + coordinator planes)."""
    rec = FlightRecorder(capacity=8, stall_ms=0)
    reg = MetricsRegistry()
    EngineObs(rec, reg)
    CoordObs(rec, reg)
    out = io.StringIO()
    reg.write_health_metrics(out)
    types, _ = _parse_exposition(out.getvalue())
    dev = [n for n in types if n.startswith("dragonboat_device_")]
    coord = [n for n in types if n.startswith("dragonboat_coord_")]
    assert len(dev) >= 8, dev
    assert len(dev) + len(coord) >= 14
    # the latency families expose as proper histograms
    assert types["dragonboat_device_dispatch_latency_ms"] == "histogram"
    assert types["dragonboat_coord_round_latency_ms"] == "histogram"


def test_nodehost_health_metrics_device_plane():
    """Live wiring: NodeHostConfig.enable_metrics + quorum_engine="tpu"
    puts the device plane into nh.write_health_metrics, the recorder on
    nh.flight_recorder, and node offload application into the registry."""
    from dragonboat_tpu import Config, NodeHostConfig, Result
    from dragonboat_tpu.config import ExpertConfig
    from dragonboat_tpu.nodehost import NodeHost
    from dragonboat_tpu.transport import ChanRouter, ChanTransport

    class CountSM:
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

    router = ChanRouter()
    nh = NodeHost(
        NodeHostConfig(
            node_host_dir=":memory:",
            rtt_millisecond=RTT_MS,
            raft_address="obs:1",
            raft_rpc_factory=lambda src, rh, ch: ChanTransport(
                src, rh, ch, router=router
            ),
            enable_metrics=True,
            expert=ExpertConfig(quorum_engine="tpu", engine_block_groups=64),
        )
    )
    try:
        assert nh.flight_recorder is not None
        out = io.StringIO()
        nh.write_health_metrics(out)
        types, _ = _parse_exposition(out.getvalue())
        assert len(
            [n for n in types if n.startswith("dragonboat_device_")]
        ) >= 8
        nh.start_cluster(
            {1: "obs:1"},
            False,
            CountSM,
            Config(cluster_id=5, node_id=1, election_rtt=10, heartbeat_rtt=1),
        )
        deadline = time.time() + 10
        while time.time() < deadline:
            _, ok = nh.get_leader_id(5)
            if ok:
                break
            time.sleep(0.01)
        s = nh.get_noop_session(5)
        for _ in range(5):
            nh.sync_propose(s, b"x", timeout=5.0)
        reg = nh.metrics_registry
        # the device plane actually served the writes: dispatches ran,
        # commits offloaded back, and the node applied them
        deadline = time.time() + 10
        while time.time() < deadline:
            if reg.counter_value(
                "dragonboat_node_offload_applied_total", {"kind": "commit"}
            ) > 0:
                break
            time.sleep(0.05)
        assert reg.counter_value("dragonboat_device_dispatch_total") > 0
        assert reg.counter_value("dragonboat_coord_rounds_total") > 0
        assert reg.counter_value(
            "dragonboat_node_offload_applied_total", {"kind": "commit"}
        ) > 0
        assert len(nh.flight_recorder.spans()) > 0
    finally:
        nh.stop()


def test_hostproc_obs_live_plane_families():
    """ISSUE 12: a live HostProcPlane with obs enabled publishes the
    ``dragonboat_hostproc_*`` families into the given registry — the
    monitor keeps workers_alive current and a worker round trip lands
    calls_total + the worker-wall histogram observation."""
    import time as _time

    from dragonboat_tpu.events import MetricsRegistry
    from dragonboat_tpu.hostproc.control import HostProcPlane

    reg = MetricsRegistry()
    p = HostProcPlane(workers=1, encode_lanes=1)
    try:
        p.enable_obs(registry=reg)
        assert reg.gauge_value("dragonboat_hostproc_workers_alive") == 1
        lane = p.encode_lane(0)
        assert lane.encode(0, [b"abc"]) is not None
        assert (
            reg.counter_value(
                "dragonboat_hostproc_calls_total", {"role": "encode"}
            )
            == 1
        )
        deadline = _time.time() + 5
        while (
            reg.gauge_value("dragonboat_hostproc_ring_depth") != 0
            and _time.time() < deadline
        ):
            _time.sleep(0.05)
    finally:
        p.stop()


# ---------------------------------------------------------------------------
# spans as intervals with a cause, phases, counters at the boundaries
# (ISSUE 26)
# ---------------------------------------------------------------------------

DISPATCH_PARTS = ("row_sync_ms", "stage_ms", "transfer_ms", "launch_ms")


def test_recorder_default_ring_holds_16k_span_run_in_order():
    from dragonboat_tpu.obs.recorder import DEFAULT_CAPACITY

    assert DEFAULT_CAPACITY >= 16384
    rec = FlightRecorder(stall_ms=0)
    for i in range(16384):
        rec.record("dispatch", n=i)
    spans = rec.spans()
    assert len(spans) == 16384 and spans[0]["n"] == 0
    assert [s["seq"] for s in spans] == list(range(16384))
    for i in range(16384, rec.capacity + 10):
        rec.record("dispatch", n=i)  # wrap: oldest -> newest still
    spans = rec.spans()
    assert len(spans) == rec.capacity
    assert [s["n"] for s in spans] == list(
        range(10, rec.capacity + 10)
    )


def test_span_is_an_interval_with_host_and_parent():
    rec = FlightRecorder(capacity=8, stall_ms=0)
    before = time.perf_counter()
    top = rec.record("coord_round", host="h1")
    child = rec.record("dispatch", host="h1", parent=top["seq"],
                       t0=before)
    assert top["t0"] == top["t1"] >= before and top["parent"] is None
    assert child["t0"] == before and child["parent"] == top["seq"]
    time.sleep(0.002)
    rec.update(child, egress_ms=1.0)
    assert child["t1"] > child["t0"] and child["t1"] <= time.perf_counter()
    rec.update(top, t1=child["t1"] + 1.0)  # a caller's own t1 wins
    assert top["t1"] == child["t1"] + 1.0
    assert top["host"] == child["host"] == "h1"


def _leader_engine(rec, host=None, groups=8):
    eng = BatchedQuorumEngine(groups, 3, device_ticks=False)
    eng.enable_obs(recorder=rec, registry=MetricsRegistry(), host=host)
    for cid in range(1, groups + 1):
        eng.add_group(cid, node_ids=[1, 2, 3], self_id=1)
        eng.set_leader(cid, term=1, term_start=1, last_index=1)
    return eng


def test_dispatch_phases_make_up_dispatch_and_egress():
    """``dispatch_ms`` holds its four phases, which are nearly all of it
    on a quiet engine; ``egress_ms`` likewise; ``t1 - t0`` and the whole
    call's ``step_ms`` hold both."""
    rec = FlightRecorder(stall_ms=0)
    eng = _leader_engine(rec, host="solo")
    idx = 1
    for i in range(40):
        idx += 1
        for cid in range(1, 9):
            eng.ack(cid, 1, idx)
            eng.ack(cid, 2, idx)
        if i % 4 == 3:  # the fused path too
            eng.begin_round()
            eng.step_rounds(do_tick=False)
        else:
            eng.step(do_tick=False)
    spans = [s for s in rec.spans() if s["kind"] in ("dispatch", "fused")]
    assert {s["kind"] for s in spans} == {"dispatch", "fused"}
    import statistics

    quiet = spans[8:]  # past the first-use compiles
    for s in quiet:
        assert s["host"] == "solo" and s["parent"] is None
        assert sum(s[k] for k in DISPATCH_PARTS) <= s["dispatch_ms"] + 0.01
        assert s["egress_wait_ms"] + s["decode_ms"] <= s["egress_ms"] + 0.01
        wall = (s["t1"] - s["t0"]) * 1e3
        assert wall + 0.01 >= s["dispatch_ms"] + s["egress_ms"], s
        # the whole call, as its caller sees it, holds both
        assert s["step_ms"] + 0.01 >= s["dispatch_ms"] + s["egress_ms"], s
    # medians, so that one preempted step of a loaded test box cannot
    # fail it: within 5% (or 0.2 ms)
    disp = statistics.median(s["dispatch_ms"] for s in quiet)
    named = statistics.median(
        sum(s[k] for k in DISPATCH_PARTS) for s in quiet
    )
    assert disp - named <= max(0.05 * disp, 0.2), (disp, named)
    eg = statistics.median(s["egress_ms"] for s in quiet)
    eg_named = statistics.median(
        s["egress_wait_ms"] + s["decode_ms"] for s in quiet
    )
    assert eg - eg_named <= max(0.05 * eg, 0.2), (eg, eg_named)
    # the dirty-row upload of the registration is a row sync
    assert spans[0]["row_sync_ms"] > 0


@pytest.mark.parametrize("reads", [False, True], ids=["writes", "reads"])
def test_steady_state_step_makes_one_array_and_retires_few(reads):
    """A steady-state step of a single-device engine makes NO array of
    its own (the ingress block rides the launch as the host buffer it is;
    the read plane rides in it) and retires the two state blocks and the
    egress block: three, where the 31-leaf state, eight puts and six flag
    vectors made it ~53 and four blocks with a put ingress block 7.  The
    drops of the blocks have a phase, ``retire_ms`` (in a bare loop the
    next launch's, so inside that step).  The six older phase fields all
    stay on the span, ``transfer_ms`` as 0.0: ``engine_other_ms`` reads
    ``step_ms`` less those six."""
    rec = FlightRecorder(stall_ms=0)
    eng = _leader_engine(rec)
    idx = 1
    for i in range(12):
        idx += 1
        for cid in range(1, 9):
            eng.ack(cid, 1, idx)
            eng.ack(cid, 2, idx)
        if reads:
            slot = eng.stage_read(1 + i % 8, count=2)
            eng.read_ack(1 + i % 8, 2, slot)
        if i % 3 == 2:  # the fused path too
            eng.begin_round()
            res = eng.step_rounds(do_tick=True, pad_rounds_to=4)
        else:
            res = eng.step(do_tick=i % 2 == 0)
        assert res.commit and (not reads or res.reads)
    spans = [s for s in rec.spans() if s["kind"] in ("dispatch", "fused")]
    assert {s["kind"] for s in spans} == {"dispatch", "fused"}
    older = ("row_sync_ms", "stage_ms", "transfer_ms", "launch_ms",
             "egress_wait_ms", "decode_ms")
    for s in spans[1:]:  # the first also uploads the registrations
        assert s["arrays_made"] == 0, s
        assert s["arrays_retired"] <= 3, s
        assert s["transfer_ms"] == 0.0, s
        assert s["retire_ms"] >= 0.0
        assert s["ack_blocks_stale"] == 0
        assert all(s[k] is not None and s[k] >= 0.0 for k in older), s
        named = sum(s[k] for k in older) + s["retire_ms"]
        assert named <= s["step_ms"] + 0.01, s
    # each launch dropped what the one before it had replaced
    assert any(s["retire_ms"] > 0.0 for s in spans[1:])


class _FakeNode:
    """The surface the coordinator's fan-out drives."""

    def __init__(self, cid, raft):
        self.cluster_id = cid
        self.peer = type("P", (), {"raft": raft})
        self.commits, self.confirms, self.echoes = [], [], []

    def offload_commit(self, q):
        self.commits.append(q)

    def offload_read_confirm(self, low, high, term):
        self.confirms.append((low, high, term))

    def offload_read_echo(self, node_id, low, high):
        self.echoes.append((node_id, low, high))


def _coord_with_leaders(cids, rec=None, host="coordhost", capacity=8):
    from dragonboat_tpu.raft import InMemLogDB
    from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator
    from tests.raft_harness import new_test_raft

    coord = TpuQuorumCoordinator(capacity=capacity, n_peers=4,
                                 drive_ticks=False)
    if rec is not None:
        coord.enable_obs(recorder=rec, registry=MetricsRegistry(),
                         host=host)
    nodes = {}
    for cid in cids:
        r = new_test_raft(1, [1, 2, 3], 10, 1, InMemLogDB())
        r.cluster_id = cid
        r.become_candidate()
        r.become_leader()
        n = nodes[cid] = _FakeNode(cid, r)
        coord._nodes[cid] = n
        with coord._mu:
            coord._sync_row_locked(n)
    coord.flush()  # absorb the registration dirt
    return coord, nodes


def test_replaced_blocks_die_after_the_commit_fan_out():
    """A coordinator round offloads its commits BEFORE the state blocks
    its step replaced and the egress block it fetched are dropped (their
    deaths hand the interpreter away and must not stand between a launch
    and an acknowledgement), the time lands late on that step's span; a
    bare ``eng.step()`` loop, which never calls ``drop_retired``, holds
    one step's arrays at most."""
    rec = FlightRecorder(stall_ms=0)
    coord, nodes = _coord_with_leaders([3, 4], rec)
    try:
        # the rounds below are this thread's alone (flush): the round
        # thread would race them for the staged acks
        coord._stopped.set()
        coord._pending.set()
        coord._thread.join(timeout=5)
        assert not coord._thread.is_alive()
        eng = coord.eng
        order = []
        held_at_commit = []
        drop = eng.drop_retired

        def dropping():
            order.append(("drop", len(eng._retired)))
            drop()

        eng.drop_retired = dropping
        for n in nodes.values():
            def offload(q, n=n):
                order.append(("commit", n.cluster_id))
                held_at_commit.append(len(eng._retired))
                n.commits.append(q)
            n.offload_commit = offload
        for idx in range(1, 6):
            for cid in nodes:
                coord.ack(cid, 2, idx)
            coord.flush()
        kinds = [k for k, _ in order]
        assert kinds.count("commit") >= 2
        # every commit of a round comes before that round's drop ...
        for i, k in enumerate(kinds):
            if k == "commit":
                assert "drop" in kinds[i:], order
        # ... the two replaced blocks and the egress block were still
        # held while they went out,
        assert held_at_commit and all(n == 3 for n in held_at_commit)
        # and dropped right after: nothing is held between rounds
        assert [n for k, n in order if k == "drop"][-1] == 3
        assert eng._retired == ()
        steps = [s for s in rec.spans() if s["kind"] == "dispatch"]
        assert steps and all(s["retire_ms"] >= 0.0 for s in steps)
        assert any(s["retire_ms"] > 0.0 for s in steps)
        assert all(s["transfer_ms"] == 0.0 and s["arrays_made"] == 0
                   for s in steps)
    finally:
        coord.stop()

    eng = _leader_engine(FlightRecorder(stall_ms=0))
    idx = 1
    seen = ()
    for i in range(6):
        idx += 1
        for cid in range(1, 9):
            eng.ack(cid, 1, idx)
            eng.ack(cid, 2, idx)
        if i % 3 == 2:
            eng.begin_round()
            eng.step_rounds(do_tick=False)
        else:
            eng.step(do_tick=False)
        # one step's: the blocks the newest launch replaced (donated:
        # no device memory) and the egress block it fetched
        assert len(eng._retired) == 3
        assert [b.is_deleted() for b in eng._retired] == [True, True, False]
        assert not any(b is old for b in eng._retired for old in seen)
        seen = eng._retired
    eng.drop_retired()
    assert eng._retired == ()


def test_round_span_is_parent_of_its_dispatches_and_holds_its_phases():
    rec = FlightRecorder(stall_ms=0)
    coord, nodes = _coord_with_leaders([3, 4], rec)
    try:
        for _ in range(12):
            for cid in nodes:
                coord.ack(cid, 2, 1)
            coord.flush()
        # this coordinator's alone; the round thread may still hold a
        # round open when the last flush() returns
        rounds = {s["seq"]: s for s in _closed_rounds(rec)}
        kids = [s for s in rec.spans() if s["kind"] in ("dispatch", "fused")]
        assert len(rounds) >= 8 and len(kids) >= 8  # the round thread
        # races flush() for the staged acks: some rounds take two turns
        by_parent = {}
        for k in kids:
            # every dispatch names the round that ran it, same host, and
            # lies inside the round's interval
            assert k["parent"] in rounds, (k, sorted(rounds))
            r = rounds[k["parent"]]
            assert k["host"] == r["host"] == "coordhost"
            assert r["t0"] <= k["t0"] and k["t1"] <= r["t1"]
            by_parent.setdefault(k["parent"], []).append(k)
        for seq, r in rounds.items():
            assert r["parent"] is None and r["wait_ms"] >= 0.0
            assert abs((r["t1"] - r["t0"]) * 1e3 - r["wall_ms"]) < 0.01
            inner = r["drain_ms"] + r["fanout_ms"] + sum(
                k["dispatch_ms"] + k["egress_ms"]
                for k in by_parent.get(seq, ())
            )
            assert inner <= r["wall_ms"] + 0.01, (r, by_parent.get(seq))
            assert by_parent.get(seq), "a recorded round dispatched"
        # a write staged before the round began waited for it
        assert any(r["wait_ms"] > 0 for r in rounds.values())
        assert any(n.commits for n in nodes.values())
    finally:
        coord.stop()


def _closed_rounds(rec, timeout_s=10.0):
    """The recorder's ``coord_round`` spans once none is still open (the
    round thread races ``flush()`` for what was staged)."""
    deadline = time.time() + timeout_s
    while True:
        rounds = [s for s in rec.spans() if s["kind"] == "coord_round"]
        if all("wall_ms" in s for s in rounds) or time.time() > deadline:
            return rounds
        time.sleep(0.005)


def _echo_cause_cases():
    from dragonboat_tpu.ops.state import READ_SLOTS

    def overflow(coord, cid, term):
        # every pending-read slot holds an unconfirmed batch: the next
        # context is refused one, and its echo is tallied scalar-side
        for low in range(1, READ_SLOTS + 2):
            coord.read_stage(cid, 1, low=low, high=low, term=term)
        coord.flush()
        coord.read_ack_hint(cid, 2, low=READ_SLOTS + 1, high=READ_SLOTS + 1)
        coord.flush()

    def late(coord, cid, term):
        # the first echo confirms the context on the device; the second
        # replica's echo of the same context comes after
        coord.read_stage(cid, 1, low=7, high=7, term=term)
        coord.read_ack_hint(cid, 2, low=7, high=7)
        coord.flush()
        coord.read_ack_hint(cid, 3, low=7, high=7)
        coord.flush()

    def purge(coord, cid, term):
        # a transition drops the group's FIFO under a staged context
        coord.read_stage(cid, 1, low=9, high=9, term=term)
        coord.flush()
        coord.set_follower(cid, term + 1)
        coord.read_ack_hint(cid, 2, low=9, high=9)
        coord.flush()

    return {"slot_overflow": overflow, "after_confirm": late,
            "purged": purge}




@pytest.mark.parametrize(
    "cause", ["slot_overflow", "after_confirm", "purged"]
)
def test_read_echo_fallbacks_are_counted_by_cause(cause):
    rec = FlightRecorder(stall_ms=0)
    reg = MetricsRegistry()
    coord, nodes = _coord_with_leaders([7], None)
    coord.enable_obs(recorder=rec, registry=reg, host="h")
    try:
        term = nodes[7].peer.raft.term
        _echo_cause_cases()[cause](coord, 7, term)
        causes = coord.read_fallback_causes
        assert causes[cause] == 1, causes
        assert sum(causes.values()) == coord.read_fallbacks == 1
        assert len(nodes[7].echoes) == 1  # the scalar tally got it
        snap = coord.health_snapshot()
        assert snap["read_fallback_causes"] == causes
        assert snap["read_fallbacks"] == 1
        assert snap["read_acks"] == coord.read_acks == (
            1 if cause == "after_confirm" else 0
        )
        if cause == "slot_overflow":
            assert coord.reads_refused == 1 and coord.reads_staged >= 1
        # per round on the span (a round that only tallied an echo
        # scalar-side dispatched nothing and has no span: its counts ride
        # the next round that has), and as the {cause} counter (no gauge)
        coord.ack(7, 2, 1)
        coord.flush()
        # the round thread races flush() for the staged ack: if it won,
        # its round may still be open (a span without its counts yet)
        rounds = _closed_rounds(rec)
        assert sum(s["read_fallback_" + cause] for s in rounds) == 1
        assert sum(s["read_acks"] for s in rounds) == coord.read_acks
        assert reg.counter_value(
            "dragonboat_coord_read_fallbacks_total", {"cause": cause}
        ) == 1
        out = io.StringIO()
        reg.write_health_metrics(out)
        text = out.getvalue()
        assert 'dragonboat_coord_read_fallbacks_total{cause="%s"} 1' % (
            cause) in text
        assert "dragonboat_coord_read_fallbacks " not in text
        assert "# TYPE dragonboat_coord_read_fallbacks gauge" not in text
    finally:
        coord.stop()


@pytest.mark.parametrize("obs_on", [True, False])
def test_round_span_counts_the_fan_in_by_origin(obs_on):
    """ISSUE 34: with the instruments attached a round's span carries the
    follower acknowledgements its drains handed on, the ReadIndex contexts
    a leader staged by origin (the host's own clients' against those a
    follower forwarded; a context refused a device slot counts too), and
    the contexts feed ``dragonboat_coord_reads_total{origin}``.  (The span
    no longer carries ``voters``, ISSUE 39: nothing read it.)  Detached, nothing of it is
    counted."""
    from dragonboat_tpu.ops.state import READ_SLOTS

    rec = FlightRecorder(stall_ms=0)
    reg = MetricsRegistry()
    coord, nodes = _coord_with_leaders([7, 8], None)
    if obs_on:
        coord.enable_obs(recorder=rec, registry=reg, host="h")
    try:
        term = nodes[7].peer.raft.term
        coord.read_stage(7, 1, low=1, high=1, term=term)
        coord.read_stage(8, 1, low=2, high=2, term=term, remote=True)
        coord.ack(7, 1, 1)  # the leader's own append: no follower's
        coord.ack(7, 2, 1)
        coord.ack(7, 3, 1)
        coord.flush()
        # more forwarded contexts than the group has slots: refused ones
        # are still contexts a follower forwarded
        for low in range(3, READ_SLOTS + 5):
            coord.read_stage(8, 1, low=low, high=low, term=term, remote=True)
        coord.ack(8, 2, 1)
        coord.flush()
        n_remote = 1 + READ_SLOTS + 2
        assert coord.reads_refused >= 1
        if not obs_on:
            assert coord._obs is None
            assert (coord.acks_drained, coord.reads_local,
                    coord.reads_remote) == (0, 0, 0)
            assert rec.spans() == []
            return
        assert (coord.acks_drained, coord.reads_local,
                coord.reads_remote) == (3, 1, n_remote)
        rounds = _closed_rounds(rec)
        assert sum(s["acks_drained"] for s in rounds) == 3
        assert sum(s["reads_local"] for s in rounds) == 1
        assert sum(s["reads_remote"] for s in rounds) == n_remote
        assert not any("voters" in s for s in rounds)
        assert sum(s["commits"] for s in rounds) == 2  # both groups
        for origin, n in (("local", 1), ("remote", n_remote)):
            assert reg.counter_value(
                "dragonboat_coord_reads_total", {"origin": origin}) == n
        out = io.StringIO()
        reg.write_health_metrics(out)
        text = out.getvalue()
        assert 'dragonboat_coord_reads_total{origin="remote"} %d' % (
            n_remote) in text
        assert "# HELP dragonboat_coord_reads_total" in text
    finally:
        coord.stop()


def test_compilation_log_names_program_and_thread(tmp_path):
    import threading

    import jax
    import jax.numpy as jnp

    from dragonboat_tpu.ops.engine import (
        compilation_cache_stats,
        compilation_log,
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache(str(tmp_path))
    before = len(compilation_log())
    counted = compilation_cache_stats()

    def compiled_on_purpose_for_issue26(x):
        return x * 3 + 1

    def work():
        jax.jit(compiled_on_purpose_for_issue26)(jnp.arange(7))

    t0 = time.perf_counter()
    th = threading.Thread(target=work, name="compile-on-purpose")
    th.start()
    th.join()
    t1 = time.perf_counter()
    new = compilation_log()[before:]
    mine = [e for e in new
            if "compiled_on_purpose_for_issue26" in e[2]]
    assert mine, new
    c0, c1, program, thread, verdict = mine[0]
    assert thread == "compile-on-purpose"
    assert t0 <= c0 <= c1 <= t1
    assert verdict in ("hit", "miss", "uncached")
    after = compilation_cache_stats()
    assert (after["hits"] + after["misses"]) - (
        counted["hits"] + counted["misses"]
    ) == sum(1 for e in new if e[4] != "uncached")


def _host_events(trace_dir):
    """name -> [duration ns] over every host thread of the newest
    capture under ``trace_dir``; plus per-thread (name, start, dur)."""
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, os.listdir(trace_dir)
    by_name, lines = {}, []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                   for e in line.events if e.name.startswith("dbtpu:")]
            if evs:
                lines.append(evs)
            for name, _s, d in evs:
                by_name.setdefault(name, []).append(d)
    return by_name, lines


def test_profiler_capture_holds_the_same_spans_as_the_ring(tmp_path):
    """The phases are on the profiler's clock too: a capture holds one
    ``dbtpu:*`` event per occurrence of a phase, and their count and
    length agree with the ring's spans of the same interval."""
    import statistics

    import jax

    rec = FlightRecorder(stall_ms=0)
    # 16,384 rows make a round ~2 ms here, so that 5% governs the round's
    # comparison: its annotation holds eleven nested ones (~30-50 us)
    coord, nodes = _coord_with_leaders([3, 4, 5], rec, capacity=16384)
    try:
        for _ in range(6):  # every program compiled before the capture
            for cid in nodes:
                coord.ack(cid, 2, 1)
            coord.flush()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            t_a = time.perf_counter()
            for _ in range(40):
                for cid in nodes:
                    coord.ack(cid, 2, 1)
                coord.flush()
            with coord._mu:  # no round in flight across the edge
                t_b = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
        by_name, lines = _host_events(str(tmp_path))
        for name in ("dbtpu:round", "dbtpu:drain", "dbtpu:stage",
                     "dbtpu:launch", "dbtpu:retire", "dbtpu:egress_wait",
                     "dbtpu:decode", "dbtpu:fanout"):
            assert by_name.get(name), (name, sorted(by_name))
        # the put rides the launch: no phase of its own any more
        assert "dbtpu:transfer" not in by_name
        spans = [s for s in rec.spans() if t_a <= s["t0"] < t_b]
        rounds = [s for s in spans if s["kind"] == "coord_round"]
        kids = [s for s in spans if s["kind"] in ("dispatch", "fused")]
        assert len(rounds) >= 30

        def agree(events_ns, field_ms, what):
            # the same spans seen twice: as many events as spans, of the
            # same median length (5%, or 30 us of annotation overhead)
            assert abs(len(events_ns) - len(field_ms)) <= max(
                1, 0.05 * len(field_ms)), (what, len(events_ns),
                                           len(field_ms))
            a = statistics.median(events_ns) / 1e6
            b = statistics.median(field_ms)
            assert abs(a - b) <= max(0.05 * b, 0.03), (what, a, b)

        # a dbtpu:round that dispatched holds a dbtpu:fanout; the quiet
        # polls of the round thread hold none and have no span
        dispatched = []
        for evs in lines:
            fans = [(s, s + d) for n, s, d in evs if n == "dbtpu:fanout"]
            for n, s, d in evs:
                if n == "dbtpu:round" and any(
                    s <= f0 and f1 <= s + d for f0, f1 in fans
                ):
                    dispatched.append(d)
        agree(dispatched, [(s["t1"] - s["t0"]) * 1e3 for s in rounds],
              "round")
        agree(by_name["dbtpu:fanout"], [s["fanout_ms"] for s in rounds],
              "fanout")
        agree(by_name["dbtpu:launch"], [s["launch_ms"] for s in kids],
              "launch")
        assert all(s["transfer_ms"] == 0.0 for s in kids)
        # the replaced blocks die after the fan-out, late on the span
        agree(by_name["dbtpu:retire"], [s["retire_ms"] for s in kids],
              "retire")
        agree(by_name["dbtpu:egress_wait"],
              [s["egress_wait_ms"] for s in kids], "egress_wait")
        # a step stages in several blocks: the phase field is their sum
        total = sum(by_name["dbtpu:stage"]) / 1e6
        field = sum(s["stage_ms"] for s in kids)
        assert abs(total - field) <= max(0.05 * field, 0.5), (total, field)
    finally:
        coord.stop()
