"""Device capacity & profiling plane suite (ISSUE 15).

Contracts under test:

- profile-OFF structural identity: ``device_profile=0`` constructs
  nothing — ``NodeHost.devprof`` is None, the engine keeps its
  bit-identical ``_devprof=None`` latch, no ``dragonboat_devprof_*``
  families exist and ``profile_device`` refuses;
- the HBM ledger prices EXACTLY the live device arrays (cpu backend:
  byte-identical per plane across devsm/read/vote shape combinations,
  including the in-flight pipelined double buffer), and the capacity
  model's prediction matches the measured resident bytes (0% error by
  construction — the acceptance bound is 10%);
- the capacity model's per-dispatch term reproduces the engine's own
  ``upload_nbytes`` accounting for a padded fused dispatch (the shared
  helper can't drift from the tensors actually shipped);
- the program registry covers the WHOLE warm set (``warm_plan`` is the
  single enumeration) with non-zero cost/memory analysis per program;
- padding-waste accounting against a forced K=16 backlog with 2 live
  rounds (14 provable no-op rounds);
- the read-only ``/debug/devprof`` endpoint round-trips (404 while the
  plane is off) and ``NodeHost.profile_device`` opens/closes a
  ``jax.profiler`` capture window whose artifact lands on disk.
"""
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dragonboat_tpu import Config, NodeHostConfig, Result
from dragonboat_tpu.config import ExpertConfig
from dragonboat_tpu.events import MetricsRegistry
from dragonboat_tpu.nodehost import NodeHost
from dragonboat_tpu.obs import FlightRecorder
from dragonboat_tpu.obs.devprof import DevProf, predict_bytes
from dragonboat_tpu.ops.engine import (
    WARM_K_BUCKETS,
    BatchedQuorumEngine,
    upload_nbytes,
)
from dragonboat_tpu.ops.state import (
    DEVSM_PLANE_FIELDS,
    READ_PLANE_FIELDS,
    field_plane,
    state_layout,
)
from dragonboat_tpu.transport import ChanRouter, ChanTransport

from tests.loadwait import wait_until

RTT_MS = 5
CID = 940


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


def _mk_host(addr="dp:1", router=None, engine="tpu", device_profile=0,
             metrics_addr="", tmpdir=None):
    router = router or ChanRouter()
    return NodeHost(
        NodeHostConfig(
            node_host_dir=tmpdir or ":memory:",
            rtt_millisecond=RTT_MS,
            raft_address=addr,
            raft_rpc_factory=lambda s, rh, ch: ChanTransport(
                s, rh, ch, router=router
            ),
            enable_metrics=True,
            device_profile=device_profile,
            metrics_addr=metrics_addr,
            expert=ExpertConfig(
                quorum_engine=engine,
                engine_block_groups=64,
                engine_warm_fused=False,
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


def _mk_engine(g=64, p=3, **kw):
    return BatchedQuorumEngine(n_groups=g, n_peers=p, **kw)


def _lead(eng, cid=1, n=3):
    eng.add_group(cid, list(range(1, n + 1)), self_id=1)
    eng.set_leader(cid, term=1, term_start=1, last_index=1)


def _live_plane_bytes(eng):
    planes = {}
    for name, arr in eng.dev._asdict().items():
        p = field_plane(name)
        planes[p] = planes.get(p, 0) + int(arr.nbytes)
    return planes


# ----------------------------------------------------------------------
# profile OFF: structural identity
# ----------------------------------------------------------------------


def test_devprof_off_structural_identity():
    eng = _mk_engine()
    assert eng._devprof is None
    _lead(eng)
    eng.ack(1, 2, 3)
    eng.step()
    assert eng._devprof is None  # the latch never flips on its own

    nh = _mk_host(device_profile=0)
    try:
        _start(nh)
        assert nh.devprof is None
        assert nh.quorum_coordinator.devprof is None
        assert nh.quorum_coordinator.eng._devprof is None
        s = nh.get_noop_session(CID)
        for _ in range(3):
            assert nh.sync_propose(s, b"x", timeout=10.0)
        assert nh.quorum_coordinator.eng._devprof is None
        assert not any(
            f.startswith("dragonboat_devprof_")
            for f in nh.metrics_registry.families()
        )
        with pytest.raises(RuntimeError):
            nh.profile_device(10)
    finally:
        nh.stop()


def test_plane_fields_match_engine_latch_keys():
    """The ledger's plane classification and the engine's latch-gated
    sync keys are the SAME field sets — a field added to one but not
    the other would let resident state escape its plane."""
    assert tuple(READ_PLANE_FIELDS) == tuple(BatchedQuorumEngine._READ_KEYS)
    assert tuple(DEVSM_PLANE_FIELDS) == tuple(BatchedQuorumEngine._KV_KEYS)
    from dragonboat_tpu.ops.state import TELEM_PLANE_FIELDS
    assert tuple(TELEM_PLANE_FIELDS) == tuple(BatchedQuorumEngine._TELEM_KEYS)


# ----------------------------------------------------------------------
# pillar 1: HBM ledger ≡ live arrays, across shape combinations
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "g,p,kw",
    [
        (64, 3, {}),
        (32, 5, {}),
        (16, 3, dict(n_kv_slots=8, n_kv_ents=8)),
    ],
)
def test_ledger_matches_live_bytes(g, p, kw):
    eng = _mk_engine(g, p, **kw)
    dp = DevProf(registry=MetricsRegistry(), sample_every=1)
    dp.bind_engine(eng)
    _lead(eng, n=min(p, 3))

    def check():
        led = dp.hbm_ledger()
        live = _live_plane_bytes(eng)
        assert led["planes"]["quorum"] == live["quorum"]
        assert led["planes"]["read"] == live["read"]
        assert led["planes"]["devsm"] == live["devsm"]
        assert led["state_bytes"] == sum(live.values())
        cap = led["capacity"]
        # acceptance bound is 10%; on the cpu backend the eval_shape
        # walk is exact by construction
        assert abs(cap["model_error_pct"]) < 10.0
        assert cap["bytes_per_group"] * g == cap["state_bytes"]
        return led

    check()  # bare engine
    eng.ack(1, 2, 3)
    eng.vote(1, 2, True)
    eng.step()
    check()  # after a vote-carrying dispatch
    # read plane live
    eng.stage_read(1, count=2, index=1)
    eng.read_ack(1, 2, 0)
    eng.step()
    check()
    # devsm plane live
    eng.stage_kv_ops(1, [2], [0], [7])
    eng.step()
    check()


def test_ledger_prices_inflight_double_buffer():
    eng = _mk_engine()
    dp = DevProf(sample_every=10_000)  # no registry, no sampling block
    dp.bind_engine(eng)
    _lead(eng)
    eng.ack(1, 2, 3)
    eng.begin_round()
    assert eng.step_rounds(pipelined=True) is None  # leaves one in flight
    led = dp.hbm_ledger()
    assert led["artifacts"]["dispatch"]["inflight_egress"] > 0
    assert led["total_bytes"] > led["state_bytes"]
    eng.harvest()
    led = dp.hbm_ledger()
    assert "dispatch" not in led["artifacts"]


# ----------------------------------------------------------------------
# pillar 1b: capacity model
# ----------------------------------------------------------------------


def test_capacity_model_extrapolates_linearly_and_budgets():
    a = predict_bytes(1024, 3)
    b = predict_bytes(2048, 3)
    assert b["state_bytes"] == 2 * a["state_bytes"]
    assert a["bytes_per_group"] == b["bytes_per_group"]
    # geometry changes the per-group figure
    wide = predict_bytes(1024, 8)
    assert wide["bytes_per_group"] > a["bytes_per_group"]

    eng = _mk_engine(64, 3)
    dp = DevProf()
    dp.bind_engine(eng)
    cap = dp.capacity_model(budget_bytes=1 << 30)
    per = cap["bytes_per_group_with_dispatch"]
    assert cap["max_groups"] == int((1 << 30) // per)
    # cpu backend reports no memory budget: max_groups degrades to None
    assert dp.capacity_model()["max_groups"] is None


def test_dispatch_term_matches_upload_accounting():
    """The capacity model's per-dispatch upload term reproduces the
    engine's own ``upload_nbytes`` accounting for a padded fused
    dispatch — the consolidation satellite's no-drift guarantee,
    asserted through the recorded span."""
    from dragonboat_tpu import obs as obs_mod

    g, p = 64, 3
    eng = _mk_engine(g, p)
    rec = FlightRecorder(capacity=16, stall_ms=0)
    eng.enable_obs(recorder=rec, registry=MetricsRegistry())
    _lead(eng)
    k = max(WARM_K_BUCKETS)
    eng.ack(1, 2, 3)
    eng.begin_round()
    eng.step_rounds(do_tick=True, pad_rounds_to=k, tick_rounds=2)
    span = [s for s in rec.spans() if s["kind"] == "fused"][-1]
    pred = predict_bytes(g, p, k_bucket=k)
    assert span["upload_bytes"] == pred["dispatch_bytes"], (
        span["upload_bytes"], pred["dispatch_bytes"],
    )


def test_predict_dispatch_term_matches_variant_spec_all_planes():
    """The closed-form dispatch term agrees with the abstract argument
    spec the warmup/lowering builder produces, for EVERY plane
    combination (the no-drift guard the capacity model's live path now
    derives from directly — a stage-tensor dtype/shape change breaks
    this test instead of silently mispricing the model)."""
    import numpy as np
    from dragonboat_tpu.obs.devprof import _spec_nbytes

    g, p = 16, 3
    eng = _mk_engine(g, p)
    k = max(WARM_K_BUCKETS)
    for ir in (False, True):
        for ik in (False, True):
            _, ing, _ = eng._variant_args(
                "fused", k, ir, ik, abstract=True
            )
            args = (ing,)
            pred = predict_bytes(
                g, p, k_bucket=k, include_reads=ir, include_kv=ik
            )
            assert _spec_nbytes(args) == pred["dispatch_bytes"], (ir, ik)


# ----------------------------------------------------------------------
# pillar 2: program registry covers the warm set
# ----------------------------------------------------------------------


def test_program_registry_covers_whole_warm_set():
    reg = MetricsRegistry()
    eng = _mk_engine(16, 3, event_cap=64)
    dp = DevProf(registry=reg)
    dp.bind_engine(eng)
    rows = dp.collect_programs(include_kv=True)
    plan = eng.warm_plan(include_kv=True)
    assert [r["variant"] for r in rows] == [
        eng.variant_label(*v) for v in plan
    ]
    for r in rows:
        assert "error" not in r, r
        assert r["flops"] > 0, r
        assert r["bytes_accessed"] > 0, r
        assert r["temp_bytes"] >= 0 and r["output_bytes"] > 0, r
        assert r["compile_ms"] > 0, r
    # every variant's gauges published
    for r in rows:
        assert reg.gauge_value(
            "dragonboat_devprof_program_flops",
            labels={"variant": r["variant"]},
        ) == r["flops"]
    assert reg.gauge_value("dragonboat_devprof_programs") == len(rows)
    # cached: a second collect returns the same rows without recompiling
    t0 = time.perf_counter()
    again = dp.collect_programs()
    assert again == rows
    assert time.perf_counter() - t0 < 1.0


# ----------------------------------------------------------------------
# pillar 3: device-time estimator + padding waste
# ----------------------------------------------------------------------


def test_padding_waste_gauge_against_forced_k16_backlog():
    reg = MetricsRegistry()
    eng = _mk_engine()
    rec = FlightRecorder(capacity=16, stall_ms=0)
    eng.enable_obs(recorder=rec, registry=reg)
    dp = DevProf(registry=reg, sample_every=1)
    dp.bind_engine(eng)
    _lead(eng)
    eng.ack(1, 2, 3)
    eng.begin_round()
    eng.step_rounds(do_tick=True, pad_rounds_to=16, tick_rounds=2)
    st = dp.estimator_stats()
    assert st["padded_rounds"] == 16
    assert st["wasted_rounds"] == 14  # 16-round program, 2 live rounds
    assert st["padding_waste_ratio"] == round(14 / 16, 4)
    assert st["sampled"] == 1 and st["device_ms"]["n"] == 1
    assert reg.counter_value(
        "dragonboat_devprof_wasted_rounds_total"
    ) == 14
    assert reg.counter_value(
        "dragonboat_devprof_padded_rounds_total"
    ) == 16
    assert reg.gauge_value(
        "dragonboat_devprof_padding_waste_ratio"
    ) == round(14 / 16, 4)
    h = reg.histogram_value("dragonboat_devprof_device_ms")
    assert h is not None and h[3] >= 1
    # the sampled delta lands on the dispatch's recorder span
    span = [s for s in rec.spans() if s["kind"] == "fused"][-1]
    assert span.get("device_ms", 0) > 0


def test_estimator_sampling_stride():
    eng = _mk_engine()
    dp = DevProf(sample_every=4)
    dp.bind_engine(eng)
    _lead(eng)
    for i in range(8):
        eng.ack(1, 2, 2 + i)
        eng.step()
    st = dp.estimator_stats()
    assert st["dispatches"] == 8
    assert st["sampled"] == 2  # the 1st and the 5th (stride 4)


# ----------------------------------------------------------------------
# pillar 4 + endpoint: capture windows, /debug/devprof, profile_device
# ----------------------------------------------------------------------


def test_capture_window_lifecycle(tmp_path):
    eng = _mk_engine(16, 3)
    reg = MetricsRegistry()
    dp = DevProf(registry=reg, artifact_dir=str(tmp_path),
                 sample_every=10_000)
    dp.bind_engine(eng)
    _lead(eng)
    d = dp.capture(ms=200)
    assert dp.capture_active
    assert d.startswith(str(tmp_path))
    with pytest.raises(RuntimeError):
        dp.capture(ms=10)  # one window at a time
    assert reg.counter_value("dragonboat_devprof_captures_total") == 1
    assert reg.gauge_value("dragonboat_devprof_capture_active") == 1
    eng.ack(1, 2, 3)
    eng.step()  # device work inside the window
    wait_until(lambda: not dp.capture_active, timeout=10.0,
               what="capture window closed")
    assert reg.gauge_value("dragonboat_devprof_capture_active") == 0
    files = [
        os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
    ]
    assert files, "capture window wrote no artifact"
    caps = dp.captures()
    assert len(caps) == 1 and caps[0]["stopped"] is not None
    # early stop path
    d2 = dp.capture(ms=60_000)
    assert dp.stop_capture() == d2
    assert not dp.capture_active
    # to_json is read-only and carries all four pillars
    j = dp.to_json()
    assert j["ledger"]["state_bytes"] > 0
    assert j["estimator"]["dispatches"] >= 1
    assert len(j["captures"]) == 2
    assert j["programs"] is None  # reading never triggered compiles


def test_debug_devprof_endpoint_round_trip(tmp_path):
    nh = _mk_host(
        device_profile=1, metrics_addr="127.0.0.1:0",
        tmpdir=str(tmp_path),
    )
    try:
        _start(nh)
        assert nh.devprof is not None
        assert nh.quorum_coordinator.eng._devprof is nh.devprof
        s = nh.get_noop_session(CID)
        for _ in range(5):
            nh.sync_propose(s, b"x", timeout=10.0)
        port = nh.metrics_server.port
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/devprof", timeout=10
        ) as resp:
            assert resp.status == 200
            d = json.loads(resp.read())
        assert d["ledger"]["planes"]["quorum"] > 0
        assert d["ledger"]["capacity"]["bytes_per_group"] > 0
        assert d["estimator"]["dispatches"] > 0
        # the devprof families ride the same /metrics exposition
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
        assert "dragonboat_devprof_hbm_plane_bytes" in text
        # profile_device writes its artifact beside the host dir
        cap_dir = nh.profile_device(150)
        assert cap_dir.startswith(str(tmp_path))
        wait_until(
            lambda: not nh.devprof.capture_active, timeout=10.0,
            what="profile window closed",
        )
        assert any(os.scandir(cap_dir))
    finally:
        nh.stop()


def test_debug_devprof_endpoint_404_when_off():
    nh = _mk_host(engine="scalar", metrics_addr="127.0.0.1:0")
    try:
        _start(nh)
        port = nh.metrics_server.port
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/devprof", timeout=10
            )
        assert ei.value.code == 404
    finally:
        nh.stop()
