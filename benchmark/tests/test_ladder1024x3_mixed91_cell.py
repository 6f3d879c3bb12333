"""``ladder1024x3.mixed91`` resolves from the names in ``BENCHMARK.json``
alone: the configuration ``ladder1024x3`` as it is, the reference beside
it, a traffic file of its own (``mixed91_x5.json``'s draw at this cluster's
rate), the end-to-end metrics it reports, a reader for every per-layer
metric it inherits.  Every seed gets the same work.  Runs nothing."""
import collections
import json
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.generator import READ, WRITE, open_schedule

CELL = "ladder1024x3.mixed91"
BLOCK_PLANE = ("hb_block_pct.lat", "rows_per_round.lat",
               "ticks_dropped_per_s.lat", "tick_flags_per_round.lat")


@pytest.fixture(params=["as_committed", "with_later_additions"])
def root(request, tmp_path):
    """The repo, and a copy to which a later PR has added a cell, a
    per-layer metric and an end-to-end metric as entries only: these tests
    hold this cell, and pass whatever is appended beside it."""
    if request.param == "as_committed":
        return harness.ROOT
    for sub in ("configs", "traffic", "layers"):
        shutil.copytree(os.path.join(harness.ROOT, "benchmark", sub),
                        tmp_path / "benchmark" / sub)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    bench["workloads"].append({
        "name": "ladder1024x3.lease91", "config": "ladder1024x3",
        "traffic": "mixed91_g1024", "chips": 1, "why": "a later cell"})
    bench["end_to_end"].append({
        "name": "read_p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
        "source": "host_clock"})
    bench["per_layer"].append({
        "name": "gen_late_ms.read", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "client API",
        "moves": "read_p50_ms"})
    for m in bench["end_to_end"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("ladder1024x3.lease91")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_the_cell_resolves_by_name(root):
    cell = harness.Cell(CELL, root=root)
    assert cell.entry["chips"] == 1 and 1 <= len(cell.entry["why"]) <= 200
    # the configuration is the write cell's own file, with no block of
    # Raft settings: the Config every replica gets is the one it got
    write_cell = harness.Cell("ladder1024x3.write_closed", root=root)
    assert cell.config == write_cell.config
    assert (cell.config["groups"], cell.config["replicas"]) == (1024, 3)
    assert "group_config" not in cell.config
    assert set(cell.reference.LIMITS.values()) == {0}
    ref = cell.reference.cluster(cell.config, 1)
    assert len(ref.cids) == 1024 and ref.replicas == 3
    # the traffic: mixed91_x5's keys and values, a rate of its own
    t = cell.traffic
    x5 = harness.Cell("ladder512x5.mixed91", root=root).traffic
    assert set(t) == set(x5)
    assert {k: t[k] for k in t if k not in ("rate_ops_per_s", "why")} == {
        k: x5[k] for k in x5 if k not in ("rate_ops_per_s", "why")} == {
        "loop": "open", "read_share": 0.9, "keys_per_group": 16,
        "read_host": "any", "read_newest_share": 0.5,
        "attempt_timeout_s": 5.0, "deadline_s": 30.0, "warmup_s": 3.0}
    rate = t["rate_ops_per_s"]
    assert isinstance(rate, float) and rate >= 150 and rate % 10 == 0
    assert "of the knee" in t["why"]
    # what it reports: the two medians and set-up, no throughput
    assert [m["name"] for m in cell.metrics("end_to_end")
            if m["name"] != "read_p95_ms"] == [
        "write_p50_ms", "read_p50_ms", "setup_s"]
    names = {m["name"] for m, mod in cell.readers() if callable(mod.read)}
    assert names and not any(n.endswith(".tput") for n in names)
    # every metric of ladder512x5.mixed91 is this cell's too, the block
    # heartbeat plane's four by their lists
    assert names == {m["name"] for m in harness.Cell(
        "ladder512x5.mixed91", root=root).metrics("per_layer")}
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    for n in BLOCK_PLANE:
        assert n in names and by_name[n]["workloads"][:2] == [
            "ladder512x5.mixed91", CELL]
    for n in ("quorum_step_roofline.lat", "kernel_us_per_dispatch.lat",
              "read_fallback_pct.read", "read_slot_overflow_pct.read",
              "read_forwarded_pct.read", "compiles_in_window.lat",
              "leader_changes.lat", "retries_per_kop.lat"):
        assert n in names


@pytest.mark.parametrize("seed", [1, 36, 2**31 + 36])
def test_every_seed_gets_the_same_work(seed):
    """``rate x 48`` arrivals in the window, a tenth of them writes, the
    same count of operations a group: only their order and instants are the
    seed's."""
    cell = harness.Cell(CELL)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    seconds, warmup = bench["run_seconds"], cell.traffic["warmup_s"]
    rate = cell.traffic["rate_ops_per_s"]
    cids = list(range(1, cell.config["groups"] + 1))
    due = [(t, kind, cid) for t, kind, cid in open_schedule(
        cell.traffic, cids, seed, seconds, warmup) if t >= 0]
    assert len(due) == round(rate * seconds)
    kinds = collections.Counter(kind for _t, kind, _c in due)
    assert kinds[WRITE] == round(rate * seconds * 0.1)
    assert set(kinds) == {READ, WRITE}
    other = [(t, kind, cid) for t, kind, cid in open_schedule(
        cell.traffic, cids, seed + 1, seconds, warmup) if t >= 0]
    per_group = collections.Counter(cid for _t, _k, cid in due)
    assert per_group == collections.Counter(cid for _t, _k, cid in other)
    assert max(per_group.values()) - min(
        per_group.get(cid, 0) for cid in cids) <= 1
    assert [x[0] for x in due] != [x[0] for x in other]
    assert json.dumps(due) == json.dumps([
        x for x in open_schedule(cell.traffic, cids, seed, seconds, warmup)
        if x[0] >= 0])


def test_the_kernel_readers_read_a_stretch_that_ran_the_dense_step_alone():
    """A round that carries reads runs ``quorum_step_dense_impl``; in one
    traced run of this cell in three the profiled 6 s dispatched nothing
    else, and readers that counted the sparse and the fused program alone
    left both kernel metrics out of the line."""
    from benchmark import reduce

    cell = harness.Cell(CELL)
    mods = {m["name"]: mod for m, mod in cell.readers()
            if m["name"] in ("kernel_us_per_dispatch.lat",
                             "quorum_step_roofline.lat")}
    assert len(mods) == 2
    kernels = {k for mod in mods.values() for k in mod.KERNELS}
    step = [("jit_quorum_step_dense_impl(7)", 1000 + 40_000 * i, 30_000)
            for i in range(3)]
    reduced = reduce.reduce_trace({"planes": [{
        "name": reduce.DEVICE_PLANE + "0", "lines": [
            {"name": reduce.OP_LINES[0], "events": step},
            {"name": reduce.MODULE_LINE, "events": step}]}]}, kernels)
    assert reduced["kernel_n"] == {"quorum_step_dense_impl": 3}
    leaves = [((40, 1024), "int32"), ((12, 1024), "int8")]
    ctx = harness.Ctx(trace=reduced, state_leaves=leaves,
                      device_kind="TPU v5 lite")
    assert mods["kernel_us_per_dispatch.lat"].read(ctx) == pytest.approx(30.0)
    share = mods["quorum_step_roofline.lat"].read(ctx)
    assert 0 < share < 100
    # and a stretch in which no quorum program ran is left out, never 0
    empty = harness.Ctx(trace={"kernel_n": {}, "kernel_s": {}},
                        state_leaves=leaves, device_kind="TPU v5 lite")
    assert all(mod.read(empty) is None for mod in mods.values())
