"""``ladder1024x3.write_closed`` live on the CPU backend at smaller sizes:
the benchmark's own ``LiveCluster`` and ``run`` (the harness a chip run
uses), the cell's own traffic, held against the plain reference.

The tier-1 form (128 groups, a few seconds) asserts what no load on the
host can change: ``correct`` and ``failed`` 0.  It asserts nothing about
timing.  The 256- and 1,024-group forms do assert "no leader change from
the end of the elections to the end of the window, a quiet 20 s included";
they are marked ``slow``: minutes of set-up on a CPU, and a claim about
wall-clock behaviour that a suite running six workers wide cannot hold.
"""
from __future__ import annotations

import time

import pytest

from benchmark import run as harness
from benchmark.cluster import LiveCluster

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _cell(groups: int, rtt_ms: int, warmup_s: float):
    cell = harness.Cell("ladder1024x3.write_closed")
    cell.config = dict(cell.config, groups=groups)
    cell.config["assumed"] = dict(
        cell.config["assumed"], rtt_millisecond=rtt_ms,
        engine_block_groups=groups)
    cell.traffic = dict(cell.traffic, warmup_s=warmup_s)
    return cell


def _settled(cluster, quiet_s: float, timeout_s: float = 120.0) -> float:
    """The end of the elections: no leader event for ``quiet_s`` (the
    harness's explicit campaigns land after ``LiveCluster`` returns where
    a group had already elected by itself)."""
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        ev = cluster.leaders.events
        last = ev[-1][0] if ev else 0.0
        if time.perf_counter() - last >= quiet_s:
            return time.perf_counter()
        time.sleep(0.1)
    raise AssertionError("elections did not come to an end")


def _run(groups, rtt_ms, seconds, seed, quiet_s=0.0, warmup_s=1.0):
    cell = _cell(groups, rtt_ms, warmup_s)
    cluster = LiveCluster(cell.config, "")
    try:
        t_led = _settled(cluster, 4 * 2 * 10 * rtt_ms / 1e3) if quiet_s else 0
        time.sleep(quiet_s)
        result = harness.run(cell, cluster, seed, seconds, False, DEVICE,
                             True, setup_clock=lambda: 0.0)
        changes = [e for e in cluster.leaders.events if e[0] >= t_led]
        coords = [(c.hb_block_rows, dict(c.hb_single_causes),
                   c.ticks_dropped) for c in cluster.coords]
    finally:
        cluster.stop()
    return result, changes, coords


def _held(result):
    assert result["correct"] is True, result["compared"]
    assert all(c["value"] == 0 for c in result["compared"].values())
    assert result["failed"] == 0 and result["attempted"] > 0


def test_128_groups_hold_the_reference_under_the_cells_traffic():
    result, _changes, coords = _run(128, 200, 4.0, seed=29)
    _held(result)
    assert result["attempted"] >= 128  # every group wrote
    # the heartbeats went by the block (a count, not a timing)
    assert all(rows > 0 for rows, _c, _d in coords)


@pytest.mark.slow
@pytest.mark.parametrize("groups,rtt_ms", [(256, 200), (256, 50),
                                           (1024, 200)])
def test_a_led_cluster_stays_led(groups, rtt_ms):
    result, changes, _coords = _run(groups, rtt_ms, 15.0, seed=29,
                                    quiet_s=20.0, warmup_s=3.0)
    _held(result)
    assert not changes, (len(changes), changes[:5])
