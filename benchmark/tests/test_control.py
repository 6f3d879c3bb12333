"""The comparison that decides ``correct`` has been shown to fail.

Every cell of ``BENCHMARK.json`` is driven through the harness's own
``run`` (generator, drain, comparison, result) with the plain reference in
the program's place: sound it comes out correct, with one stated guarantee
given up it does not.  Then the program itself, at a size a test run can
hold: sound, with its weaker read path switched on, and with the timed path
broken underneath (one replica's state machine dropping updates).
"""
import json
import os

import pytest

from benchmark import control, run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def small(cell_name, groups=6, seconds_rate=None):
    cell = harness.Cell(cell_name)
    cell.config = dict(cell.config, groups=groups)
    cell.traffic = dict(cell.traffic, warmup_s=0.2)
    if seconds_rate is not None:
        cell.traffic["rate_ops_per_s"] = seconds_rate
    return cell


def reads(cell):
    return cell.traffic["read_share"] > 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_reference_in_the_programs_place(cell_name):
    cell = small(cell_name)
    sound = harness.run(cell, control.build("reference", cell, 1), 1, 0.5,
                        False, DEVICE, True, setup_clock=lambda: 0.0)
    assert sound["correct"] and sound["attempted"] > 0
    assert all(c["value"] == 0 for c in sound["compared"].values())
    assert list(sound)[-1] == "compared"  # the compared numbers come last

    lossy = harness.run(
        cell, control.build("reference:ack_before_quorum", cell, 1), 1, 0.5,
        False, DEVICE, True, setup_clock=lambda: 0.0)
    assert not lossy["correct"]
    assert lossy["compared"]["lost_acked_writes"]["value"] > 0

    stale = harness.run(
        cell, control.build("reference:stale_read", cell, 1), 1, 0.5,
        False, DEVICE, True, setup_clock=lambda: 0.0)
    if reads(cell):
        assert not stale["correct"]
        assert stale["compared"]["wrong_reads"]["value"] > 0
    else:
        assert stale["correct"]  # a cell without reads can not see it


@pytest.fixture(scope="module")
def cpu():
    from dragonboat_tpu import hostplatform

    hostplatform.force_cpu()


def drive(cell, name, seed, seconds):
    cluster = control.build(name, cell, seed)
    try:
        return harness.run(cell, cluster, seed, seconds, False, DEVICE, True,
                           setup_clock=lambda: 0.0)
    finally:
        cluster.stop()


def test_program_sound_then_broken_underneath(cpu):
    """Skips the harness's look for a chip and drives the rest of a run."""
    cell = small("upstream48x3.write_closed")
    sound = drive(cell, "none", 2**31 + 11, 1.5)
    assert sound["correct"] and sound["failed"] == 0
    assert sound["attempted"] > 100
    broken = drive(cell, "program:dropped_apply", 2**31 + 11, 1.5)
    assert not broken["correct"]
    assert broken["compared"]["lost_acked_writes"]["value"] > 0
    assert broken["compared"]["divergent_groups"]["value"] > 0


def test_program_with_its_stale_read_path_switched_on(cpu):
    """Every seed on a cluster of its own: each has to fail alone."""
    cell = small("upstream48x3.mixed91", seconds_rate=300.0)
    # at six groups and a few seconds, make every read the racing kind
    cell.traffic.update(read_newest_share=1.0)
    sound = drive(cell, "none", 5, 3.0)
    assert sound["correct"] and sound["failed"] == 0
    for seed in (5, 2**31 + 6, 7):
        stale = drive(cell, "program:stale_read", seed, 3.0)
        assert not stale["correct"]
        assert stale["compared"]["wrong_reads"]["value"] > 0
