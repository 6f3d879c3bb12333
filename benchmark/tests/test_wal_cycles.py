"""``layers/wal_syncs_per_cycle`` and ``layers/wal_cycle_ms``: the committer
cycles' durable write batches and save seconds over the window's whole
seconds and every live tracer, the sums on one earlier line; ``None`` where
the program keeps no such count or no cycle ran in the window."""
import json
from types import SimpleNamespace

import pytest

from dragonboat_tpu.obs.trace import Tracer

from test_span_readers import T0, T_END, ctx, reader


def tracer(by_second):
    return SimpleNamespace(wal_cycles=lambda: by_second)


def test_readers_take_the_windows_sums_over_every_tracer(capsys):
    a = tracer({int(T0) - 1: (500, 2000, 900, 9.0),
                int(T0): (10, 38, 60, 0.25),
                int(T0) + 7: (30, 30, 100, 0.15),
                int(T_END): (9, 36, 9, 0.9)})
    b = tracer({int(T0) + 20: (10, 12, 40, 0.1)})
    got = reader("wal_syncs_per_cycle").read(ctx(), tracers=[a, b])
    assert got == pytest.approx(80 / 50)
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "event": "wal_cycles", "cycles": 50, "sync_batches": 80,
        "updates": 200, "commit_s": 0.5}
    # the cycle's wall time: the same selection, no second line
    got = reader("wal_cycle_ms").read(ctx(), tracers=[a, b])
    assert got == pytest.approx(1000.0 * 0.5 / 50)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("family", ["wal_syncs_per_cycle", "wal_cycle_ms"])
def test_readers_return_none_without_the_counter_or_a_cycle(family):
    mod = reader(family)
    # the parent commit's tracer: hand-offs, no cycle series
    old = SimpleNamespace(apply_handoffs=lambda: {int(T0): (9, 1)})
    one = tracer({int(T0): (4, 4, 9, 0.02)})
    assert mod.read(ctx(), tracers=[old]) is None
    assert mod.read(ctx(), tracers=[one, old]) is None
    assert mod.read(ctx(), tracers=[]) is None
    assert mod.read(ctx(), tracers=[tracer({})]) is None
    assert mod.read(ctx(), tracers=[tracer({int(T0) - 5: (4, 4, 9, 0.02)})]) \
        is None
    assert mod.read(ctx(), tracers=[one]) is not None


def test_readers_read_what_the_programs_tracer_keeps():
    tr = Tracer(sample_every=1)
    try:
        tr.count_wal_cycle(1, 5, 0.008)
        tr.count_wal_cycle(4, 6, 0.024)
        secs = tr.wal_cycles()
        assert [sum(c[i] for c in secs.values()) for i in (0, 1, 2)] == \
            [2, 5, 11]
        sec = min(secs)  # the two cycles may straddle a second's edge
        c = ctx()
        c.outcome = SimpleNamespace(t0=sec - 1.0, t_end=sec + 3.0)
        assert reader("wal_syncs_per_cycle").read(c, tracers=[tr]) == 2.5
        assert reader("wal_cycle_ms").read(c, tracers=[tr]) == \
            pytest.approx(16.0)
    finally:
        tr.close()
