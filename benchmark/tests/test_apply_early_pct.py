"""``layers/apply_early_pct``: the updates whose committed entries were
handed to the apply queue before their persist against those that waited for
it, over the window's whole seconds and every live tracer, the counts on an
earlier line; ``None`` where the program keeps no such count or nothing was
committed in the window."""
import json
from types import SimpleNamespace

import pytest

from dragonboat_tpu.obs.trace import Tracer

from test_span_readers import T0, T_END, ctx, reader


def tracer(by_second):
    return SimpleNamespace(apply_handoffs=lambda: by_second)


def test_reader_takes_the_windows_share_over_every_tracer(capsys):
    a = tracer({int(T0) - 1: (500, 500), int(T0): (90, 1),
                int(T0) + 7: (10, 0), int(T_END): (0, 900)})
    b = tracer({int(T0) + 20: (95, 4)})
    got = reader("apply_early_pct").read(ctx(), tracers=[a, b])
    assert got == pytest.approx(100.0 * 195 / 200)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == {"event": "apply_handoffs", "early": 195, "after_sync": 5}


def test_reader_returns_none_without_the_counter_or_a_commit():
    mod = reader("apply_early_pct")
    # the parent commit's tracer: outcomes, no hand-off series
    old = SimpleNamespace(outcomes=lambda: {"counts": {}, "events": [],
                                            "by_second": {}})
    assert mod.read(ctx(), tracers=[old]) is None
    assert mod.read(ctx(), tracers=[tracer({int(T0): (9, 1)}), old]) is None
    assert mod.read(ctx(), tracers=[]) is None
    assert mod.read(ctx(), tracers=[tracer({})]) is None
    assert mod.read(ctx(), tracers=[tracer({int(T0) - 5: (9, 1)})]) is None


def test_reader_reads_what_the_programs_tracer_keeps():
    tr = Tracer(sample_every=1)
    try:
        tr.count_apply_handoffs(3, 0)
        tr.count_apply_handoffs(0, 1)
        secs = tr.apply_handoffs()
        assert [sum(c[i] for c in secs.values()) for i in (0, 1)] == [3, 1]
        sec = min(secs)  # the two counts may straddle a second's edge
        c = ctx()
        c.outcome = SimpleNamespace(t0=sec - 1.0, t_end=sec + 3.0)
        assert reader("apply_early_pct").read(c, tracers=[tr]) == 75.0
    finally:
        tr.close()
