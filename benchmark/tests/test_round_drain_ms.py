"""``layers/round_drain_ms``: the median ``drain_ms`` of the window's
finished ``coord_round`` spans, driven with the hand-made ring of
``test_span_readers``; ``None`` where the program recorded nothing."""
import pytest

from test_span_readers import T0, T_END, ctx, reader, ring, round_span


def test_reader_takes_the_windows_drains():
    spans = ring()
    inside = [s for s in spans if s["kind"] == "coord_round"
              and T0 <= s["t0"] < T_END and "wall_ms" in s]
    for s, drain in zip(inside, (46.0, 0.2, 57.0)):
        s["drain_ms"] = drain
    assert reader("round_drain_ms").read(ctx(spans)) == pytest.approx(46.0)


def test_reader_returns_none_without_a_drain_to_read():
    assert reader("round_drain_ms").read(ctx([])) is None
    no_field = round_span(T0 + 1.0)
    del no_field["drain_ms"]
    assert reader("round_drain_ms").read(ctx([no_field])) is None
    outside = [round_span(T0 - 1.0), round_span(T_END)]
    assert reader("round_drain_ms").read(ctx(outside)) is None
