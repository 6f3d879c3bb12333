"""``layers/engine_retire_ms``: the median ``retire_ms`` of the window's
steps; ``None`` where the program has no such phase."""
import pytest
from test_span_readers import T0, T_END, ctx, dispatch_span, reader, ring


def test_reader_takes_the_median_retire_ms():
    spans = [
        dispatch_span(T0 + 1, retire_ms=2.0),
        dispatch_span(T0 + 2, kind="fused", retire_ms=6.0),
        dispatch_span(T0 + 3, retire_ms=4.0),
        dispatch_span(T0 - 1, retire_ms=99.0),
        dispatch_span(T_END, retire_ms=99.0),
    ]
    assert reader("engine_retire_ms").read(ctx(spans)) == pytest.approx(4.0)


def test_reader_returns_none_without_the_field():
    assert reader("engine_retire_ms").read(ctx(ring())) is None
    assert reader("engine_retire_ms").read(ctx([])) is None
