"""``layers/engine_arrays_per_step``: the median over the window's steps of
arrays made by put plus arrays retired; ``None`` where the program does
not count them."""
from test_span_readers import T0, T_END, ctx, dispatch_span, reader, ring


def test_reader_takes_the_median_of_made_plus_retired():
    spans = [
        dispatch_span(T0 + 1, arrays_made=1, arrays_retired=6),
        dispatch_span(T0 + 2, kind="fused", arrays_made=1, arrays_retired=6),
        dispatch_span(T0 + 3, arrays_made=3, arrays_retired=16),  # chunked
        dispatch_span(T0 - 1, arrays_made=8, arrays_retired=45),  # before
        dispatch_span(T_END, arrays_made=8, arrays_retired=45),   # after
    ]
    assert reader("engine_arrays_per_step").read(ctx(spans)) == 7


def test_reader_skips_a_step_whose_egress_is_still_in_flight():
    inflight = dispatch_span(T0 + 2, arrays_made=1, arrays_retired=5)
    del inflight["egress_ms"]
    spans = [dispatch_span(T0 + 1, arrays_made=1, arrays_retired=6), inflight]
    assert reader("engine_arrays_per_step").read(ctx(spans)) == 7


def test_reader_returns_none_without_the_fields():
    assert reader("engine_arrays_per_step").read(ctx(ring())) is None
    assert reader("engine_arrays_per_step").read(ctx([])) is None
