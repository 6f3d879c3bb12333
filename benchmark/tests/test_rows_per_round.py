"""``layers/rows_per_round``: the median ``rows`` of the window's rounds;
``None`` where the program does not record it."""
from test_span_readers import T0, T_END, ctx, reader, ring, round_span


def test_reader_takes_the_median_rows():
    spans = [round_span(T0 + 1, rows=1024), round_span(T0 + 2, rows=1024),
             round_span(T0 + 3, host="h2", rows=1000),
             round_span(T0 - 1, rows=3), round_span(T_END, rows=3)]
    assert reader("rows_per_round").read(ctx(spans)) == 1024


def test_reader_returns_none_without_the_field():
    assert reader("rows_per_round").read(ctx(ring())) is None
    assert reader("rows_per_round").read(ctx([])) is None
