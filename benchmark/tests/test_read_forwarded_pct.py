"""``layers/read_forwarded_pct``: forwarded against all ReadIndex contexts
the window's rounds staged; ``None`` where the program records no origin or
no read fell in the window."""
import pytest

from test_span_readers import T0, T_END, ctx, reader, ring, round_span


def test_reader_takes_the_forwarded_share():
    spans = [round_span(T0 + 1, reads_local=10, reads_remote=38),
             round_span(T0 + 2, host="h2", reads_local=9, reads_remote=41),
             round_span(T0 + 3, reads_local=1, reads_remote=1),
             round_span(T0 - 1, reads_local=500, reads_remote=0),
             round_span(T_END, reads_local=500, reads_remote=0)]
    got = reader("read_forwarded_pct").read(ctx(spans))
    assert got == pytest.approx(100.0 * 80 / 100)


def test_reader_returns_none_without_the_fields_or_a_read():
    assert reader("read_forwarded_pct").read(ctx(ring())) is None
    assert reader("read_forwarded_pct").read(ctx([])) is None
    quiet = [round_span(T0 + 1, reads_local=0, reads_remote=0)]
    assert reader("read_forwarded_pct").read(ctx(quiet)) is None
