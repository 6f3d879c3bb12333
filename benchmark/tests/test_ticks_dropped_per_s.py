"""``layers/ticks_dropped_per_s``: the window's dropped ticks a second and
host, on the hand-made ring of ``test_span_readers``; ``None`` where the
program counts none."""
import pytest

from test_span_readers import T0, T_END, ctx, reader, ring, round_span


def test_reader_sums_the_windows_dropped_ticks_per_host():
    spans = [round_span(T0 + 1.0, host="h1", ticks_dropped=0),
             round_span(T0 + 2.0, host="h1", ticks_dropped=12),
             round_span(T0 + 3.0, host="h2", ticks_dropped=36),
             round_span(T0 - 1.0, host="h1", ticks_dropped=500),
             round_span(T_END, host="h2", ticks_dropped=500)]
    assert reader("ticks_dropped_per_s").read(ctx(spans)) == pytest.approx(
        48 / (T_END - T0) / 2)


def test_reader_reads_zero_in_a_sound_window_and_none_without_the_count():
    sound = [round_span(T0 + i, ticks_dropped=0) for i in range(3)]
    assert reader("ticks_dropped_per_s").read(ctx(sound)) == 0.0
    assert reader("ticks_dropped_per_s").read(ctx(ring())) is None
    assert reader("ticks_dropped_per_s").read(ctx([])) is None
