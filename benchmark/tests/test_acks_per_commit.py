"""``layers/acks_per_commit``: follower acknowledgements drained over
commits, across the window's rounds; ``None`` where the program does not
record the acknowledgements (an older commit has ``commits`` alone) or
nothing committed."""
import pytest

from test_span_readers import T0, T_END, ctx, reader, ring, round_span


def test_reader_divides_the_windows_acks_by_its_commits():
    spans = [round_span(T0 + 1, acks_drained=7, commits=2),
             round_span(T0 + 2, host="h2", acks_drained=2, commits=1),
             round_span(T0 + 3, acks_drained=3, commits=0),  # late acks
             round_span(T0 - 1, acks_drained=900, commits=1),
             round_span(T_END, acks_drained=900, commits=1)]
    assert reader("acks_per_commit").read(ctx(spans)) == pytest.approx(4.0)


def test_reader_returns_none_without_the_field_or_a_commit():
    older = [round_span(T0 + 1, commits=5)]
    assert reader("acks_per_commit").read(ctx(older)) is None
    assert reader("acks_per_commit").read(ctx(ring())) is None
    assert reader("acks_per_commit").read(ctx([])) is None
    idle = [round_span(T0 + 1, acks_drained=0, commits=0)]
    assert reader("acks_per_commit").read(ctx(idle)) is None
