"""``layers/tick_flags_per_round``: the median number of tick flags a
ticking round fanned out; ``None`` where the program does not count them."""
from test_span_readers import T0, ctx, reader, ring, round_span


def flags(t, hb, elect=0, demote=0, gate="tick+acks", **kw):
    return round_span(t, gate=gate, hb_flags=hb, elect_flags=elect,
                      demote_flags=demote, **kw)


def test_reader_takes_the_median_over_the_rounds_that_ticked():
    spans = [flags(T0 + 1, 341), flags(T0 + 2, 341, demote=34),
             flags(T0 + 3, 340, elect=2),
             flags(T0 + 4, 0, gate="acks"),      # no tick: not read
             flags(T0 - 1, 9999)]                # before the window
    assert reader("tick_flags_per_round").read(ctx(spans)) == 342


def test_reader_returns_none_without_the_counts():
    assert reader("tick_flags_per_round").read(ctx(ring())) is None
    assert reader("tick_flags_per_round").read(ctx([])) is None
    only_acks = [flags(T0 + 1, 0, gate="acks")]
    assert reader("tick_flags_per_round").read(ctx(only_acks)) is None
