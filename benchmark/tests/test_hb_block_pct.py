"""``layers/hb_block_pct``: block rows against per-group messages over the
window's rounds, the causes on an earlier line; ``None`` where the program
has no heartbeat plane or no heartbeat fell in the window."""
import json

import pytest

from test_span_readers import T0, T_END, ctx, reader, ring, round_span


def test_reader_takes_the_share_and_prints_the_causes(capsys):
    spans = [round_span(T0 + 1, hb_block_rows=1300, hb_single=64,
                        hb_single_lagging=60, hb_single_busy=4),
             round_span(T0 + 2, hb_block_rows=1364, hb_single=0),
             round_span(T0 + 3, hb_block_rows=1300, hb_single=36,
                        hb_single_lagging=30, hb_single_read_ctx=6),
             round_span(T0 - 1, hb_block_rows=0, hb_single=5000),
             round_span(T_END, hb_block_rows=0, hb_single=5000)]
    got = reader("hb_block_pct").read(ctx(spans))
    assert got == pytest.approx(100.0 * 3964 / 4064)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line == {"event": "heartbeats", "block": 3964,
                    "single": {"lagging": 90, "busy": 4, "read_ctx": 6}}


def test_reader_returns_none_without_a_plane_or_a_heartbeat():
    assert reader("hb_block_pct").read(ctx(ring())) is None
    assert reader("hb_block_pct").read(ctx([])) is None
    quiet = [round_span(T0 + 1, hb_block_rows=0, hb_single=0)]
    assert reader("hb_block_pct").read(ctx(quiet)) is None
