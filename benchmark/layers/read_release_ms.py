"""Median ``release_ms`` of the window's ``read_ctx`` spans: from a
context's confirmation at the round's fan-out to the step worker that
answers its requesters (a READ_INDEX_RESP sent, ``ready_to_read`` filed):
the wake and the wait for that worker's turn.  ``None`` where the program
writes no such span."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.span_median(ctx, "release_ms")
