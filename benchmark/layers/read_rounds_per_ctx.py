"""Median ``rounds`` of the window's ``read_ctx`` spans: the rounds the
leader's host dispatched from accepting a ReadIndex context to confirming it
(1: staged, echoed and confirmed by one round; 2: the echo missed the round
that staged it).  ``None`` where the program writes no such span."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.span_median(ctx, "rounds")
