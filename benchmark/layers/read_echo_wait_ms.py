"""Median ``echo_wait_ms`` of the window's ``read_ctx`` spans: the echo that
completes a context's quorum waiting, staged, for the round thread to drain
it.  ``None`` where the program writes no such span."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.span_median(ctx, "echo_wait_ms")
