"""Median ``confirm_ms`` of the window's ``read_ctx`` spans: from the drain
of the echo that completes a context's quorum to its confirmation: the rest
of the drain, the device step and its egress, up to the round's fan-out
(device path); the wait for the step worker's scalar tally (scalar path).
``None`` where the program writes no such span."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.span_median(ctx, "confirm_ms")
