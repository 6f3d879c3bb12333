"""Median ``echo_trip_ms`` of the window's ``read_ctx`` spans: from the
leader's step accepting a ReadIndex context (and sending the hinted
heartbeats in that turn) to the step worker staging the follower's echo that
completes the quorum: two transports, a follower's step-worker turn and a
leader's.  ``None`` where the program writes no such span."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.span_median(ctx, "echo_trip_ms")
