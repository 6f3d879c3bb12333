"""Median ``leader_ms`` of the window's ``read_ctx`` spans: a ReadIndex
context from the leader's step accepting it to the step worker answering its
requesters, every origin and path: the sum of ``read_echo_trip_ms``,
``read_echo_wait_ms``, ``read_confirm_ms`` and ``read_release_ms``.  ``None``
where the program writes no such span."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.span_median(ctx, "leader_ms")
