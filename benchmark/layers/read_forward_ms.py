"""Median time a forwarded ReadIndex context took from the requester's
``raft_step`` stamp to the instant the leader's step accepted it (``t0`` of
its ``read_ctx`` span): the follower's turn that forwards the READ_INDEX,
the transport, the leader's step worker taking the message.  Forwarded reads
a span was joined to; ``None`` where the program writes no such span."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.leg_median(ctx, "forward_ms", origin="forwarded", joined=True)
