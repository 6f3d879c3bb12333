"""Median time from the leader answering a forwarded ReadIndex context
(``t1`` of its ``read_ctx`` span) to the requester's ``read_confirm`` stamp:
the READ_INDEX_RESP's transport, the follower's step worker, the update that
files ``ready_to_read``.  Forwarded reads a span was joined to; ``None``
where the program writes no such span."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.leg_median(ctx, "answer_ms", origin="forwarded", joined=True)
