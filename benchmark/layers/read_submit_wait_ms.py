"""Median time a sampled linearizable read waited, at the replica it was
submitted to, between its ``ingress`` stamp (``Node.read`` queued it) and its
``raft_step`` stamp (a step worker formed the ReadIndex context that covers
it): the wait for a step worker's turn.  Reads only, every origin
(``obs/trace.py`` stamps of the requests the program follows a context for;
``None`` where it follows none)."""
from benchmark.layers import read_legs as rl


def read(ctx):
    return rl.leg_median(ctx, "submit_wait_ms")
