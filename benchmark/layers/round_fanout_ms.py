"""Median time a coordinator round spent after the engine returned: trace
stamps, commit and read-confirm offloads under each node's ``raft_mu``, the
tick flags (``fanout_ms`` of the window's ``coord_round`` spans)."""
from benchmark.layers import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.ROUND, "fanout_ms")
