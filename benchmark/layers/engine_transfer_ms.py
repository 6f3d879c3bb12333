"""Median host time of one engine step spent in the host-to-device puts
(``transfer_ms``: the ``jnp.asarray`` arguments of the quorum program)."""
from benchmark.layers import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.DISPATCH, "transfer_ms")
