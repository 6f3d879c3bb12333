"""Median host time of one engine step spent on its egress: the blocking
``device_get`` (``egress_wait_ms``) plus the decode of what came back
(``decode_ms``)."""
from benchmark.layers import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.DISPATCH, "egress_wait_ms", "decode_ms")
