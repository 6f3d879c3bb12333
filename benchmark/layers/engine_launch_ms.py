"""Median host time of one engine step inside the jitted call until it
returns (``launch_ms``)."""
from benchmark.layers import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.DISPATCH, "launch_ms")
