"""Median host time of one engine step spent in the explicit drops of the
arrays it retires (``retire_ms``: the previous state blocks and the ingress
block, dropped right after the launch).  ``engine_other_ms`` is ``step_ms``
less the six older phases and so still contains it.  ``None`` where the
program has no such phase."""
from benchmark.layers import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.DISPATCH, "retire_ms")
