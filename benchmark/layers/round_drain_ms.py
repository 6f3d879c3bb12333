"""Median time a coordinator round spent applying its staged ops to the
engine before the step: ``_drain_locked`` with its row syncs, the read
stages and echoes, the scalar read-echo fallbacks (``drain_ms`` of the
window's ``coord_round`` spans)."""
from benchmark.layers import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.ROUND, "drain_ms")
