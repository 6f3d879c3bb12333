"""Median time the oldest staged op (or tick) of a dispatched coordinator
round had waited for the round thread to come back: ``wait_ms`` of the
window's ``coord_round`` spans, every host (``program_spans``)."""
from benchmark.layers import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.ROUND, "wait_ms")
