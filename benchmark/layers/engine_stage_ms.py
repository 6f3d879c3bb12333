"""Median host time of one engine step spent staging: gathering events,
padding, the K-round block build (``stage_ms``) plus the row syncs
(``row_sync_ms``: dirty-row upload, row pulls), over the window's
``dispatch`` / ``fused`` spans."""
from benchmark.layers import program_spans as ps


def read(ctx):
    return ps.median_ms(ctx, ps.DISPATCH, "stage_ms", "row_sync_ms")
