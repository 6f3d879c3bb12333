"""Median number of groups registered on a host's engine when a
coordinator round dispatched (``rows`` of the window's ``coord_round``
spans): the rows one device step carries.  ``None`` where the program does
not record it."""
from benchmark.layers import program_spans as ps


def read(ctx):
    vals = [s["rows"] for s in ps.spans(ctx, ps.ROUND)
            if s.get("rows") is not None]
    return ctx.percentile(vals, 50) if vals else None
