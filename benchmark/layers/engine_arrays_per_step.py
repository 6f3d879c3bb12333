"""Median number of device arrays one engine step made by a host-to-device
put (``arrays_made``) and retired (``arrays_retired``: the state blocks,
ingress block and egress block it dropped), summed.  Each birth by put and
each death costs the round thread a hand-off of the interpreter, whatever
the array's size.  ``None`` where the program does not count them."""
from benchmark.layers import program_spans as ps


def read(ctx):
    vals = [s["arrays_made"] + s["arrays_retired"]
            for s in ps.spans(ctx, ps.DISPATCH)
            if s.get("arrays_made") is not None
            and s.get("arrays_retired") is not None]
    return ctx.percentile(vals, 50) if vals else None
