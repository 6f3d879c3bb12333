"""Dispatched coordinator rounds a second and host: the window's
``coord_round`` spans over its seconds and the hosts that wrote them."""
from benchmark.layers import program_spans as ps


def read(ctx):
    rounds = ps.spans(ctx, ps.ROUND)
    if not rounds:
        return None
    hosts = len({s.get("host") for s in rounds})
    return len(rounds) / ctx.seconds / hosts
