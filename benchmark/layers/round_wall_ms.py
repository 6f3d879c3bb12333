"""Median length of a dispatched coordinator round, drain to fan-out:
``t1 - t0`` of the window's finished ``coord_round`` spans, every host."""
from benchmark.layers import program_spans as ps


def read(ctx):
    vals = [(s["t1"] - s["t0"]) * 1e3
            for s in ps.spans(ctx, ps.ROUND)]
    return ctx.percentile(vals, 50) if vals else None
