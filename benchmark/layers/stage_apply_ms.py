"""Median time a sampled request spent reaching its ``apply`` stamp from
the stamp before (``obs/trace.py``, host clock, every host's tracer)."""


def read(ctx):
    got = ctx.stages.get("apply")
    return got[0] if got else None
