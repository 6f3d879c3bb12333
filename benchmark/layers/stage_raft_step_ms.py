"""Median time a sampled request spent reaching its ``raft_step`` stamp from
the stamp before (``obs/trace.py``, host clock, every host's tracer)."""


def read(ctx):
    got = ctx.stages.get("raft_step")
    return got[0] if got else None
