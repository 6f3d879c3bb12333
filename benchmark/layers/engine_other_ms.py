"""Median host time of one engine step inside none of its phases: what the
whole ``eng.step`` / ``eng.step_rounds`` call took (``step_ms``) beyond
row syncs, staging, puts, launch, the egress wait and its decode.  It is
the round thread waiting to get the interpreter back between two phases,
and the program's own span bookkeeping.  With ``engine_stage_ms``,
``engine_transfer_ms``, ``engine_launch_ms`` and ``engine_egress_ms`` it
makes up what the outside ``dispatch_ms`` sees."""
from benchmark.layers import program_spans as ps

PHASES = ("row_sync_ms", "stage_ms", "transfer_ms", "launch_ms",
          "egress_wait_ms", "decode_ms")


def read(ctx):
    vals = [s["step_ms"] - sum(s[f] for f in PHASES)
            for s in ps.spans(ctx, ps.DISPATCH)
            if s.get("step_ms") is not None
            and all(s.get(f) is not None for f in PHASES)]
    return ctx.percentile(vals, 50) if vals else None
