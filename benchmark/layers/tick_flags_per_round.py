"""Median number of device tick flags a ticking coordinator round fanned
out: heartbeats due, elections due, check-quorum windows closed
(``hb_flags`` + ``elect_flags`` + ``demote_flags`` of the window's
``coord_round`` spans that ran a tick).  About the leaders a host holds;
``None`` where the program does not count them."""
from benchmark.layers import program_spans as ps

FIELDS = ("hb_flags", "elect_flags", "demote_flags")


def read(ctx):
    vals = [sum(s[f] for f in FIELDS) for s in ps.spans(ctx, ps.ROUND)
            if all(s.get(f) is not None for f in FIELDS)
            and "tick" in (s.get("gate") or "")]
    return ctx.percentile(vals, 50) if vals else None
