"""Follower acknowledgements the coordinators drained per commit advance,
over the window's ``coord_round`` spans (``acks_drained`` / ``commits``):
the fan-in a commit costs the round.  ``commits`` counts rows whose commit
index a round advanced (row x round), not entries: an acknowledgement that
carries several entries still advances its row once, so the ratio stays at
the followers a row has, replicas - 1 (two at three replicas, four at
five), and falls under that only where acknowledgements are coalesced
before the drain.  The two coincide at ``mixed91``'s write rate, one entry
an advance.  ``None`` where the program does not record it or nothing
committed."""
from benchmark.layers import program_spans as ps


def read(ctx):
    rounds = [s for s in ps.spans(ctx, ps.ROUND)
              if s.get("acks_drained") is not None]
    commits = sum(s.get("commits") or 0 for s in rounds)
    if not commits:
        return None
    return sum(s["acks_drained"] for s in rounds) / commits
