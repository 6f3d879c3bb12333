"""Leader changes inside the window (``raft_event_listener``; one per group
and term, whichever host reported it first)."""


def read(ctx):
    return float(len(ctx.leader_changes))
