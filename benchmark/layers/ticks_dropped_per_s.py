"""Host ticks a second and host that a coordinator round could not replay
(``ticks_dropped`` of the window's ``coord_round`` spans): the host's
device clocks ran that much slow.  0 in a sound run; the first thing to
look at in one that changed leaders.  ``None`` where the program counts no
dropped ticks."""
from benchmark.layers import program_spans as ps


def read(ctx):
    rounds = [s for s in ps.spans(ctx, ps.ROUND)
              if s.get("ticks_dropped") is not None]
    if not rounds:
        return None
    hosts = len({s.get("host") for s in rounds})
    return sum(s["ticks_dropped"] for s in rounds) / ctx.seconds / hosts
