"""Share of the ReadIndex contexts a leader's coordinator staged or echoed
that a follower forwarded for its own clients (``reads_remote``) against
those of the leading host's own clients (``reads_local``), from the
window's ``coord_round`` spans.  A descriptor of the traffic as the program
saw it, not a lever: the traffic file's ``read_host: any`` draw fixes it at
two of three where reads go to any of three hosts and four of five at five,
and no change to the program moves it, so its ``better`` means nothing and
no gain is claimed on it.  ``None`` where the program does not record it."""
from benchmark.layers import program_spans as ps


def read(ctx):
    local = ps.total(ctx, ps.ROUND, "reads_local")
    remote = ps.total(ctx, ps.ROUND, "reads_remote")
    if not local + remote:
        return None
    return 100.0 * remote / (local + remote)
