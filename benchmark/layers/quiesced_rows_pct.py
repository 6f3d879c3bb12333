"""Share of a host's replicas that were asleep, median over the window's
coordinator rounds (``rows_quiesced`` against ``rows`` of the ``coord_round``
spans).  A descriptor of the cell's state, not a lever: the traffic's
sparsity fixes it (about ``exp(-threshold / mean gap)``), and its ``better``
means nothing.  ``None`` where the program has no such field."""
from benchmark.layers import quiesce_plane as qp


def read(ctx):
    vals = [100.0 * s["rows_quiesced"] / s["rows"] for s in qp.rounds(ctx)]
    return ctx.percentile(vals, 50) if vals else None
