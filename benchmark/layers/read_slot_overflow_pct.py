"""Share of the ReadIndex contexts staged to the coordinator that the
engine refused a device slot (every pending-read slot of the group held an
unconfirmed batch), from the window's ``coord_round`` spans."""
from benchmark.layers import program_spans as ps


def read(ctx):
    given = ps.total(ctx, ps.ROUND, "reads_staged")
    refused = ps.total(ctx, ps.ROUND, "reads_refused")
    if not given + refused:
        return None
    return 100.0 * refused / (given + refused)
