"""Median ``remaining_ticks`` of the window's ``read_ctx`` spans answered
under the leader's lease (``path: lease``): the ticks of validity the lease
had left when it answered, what ``LeaderLease.check`` returned.  A lease
renewed by every tick's heartbeats reads its whole duration less the acks'
age (8 at ``election_rtt`` 10); one that runs near expiry reads near 0.
``None`` where no read of the window was answered under a lease."""
from benchmark.layers import read_legs as rl
from benchmark.layers.lease_read_pct import PATH


def read(ctx):
    vals = [s["remaining_ticks"] for s in rl.select(ctx)["spans"]
            if s.get("path") == PATH
            and s.get("remaining_ticks") is not None]
    return ctx.percentile(vals, 50) if vals else None
