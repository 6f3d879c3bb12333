"""The plain reference of the ``upstream48x3lease`` deployment.

The semantics are those of ``reference/kv.py`` (a replicated key-value
log) at ``upstream48x3``'s size, 48 groups of three replicas.  A leader
lease is invisible to a client while its clock bound holds: a read answered
under it returns what a ReadIndex read would have returned, one confirmation
round sooner.  So the plain reference is the same replicated log, and what
is new is how the program reaches the same answers.  The limits are the
guarantees ``upstream48x3lease.json`` promises, and every comparison is
exact.  ``wrong_reads`` 0 is what holds the program to ``guarantees.read``
as far as a run can: every read is checked for linearizability, and half of
them ask for the key whose write was acknowledged last, which a leader that
answers from a stale commit index (a lease it should no longer hold) gets
wrong.

What a run cannot show: no link is cut and no clock jumps inside a window,
so no lease is ever held past its bound here.  A leader cut off from its
quorum that stops answering within the lease's duration, and a tick thread
held back for longer than the lease (the wall guard), are held by
``tests/test_lease_under_load.py`` and ``tests/test_lease.py``.
"""
from benchmark.reference.kv import (  # noqa: F401
    ReferenceCluster, commit_range, expected_state, wrong_reads,
)

LIMITS = {
    "lost_acked_writes": 0,
    "foreign_keys": 0,
    "divergent_groups": 0,
    "wrong_reads": 0,
    "bad_apply_seq": 0,
    "device_commit_out_of_range": 0,
}


def cluster(config: dict, seed: int, broken: str = None) -> ReferenceCluster:
    """The reference in the program's place; ``broken`` gives up one
    guarantee (the control)."""
    return ReferenceCluster(
        int(config["groups"]), int(config["replicas"]),
        config["assumed"]["rtt_millisecond"] / 1000.0, seed, broken,
        key_bytes=int(config["key_bytes"]),
        value_bytes=int(config["value_bytes"]),
    )
