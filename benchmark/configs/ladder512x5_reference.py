"""The plain reference of the ``ladder512x5`` deployment.

The semantics are those of ``reference/kv.py`` (a replicated key-value
log) at this deployment's size: 512 groups of five replicas, 2,560 replicas
held against it, a majority of three.  The limits are the guarantees
``ladder512x5.json`` promises, word for word ``upstream48x3``'s, and every
comparison is exact: the newest acknowledged write of every key is on all
five replicas with its value, a linearizable read returns nothing older
than what was acknowledged before it was submitted, replicas hold nothing
else and do not differ, an acknowledgement carries the apply it came from,
and the device's commit index lies in ``commit_range(5, ...)``.
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
    guarantee (the control).  At five replicas ``ack_before_quorum`` is an
    acknowledgement that a replica short of the three never got."""
    return ReferenceCluster(
        int(config["groups"]), int(config["replicas"]),
        config["assumed"]["rtt_millisecond"] / 1000.0, seed, broken,
        key_bytes=int(config["key_bytes"]),
        value_bytes=int(config["value_bytes"]),
    )
