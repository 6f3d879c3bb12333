"""The plain reference of the ``upstream48x3snap`` deployment.

The semantics are those of ``reference/kv.py`` (a replicated key-value
log) at ``upstream48x3``'s size, 48 groups of three replicas.  A snapshot,
the compaction behind it and a check-quorum window are invisible to a
client, so the reference is the same log: what is new is that the program
now reaches the same answers through saves, compactions and windows.  The
limits are the guarantees ``upstream48x3snap.json`` promises, and every
comparison is exact: the newest acknowledged write of every key is on every
replica with its value, a linearizable read returns nothing older than what
was acknowledged before it was submitted, replicas hold nothing else and do
not differ, an acknowledgement carries the apply it came from, and the
device's commit index lies where the acknowledged and the proposed writes
put it.
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
