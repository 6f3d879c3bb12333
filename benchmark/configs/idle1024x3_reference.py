"""The plain reference of the ``idle1024x3`` deployment.

The semantics are those of ``reference/kv.py`` (a replicated key-value
log) at this deployment's size: 1,024 groups of three replicas, 3,072
replicas held against it.  A sleeping group is invisible to a client: it
serves the request that wakes it like any other, so the plain reference is
the same replicated log and knows no sleep.  The limits are the guarantees
``idle1024x3.json`` promises, ``ladder1024x3``'s four word for word (and
the fifth, that sleeping and waking change no replica's state and lose no
acknowledged write, is what the same six comparisons hold it to), and every
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
