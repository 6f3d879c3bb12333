"""The comparison that decides ``correct``.

Once the window has closed and every operation has reached its outcome,
the replicas of the program are held against the plain reference
(``reference/kv.py``) fed the operations the run acknowledged.  It covers
the whole served path of the window's own operations: client API, raft
step, WAL, coordinator round, the device kernels' commit indices, apply,
and the state machine on all three replicas.

Each number compared has a limit of its own (``LIMITS`` of the
configuration's reference file; all are exact comparisons, limit 0):

* ``lost_acked_writes``: (replica, write) pairs where an acknowledged
  write is missing or holds another value.
* ``foreign_keys``: keys a replica holds that no operation ever proposed.
* ``divergent_groups``: groups whose replicas differ once converged.
* ``wrong_reads``: linearizable reads that returned nothing, a value no
  write of their key ever carried, a value first proposed only after the
  lookup, or a value older (in the state machine's own apply order) than
  the newest write of their key acknowledged before the read was submitted
  (``reference/kv.py wrong_reads``).
* ``bad_apply_seq``: acknowledged writes whose answer, the state machine's
  own apply count, is missing, repeated within its group, or beyond what the
  acknowledging replica has applied — an acknowledgement not produced by
  the local apply.
* ``device_commit_out_of_range``: groups whose commit index, as the device
  engine of the leading host holds it, lies below what the acknowledged
  writes need or above what was ever proposed (``reference/kv.py
  commit_range``).
"""
from __future__ import annotations


def compare(cluster, outcomes: list, reference, limits: dict,
            converge_timeout_s: float = 120.0) -> dict:
    """{name: {"value": n, "limit": n}} over the prefill and the window."""
    acked, unacked, reads, attempts = [], [], [], {}
    for o in outcomes:
        acked += o.acked_writes
        unacked += o.unacked_writes
        reads += o.reads
        for cid, n in o.attempts_by_group.items():
            attempts[cid] = attempts.get(cid, 0) + n
    cluster.wait_converged(converge_timeout_s)
    want = reference.expected_state(acked)
    maybe = {}  # a write that was never acknowledged may or may not apply
    for cid, key, val, _first in unacked:
        maybe.setdefault((cid, key), set()).add(val)

    lost = foreign = divergent = 0
    for cid in cluster.cids:
        replicas = cluster.replica_contents(cid)
        exp = want.get(cid, {})
        for kv in replicas:
            for key, val in exp.items():
                got = kv.get(key)
                if got != val and got not in maybe.get((cid, key), ()):
                    lost += 1
            if len(kv) != len(exp):
                foreign += sum(
                    1 for k in kv if k not in exp and (cid, k) not in maybe
                )
        if any(kv != replicas[0] for kv in replicas[1:]):
            divergent += 1

    commit = cluster.device_commit()
    seqs, n_acked = {}, {}
    bad_seq = 0
    for cid, _key, _val, seq, *_times in acked:
        n_acked[cid] = n_acked.get(cid, 0) + 1
        seen = seqs.setdefault(cid, set())
        if seq < 1 or seq in seen:
            bad_seq += 1
        seen.add(seq)
    out_of_range = 0
    for cid in cluster.cids:
        lo, hi = reference.commit_range(
            len(cluster.replica_contents(cid)), n_acked.get(cid, 0),
            attempts.get(cid, 0), cluster.terms_seen(cid),
        )
        if not lo <= commit[cid] <= hi:
            out_of_range += 1
        # an apply count can not exceed the entries the device committed
        bad_seq += sum(1 for s in seqs.get(cid, ()) if s > commit[cid])

    values = {
        "lost_acked_writes": lost,
        "foreign_keys": foreign,
        "divergent_groups": divergent,
        "bad_apply_seq": bad_seq,
        "device_commit_out_of_range": out_of_range,
    }
    if reads:
        values["wrong_reads"] = reference.wrong_reads(acked, unacked, reads)
    return {
        name: {"value": value, "limit": limits[name]}
        for name, value in values.items()
    }


def is_correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
