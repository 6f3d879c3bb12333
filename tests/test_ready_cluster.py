"""``queue.ReadyCluster``: the engine's ready set, without a lock.

Every message, device flag, commit and tick sweep of a host's groups goes
through ``set_ready``; one worker takes the set.  What must hold without
the mutex: a group flagged is taken exactly once until it is flagged
again, and whatever a caller queued BEFORE it flagged the group is seen by
the step that follows the ``get_ready`` that took (or had taken) the flag.
"""
from __future__ import annotations

import collections
import sys
import threading

import pytest

from dragonboat_tpu.queue import ReadyCluster


def test_a_flagged_group_is_taken_once_until_flagged_again():
    rc = ReadyCluster()
    assert rc.get_ready() == set() and len(rc) == 0
    rc.set_ready(3)
    rc.set_ready(3)
    rc.set_ready(5)
    assert len(rc) == 2
    assert rc.get_ready() == {3, 5}
    assert len(rc) == 0 and rc.get_ready() == set()
    rc.set_ready(3)
    assert rc.get_ready() == {3}


@pytest.mark.parametrize("producers,groups", [(2, 1), (6, 7), (12, 64)])
def test_no_work_is_lost_between_racing_producers_and_the_worker(
    producers, groups
):
    """Producers queue a token for a group, then flag it; the worker takes
    the flags and then drains the groups it took (the step worker's
    order).  With the interpreter handed over every few bytecodes, every
    token must have been drained once the producers are done and the
    worker has taken one more set."""
    per_producer = 3000
    rc = ReadyCluster()
    inbox = [collections.deque() for _ in range(groups)]
    drained = [0] * groups
    done = threading.Event()

    def produce(seed):
        g = seed
        for _ in range(per_producer):
            g = (g * 1103515245 + 12345) % (1 << 31)
            cid = g % groups
            inbox[cid].append(1)
            rc.set_ready(cid)

    def work():
        while True:
            finishing = done.is_set()
            for cid in rc.get_ready():
                q = inbox[cid]
                for _ in range(len(q)):
                    q.popleft()
                    drained[cid] += 1
            if finishing:
                return

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        worker = threading.Thread(target=work)
        worker.start()
        threads = [
            threading.Thread(target=produce, args=(i + 1,))
            for i in range(producers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        done.set()
        worker.join()
    finally:
        sys.setswitchinterval(old)
    assert sum(drained) == producers * per_producer
    assert all(not q for q in inbox)
    assert len(rc) == 0 and rc.get_ready() == set()
