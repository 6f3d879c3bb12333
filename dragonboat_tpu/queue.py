"""Input queues between the API layer and the step engine.

Reference: ``queue.go`` — double-buffered ``entryQueue`` for proposals,
``readIndexQueue`` for reads, and the ``readyCluster`` map pair used by the
engine's wakeup paths.
"""
from __future__ import annotations

import collections
import threading
from typing import Dict, List, Optional, Set

from .requests import RequestState
from .wire import Entry


class EntryQueue:
    """Reference ``queue.go:24`` — bounded, double-buffered."""

    def __init__(self, size: int):
        self.size = size
        self._mu = threading.Lock()
        self._left: List[Entry] = []
        self._right: List[Entry] = []
        self._use_left = True
        self._stopped = False
        self._paused = False

    def _active(self) -> List[Entry]:
        return self._left if self._use_left else self._right

    def add(self, e: Entry) -> bool:
        with self._mu:
            if self._stopped or self._paused:
                return False
            q = self._active()
            if len(q) >= self.size:
                return False
            q.append(e)
            return True

    def add_batch(self, entries: List[Entry]) -> int:
        """Append a burst under ONE lock acquisition (hostplane ingress
        batcher).  Returns how many were accepted — a full queue truncates
        the tail exactly like per-entry ``add`` calls would."""
        with self._mu:
            if self._stopped or self._paused:
                return 0
            q = self._active()
            room = self.size - len(q)
            if room <= 0:
                return 0
            take = entries[:room]
            q.extend(take)
            return len(take)

    def get(self, paused: bool = False) -> List[Entry]:
        # lock-free empty fast path (hot: every step round polls this);
        # only valid when the pause flag isn't changing
        if paused == self._paused and not self._left and not self._right:
            return []
        with self._mu:
            self._paused = paused
            q = self._active()
            self._use_left = not self._use_left
            out = list(q)
            q.clear()
            return out

    def close(self) -> None:
        with self._mu:
            self._stopped = True


class ReadIndexQueue:
    """Reference ``queue.go:110``."""

    def __init__(self, size: int):
        self.size = size
        self._mu = threading.Lock()
        self._reqs: List[RequestState] = []
        self._stopped = False

    def add(self, rs: RequestState) -> bool:
        with self._mu:
            if self._stopped or len(self._reqs) >= self.size:
                return False
            self._reqs.append(rs)
            return True

    def get(self) -> List[RequestState]:
        with self._mu:
            out, self._reqs = self._reqs, []
            return out

    def peep(self) -> bool:
        # GIL-atomic read; hot-path poll (node._handle_read_index)
        return bool(self._reqs)

    def close(self) -> None:
        with self._mu:
            self._stopped = True


class ReadyCluster:
    """Groups with pending work, taken all at once by their one worker
    (reference ``queue.go:178`` ``readyCluster``).

    No lock: a ``deque`` append, a set ``add`` and a membership test are
    each atomic under the interpreter lock.  Every message, device flag,
    commit and tick sweep of a host's groups comes through ``set_ready``;
    behind a mutex, a holder that lost the interpreter mid-hold left every
    step worker, committer, transport thread and the host's tick worker
    queueing for it, each paying a switch interval to get in, and a host
    of a thousand groups stopped ticking for seconds."""

    def __init__(self) -> None:
        self._q: "collections.deque[int]" = collections.deque()
        # groups flagged and not taken yet: keeps a busy group to one
        # entry in the queue
        self._pending: Set[int] = set()

    def __len__(self) -> int:
        return len(self._pending)

    def set_ready(self, cluster_id: int) -> None:
        # already flagged: the worker takes it, and steps it after the
        # caller queued whatever it is flagging
        pending = self._pending
        if cluster_id in pending:
            return
        pending.add(cluster_id)
        self._q.append(cluster_id)

    def get_ready(self) -> Set[int]:
        q = self._q
        out = {q.popleft() for _ in range(len(q))}
        # un-flag only now: a caller that found its group flagged in
        # between is served by the step that follows this call
        self._pending.difference_update(out)
        return out


class LeaderInfoQueue:
    """Reference ``queue.go:213`` — leader change notifications."""

    def __init__(self, size: int = 2048):
        self.size = size
        self._mu = threading.Lock()
        self._q: List = []

    def add(self, info) -> bool:
        with self._mu:
            if len(self._q) >= self.size:
                return False
            self._q.append(info)
            return True

    def get(self) -> List:
        with self._mu:
            out, self._q = self._q, []
            return out
