"""LogReader: the raft core's read view over the sharded LogDB.

Reference: ``internal/logdb/logreader.go`` — keeps an in-memory
``[marker, marker+length)`` window describing which indexes are available in
stable storage; ``append``/``set_range`` advance it after each persisted
round, while reads go straight to the DB.  The marker entry mirrors etcd's
dummy entry carrying the snapshot boundary term.
"""
from __future__ import annotations

import threading
from dataclasses import replace
from typing import List, Tuple

from ..wire import Entry, Membership, Snapshot, State
from ..raft.log import CompactedError, SnapshotOutOfDateError, UnavailableError


class LogReader:
    """Reference ``logreader.go`` ``LogReader``."""

    def __init__(self, cluster_id: int, node_id: int, logdb):
        self.cluster_id = cluster_id
        self.node_id = node_id
        self.logdb = logdb
        self._mu = threading.Lock()
        self.marker = 0
        self.marker_term = 0
        self.length = 1  # includes the marker dummy entry
        self.state = State()
        self.snapshot_record = Snapshot()

    # ---- ILogDB protocol (raft read view) ----

    def get_range(self) -> Tuple[int, int]:
        with self._mu:
            return self._first_index(), self._last_index()

    def _first_index(self) -> int:
        return self.marker + 1

    def _last_index(self) -> int:
        return self.marker + self.length - 1

    def node_state(self) -> Tuple[State, Membership]:
        with self._mu:
            return self.state, self.snapshot_record.membership

    def set_state(self, ps: State) -> None:
        with self._mu:
            self.state = ps

    def term(self, index: int) -> int:
        with self._mu:
            return self._term_locked(index)

    def _term_locked(self, index: int) -> int:
        if index == self.marker:
            return self.marker_term
        if index < self.marker:
            raise CompactedError()
        if index > self._last_index():
            raise UnavailableError()
        ents, _ = self.logdb.iterate_entries(
            [], 0, self.cluster_id, self.node_id, index, index + 1, 1 << 62
        )
        if not ents:
            raise UnavailableError()
        return ents[0].term

    def entries(self, low: int, high: int, max_size: int) -> List[Entry]:
        # the lock is held across the DB read so a concurrent compact cannot
        # delete the head of a validated range (reference logreader.go holds
        # lr.Lock() for the whole read)
        with self._mu:
            if low > high:
                raise ValueError(f"invalid range {low} > {high}")
            if low <= self.marker:
                raise CompactedError()
            if high > self._last_index() + 1:
                raise UnavailableError()
            ents, _ = self.logdb.iterate_entries(
                [], 0, self.cluster_id, self.node_id, low, high, max_size
            )
            return ents

    def snapshot(self) -> Snapshot:
        with self._mu:
            return self.snapshot_record

    def create_snapshot(self, ss: Snapshot) -> None:
        """Record a newly taken snapshot (reference ``logreader.go``
        ``CreateSnapshot``)."""
        with self._mu:
            if ss.index <= self.snapshot_record.index:
                raise SnapshotOutOfDateError()
            self.snapshot_record = ss

    def apply_snapshot(self, ss: Snapshot) -> None:
        """Reset the window to an installed snapshot (reference
        ``ApplySnapshot``)."""
        with self._mu:
            if ss.index <= self.snapshot_record.index:
                raise SnapshotOutOfDateError()
            self.snapshot_record = ss
            self.marker = ss.index
            self.marker_term = ss.term
            self.length = 1

    def append(self, entries: List[Entry]) -> None:
        """Advance the stable window after a persisted round (reference
        ``logreader.go`` ``Append``); entries were already written via
        ``SaveRaftState``."""
        if not entries:
            return
        first, last = entries[0].index, entries[-1].index
        if first + len(entries) - 1 != last:
            raise RuntimeError("gap in appended entries")
        self.set_range(first, len(entries))

    def set_range(self, index: int, length: int) -> None:
        """Merge ``[index, index+length)`` into the stable window
        (reference ``logreader.go`` ``SetRange``)."""
        if length == 0:
            return
        with self._mu:
            first = index
            last = index + length - 1
            if last < self._first_index():
                return
            if self.marker > first:
                cut = self.marker + 1 - first
                first = self.marker + 1
                length -= cut
            offset = first - self.marker
            if self.length > offset:
                self.length = offset + length
            elif self.length == offset:
                self.length += length
            else:
                raise RuntimeError(
                    f"gap in log: marker {self.marker} len {self.length} "
                    f"first {first}"
                )

    def extend_to(self, last: int) -> None:
        """Monotonically grow the stable window to cover ``last``.

        Unlike a ``get_range``+``set_range`` pair this is atomic, and it
        can only GROW the window — the no-eject snapshot path extends the
        window from outside raftMu, so it must never shrink a range a
        concurrent ``fast_eject`` (which holds raftMu) just set."""
        with self._mu:
            cur_last = self._last_index()
            if last > cur_last:
                self.length += last - cur_last

    def compact(self, index: int) -> None:
        """Move the marker forward (reference ``logreader.go:273``
        ``Compact``; strict ``<`` — compacting AT the marker is a no-op
        success, matching the real LogReader rather than the etcd test
        double, whose table treats it as already-compacted)."""
        with self._mu:
            if index < self.marker:
                raise CompactedError()
            if index > self._last_index():
                raise UnavailableError()
            term = self._term_locked(index)
            i = index - self.marker
            self.length -= i
            self.marker = index
            self.marker_term = term

    # ---- recovery ----

    def set_compact_to(self, index: int, term: int) -> None:
        with self._mu:
            self.marker = index
            self.marker_term = term
            self.length = 1

    @staticmethod
    def load(cluster_id: int, node_id: int, logdb) -> "LogReader":
        """Rebuild the reader from storage on restart: newest snapshot sets
        the marker, ``read_raft_state`` sets state + entry window
        (reference ``node.go`` ``replayLog`` first half)."""
        lr = LogReader(cluster_id, node_id, logdb)
        snapshots = logdb.list_snapshots(cluster_id, node_id)
        ss = snapshots[-1] if snapshots else None
        if ss is not None and not ss.is_empty():
            lr.snapshot_record = ss
            lr.marker = ss.index
            lr.marker_term = ss.term
            lr.length = 1
        rs = logdb.read_raft_state(cluster_id, node_id, lr.marker)
        if rs is not None:
            if not rs.state.is_empty():
                lr.state = rs.state
                if lr.state.commit < lr.marker:
                    # a snapshot is of applied, hence committed, entries,
                    # and the commit index is the one part of the hard
                    # state a restart may rebuild: a replica that applied
                    # entries beside the persist of the update that
                    # committed them (``Node.apply_committed``), saved a
                    # snapshot of them and stopped before that persist
                    # comes up with the snapshot ahead of its record
                    lr.state = replace(lr.state, commit=lr.marker)
            if rs.entry_count > 0:
                lr.set_range(rs.first_index, rs.entry_count)
        return lr
