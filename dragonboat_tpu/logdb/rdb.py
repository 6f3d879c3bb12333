"""Per-shard storage engine: key-schema CRUD over one KV store.

Reference: ``internal/logdb/rdb.go`` — State / MaxIndex / Bootstrap /
Snapshot-list / Entries records, one atomic WriteBatch per ``SaveRaftState``
round (``rdb.go:187-210``), plus the per-node write-suppression cache
(``internal/logdb/rdbcache.go``).
"""
from __future__ import annotations

import struct
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..wire import Bootstrap, Entry, Snapshot, State, Update
from ..wire.codec import (
    decode_bootstrap,
    decode_snapshot,
    decode_state,
    encode_bootstrap,
    encode_snapshot,
    encode_state,
)
from . import keys
from .entries import BatchedEntries, PlainEntries
from .kv import IKVStore, KVWriteBatch


@dataclass(frozen=True)
class NodeInfo:
    """Reference ``raftio/logdb.go`` ``NodeInfo``."""

    cluster_id: int
    node_id: int


@dataclass
class RaftState:
    """Reference ``raftio/logdb.go`` ``RaftState``."""

    state: State = field(default_factory=State)
    first_index: int = 0
    entry_count: int = 0


class RDBCache:
    """Suppresses redundant State / maxIndex writes (reference
    ``rdbcache.go:28-116``)."""

    def __init__(self) -> None:
        self._ps: Dict[Tuple[int, int], State] = {}
        self._max_index: Dict[Tuple[int, int], int] = {}
        self._mu = threading.Lock()

    def set_state(self, cluster_id: int, node_id: int, st: State) -> bool:
        """Returns True when the state changed and must be written."""
        key = (cluster_id, node_id)
        with self._mu:
            cur = self._ps.get(key)
            if (
                cur is not None
                and cur.term == st.term
                and cur.vote == st.vote
                and cur.commit == st.commit
            ):
                return False
            self._ps[key] = State(term=st.term, vote=st.vote, commit=st.commit)
            return True

    def set_max_index(self, cluster_id: int, node_id: int, max_index: int) -> bool:
        key = (cluster_id, node_id)
        with self._mu:
            if self._max_index.get(key) == max_index:
                return False
            self._max_index[key] = max_index
            return True

    def get_max_index(self, cluster_id: int, node_id: int) -> Optional[int]:
        with self._mu:
            return self._max_index.get((cluster_id, node_id))

    def invalidate(self, pairs) -> None:
        """Drop the cached State/maxIndex for ``(cluster_id, node_id)``
        pairs whose write batch FAILED to commit (ISSUE 12 fix): the
        cache was advanced at build time, so without this the retry's
        rebuild suppresses the very records the failed batch lost and
        the state silently never lands.  A dropped entry only costs the
        next round one unsuppressed write."""
        with self._mu:
            for key in pairs:
                self._ps.pop(key, None)
                self._max_index.pop(key, None)


_U64 = struct.Struct(">Q")


class RDB:
    """One storage shard (reference ``rdb.go:50``)."""

    def __init__(self, kv: IKVStore, batched: bool = False):
        self.kv = kv
        self.cache = RDBCache()
        self.entries = BatchedEntries(kv) if batched else PlainEntries(kv)

    # ---- bootstrap ----

    def save_bootstrap(self, cluster_id: int, node_id: int, bs: Bootstrap) -> None:
        self.kv.put(keys.bootstrap_key(cluster_id, node_id), encode_bootstrap(bs))

    def get_bootstrap(self, cluster_id: int, node_id: int) -> Optional[Bootstrap]:
        v = self.kv.get(keys.bootstrap_key(cluster_id, node_id))
        return decode_bootstrap(v) if v is not None else None

    def list_node_info(self) -> List[NodeInfo]:
        first = keys.make_key(keys.TAG_BOOTSTRAP, 0, 0, 0)
        last = keys.make_key(keys.TAG_BOOTSTRAP, 2**64 - 1, 2**64 - 1, 0)
        out = []
        for k, _ in self.kv.iterate(first, last, True):
            _, cid, nid, _ = keys.parse_key(k)
            out.append(NodeInfo(cluster_id=cid, node_id=nid))
        return out

    # ---- raft state round (the hot write path) ----

    def save_raft_state(self, updates: List[Update], wb: KVWriteBatch) -> bool:
        """One atomic, fsynced write batch for a worker round
        (reference ``rdb.go:187-210``).  True when a batch was committed."""
        self.build_raft_state(updates, wb)
        # rounds where every record was suppressed (heartbeat traffic with
        # unchanged State) must not pay a WAL append + fsync for an empty
        # batch — the rdbcache exists precisely to elide these writes
        if not wb.ops:
            return False
        try:
            self.kv.commit_write_batch(wb)
        except BaseException:
            # the build advanced the rdbcache for records this batch
            # was carrying; a failed commit must drop those entries
            # or the retry's rebuild suppresses them forever
            self.cache.invalidate(
                {(u.cluster_id, u.node_id) for u in updates}
            )
            raise
        return True

    def build_raft_state(self, updates: List[Update], wb: KVWriteBatch) -> None:
        """Fill ``wb`` with the round's records WITHOUT committing — the
        host-plane group-commit journal path commits the batch itself
        (journal fsync first, then ``commit_write_batch_nosync``)."""
        for ud in updates:
            self._record_state(ud, wb)
            if ud.snapshot is not None and not ud.snapshot.is_empty():
                self._record_snapshot(wb, ud.cluster_id, ud.node_id, ud.snapshot)
            if ud.entries_to_save:
                mi = self.entries.record_entries(
                    wb, ud.cluster_id, ud.node_id, ud.entries_to_save
                )
                if mi > 0:
                    self._record_max_index(wb, ud.cluster_id, ud.node_id, mi)
            elif ud.snapshot is not None and not ud.snapshot.is_empty():
                self._record_max_index(
                    wb, ud.cluster_id, ud.node_id, ud.snapshot.index
                )

    def _record_state(self, ud: Update, wb: KVWriteBatch) -> None:
        if ud.state.is_empty():
            return
        if not self.cache.set_state(ud.cluster_id, ud.node_id, ud.state):
            return
        wb.put(keys.state_key(ud.cluster_id, ud.node_id), encode_state(ud.state))

    def _record_max_index(
        self, wb: KVWriteBatch, cluster_id: int, node_id: int, max_index: int
    ) -> None:
        if not self.cache.set_max_index(cluster_id, node_id, max_index):
            return
        wb.put(keys.max_index_key(cluster_id, node_id), _U64.pack(max_index))

    def read_max_index(self, cluster_id: int, node_id: int) -> int:
        v = self.kv.get(keys.max_index_key(cluster_id, node_id))
        return _U64.unpack(v)[0] if v is not None else 0

    def read_state(self, cluster_id: int, node_id: int) -> Optional[State]:
        v = self.kv.get(keys.state_key(cluster_id, node_id))
        return decode_state(v) if v is not None else None

    def read_raft_state(
        self, cluster_id: int, node_id: int, last_index: int
    ) -> Optional[RaftState]:
        """Reference ``rdb.go`` ``readRaftState``: state + entry range."""
        st = self.read_state(cluster_id, node_id)
        if st is None:
            return None
        max_index = self.read_max_index(cluster_id, node_id)
        first, length = self._entry_range(cluster_id, node_id, last_index, max_index)
        return RaftState(state=st, first_index=first, entry_count=length)

    def _entry_range(
        self, cluster_id: int, node_id: int, snapshot_index: int, max_index: int
    ) -> Tuple[int, int]:
        if max_index == 0 or max_index < snapshot_index:
            return 0, 0
        # find the first stored entry at or after the snapshot boundary
        ents, _ = self.entries.iterate_entries(
            [], 0, cluster_id, node_id, snapshot_index, snapshot_index + 1, 1 << 62
        )
        start = snapshot_index
        if not ents:
            start = snapshot_index + 1
            e = self.entries.get_entry(cluster_id, node_id, start)
            if e is None:
                return 0, 0
        return start, max_index - start + 1

    def iterate_entries(
        self,
        ents: List[Entry],
        size: int,
        cluster_id: int,
        node_id: int,
        low: int,
        high: int,
        max_size: int,
    ) -> Tuple[List[Entry], int]:
        max_index = self.read_max_index(cluster_id, node_id)
        if high > max_index + 1:
            high = max_index + 1
        if low >= high:
            return ents, size
        return self.entries.iterate_entries(
            ents, size, cluster_id, node_id, low, high, max_size
        )

    # ---- snapshots ----

    def _record_snapshot(
        self, wb: KVWriteBatch, cluster_id: int, node_id: int, ss: Snapshot
    ) -> None:
        wb.put(
            keys.snapshot_key(cluster_id, node_id, ss.index), encode_snapshot(ss)
        )

    def save_snapshot(self, cluster_id: int, node_id: int, ss: Snapshot) -> None:
        wb = self.kv.get_write_batch()
        self._record_snapshot(wb, cluster_id, node_id, ss)
        self.kv.commit_write_batch(wb)

    def commit_snapshot(
        self, cluster_id: int, node_id: int, ss: Snapshot, stale: List[int],
    ) -> None:
        """What a committed save leaves among the snapshot records, as one
        atomic, fsynced batch (one crc'd record, as ``remove_node_data``
        mixes ``put`` and ``delete``): the record of ``ss`` and the deletes
        of the records at the ``stale`` indexes: ``save_snapshot`` +
        ``delete_snapshot`` x n under one commit.  The log's range delete
        is NOT in it (``remove_entries_to``, after): an entry may leave
        only once the LogReader holds the snapshot that covers it, and the
        reader learns of a snapshot only once this record is durable."""
        wb = self.kv.get_write_batch()
        self._record_snapshot(wb, cluster_id, node_id, ss)
        for index in stale:
            wb.delete(keys.snapshot_key(cluster_id, node_id, index))
        self.kv.commit_write_batch(wb)

    def delete_snapshot(self, cluster_id: int, node_id: int, index: int) -> None:
        self.kv.delete(keys.snapshot_key(cluster_id, node_id, index))

    def list_snapshots(
        self, cluster_id: int, node_id: int, index: int = keys.MAX_INDEX
    ) -> List[Snapshot]:
        """Ascending snapshot records up to ``index`` inclusive."""
        fk = keys.snapshot_key(cluster_id, node_id, 0)
        lk = keys.snapshot_key(cluster_id, node_id, index)
        return [decode_snapshot(v) for _, v in self.kv.iterate(fk, lk, True)]

    # ---- removal / compaction ----

    def remove_entries_to(self, cluster_id: int, node_id: int, index: int) -> None:
        wb = self.kv.get_write_batch()
        self.entries.remove_entries_to(wb, cluster_id, node_id, index)
        self.kv.commit_write_batch(wb)

    def compact_entries_to(self, cluster_id: int, node_id: int, index: int) -> None:
        self.entries.compact_range(cluster_id, node_id, index)

    def remove_node_data(self, cluster_id: int, node_id: int) -> None:
        """Reference ``rdb.go`` ``removeNodeData``: wipe everything.

        Keys are tag-major, so each tag's ``(cluster, node)`` range must be
        deleted separately — one cross-tag range would span other nodes'
        records.
        """
        wb = self.kv.get_write_batch()
        wb.delete(keys.bootstrap_key(cluster_id, node_id))
        wb.delete(keys.state_key(cluster_id, node_id))
        wb.delete(keys.max_index_key(cluster_id, node_id))
        for tag in (keys.TAG_SNAPSHOT, keys.TAG_ENTRY, keys.TAG_ENTRY_BATCH):
            wb.delete_range(
                keys.make_key(tag, cluster_id, node_id, 0),
                keys.make_key(tag, cluster_id, node_id, keys.MAX_INDEX),
            )
            wb.delete(keys.make_key(tag, cluster_id, node_id, keys.MAX_INDEX))
        self.kv.commit_write_batch(wb)
        self.cache.set_max_index(cluster_id, node_id, 0)

    def import_snapshot(self, ss: Snapshot, node_id: int) -> None:
        """Reference ``rdb.go:212-237``: reset a node's records from an
        imported snapshot (quorum-loss repair)."""
        if ss.type == 0 and not ss.membership.addresses:
            raise ValueError("invalid snapshot for import")
        selected = [
            rec
            for rec in self.list_snapshots(ss.cluster_id, node_id)
            if rec.index >= ss.index
        ]
        bs = Bootstrap(join=True, type=ss.type)
        wb = self.kv.get_write_batch()
        wb.put(keys.bootstrap_key(ss.cluster_id, node_id), encode_bootstrap(bs))
        for rec in selected:
            wb.delete(keys.snapshot_key(ss.cluster_id, node_id, rec.index))
        wb.put(
            keys.state_key(ss.cluster_id, node_id),
            encode_state(State(term=ss.term, commit=ss.index)),
        )
        self._record_snapshot(wb, ss.cluster_id, node_id, ss)
        wb.put(keys.max_index_key(ss.cluster_id, node_id), _U64.pack(ss.index))
        self.kv.commit_write_batch(wb)
        self.cache.set_max_index(ss.cluster_id, node_id, ss.index)

    def close(self) -> None:
        self.kv.close()
