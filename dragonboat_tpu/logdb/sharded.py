"""Sharded LogDB: N independent shards + async compaction worker.

Reference: ``internal/logdb/sharded_rdb.go`` — 16 shards
(``settings/hard.go:37``), ``clusterID % shards`` placement via the
partitioner (``server/partition.go:59``), background compaction queue
(``sharded_rdb.go:292``), and the plain/batched format self-check.
"""
from __future__ import annotations

import os
import queue
import re
import threading
from typing import Callable, List, Optional, Tuple

from ..logger import get_logger
from ..settings import Hard
from ..wire import Bootstrap, Entry, Snapshot, Update
from .entries import has_entry_records
from .kv import IKVStore, InMemKV, WalKV
from .rdb import RDB, NodeInfo, RaftState

plog = get_logger("logdb")

_STOP = object()
_SHARD_DIR = re.compile(r"shard-(\d\d+)")


class ShardedDB:
    """Reference ``sharded_rdb.go:44`` ``ShardedRDB``."""

    def __init__(
        self, shards: List[RDB], batched: bool = False, dirname: str = ""
    ):
        self._shards = shards
        self._batched = batched
        self._dir = dirname
        # host-plane group-commit journal (logdb/journal.py): armed by
        # enable_host_journal(); save_raft_state_journaled then rides ONE
        # journal fsync per flush cycle for every shard's batches.
        # _journal_mu serializes a whole journaled cycle (append + the
        # nosync shard applies) against checkpoints: a checkpoint
        # truncating between the two would discard the only durable copy
        # of the in-flight cycle while the shard stores still lag.
        self.journal = None
        self._journal_mu = threading.Lock()
        # invoked after each async compaction round (cluster_id, node_id);
        # nodehost publishes LOGDB_COMPACTED through it
        self.on_compaction = None
        self._compaction_q: "queue.Queue" = queue.Queue()
        self._compaction_worker = threading.Thread(
            target=self._compaction_main, name="logdb-compaction", daemon=True
        )
        self._compaction_worker.start()

    # ---- identity / format ----

    def name(self) -> str:
        fmt = "batched" if self._batched else "plain"
        return f"sharded-{self._shards[0].kv.name()}-{fmt}"

    def binary_format(self) -> int:
        return 1

    def selfcheck_failed(self) -> bool:
        """True when on-disk entry format disagrees with the configured one
        (reference ``logdb.go:44-56``)."""
        other = not self._batched
        return any(has_entry_records(s.kv, other) for s in self._shards)

    def _shard(self, cluster_id: int) -> RDB:
        return self._shards[cluster_id % len(self._shards)]

    # ---- bootstrap ----

    def save_bootstrap_info(
        self, cluster_id: int, node_id: int, bs: Bootstrap
    ) -> None:
        self._shard(cluster_id).save_bootstrap(cluster_id, node_id, bs)

    def get_bootstrap_info(
        self, cluster_id: int, node_id: int
    ) -> Optional[Bootstrap]:
        return self._shard(cluster_id).get_bootstrap(cluster_id, node_id)

    def list_node_info(self) -> List[NodeInfo]:
        out: List[NodeInfo] = []
        for s in self._shards:
            out.extend(s.list_node_info())
        return out

    # ---- raft state ----

    def save_raft_state(self, updates: List[Update]) -> int:
        """Group updates by shard; one atomic write batch per shard.
        Returns how many shard batches were committed (each one durable
        write; a bucket whose records were all suppressed commits none).

        The reference passes a per-worker IContext whose write batch covers
        exactly one shard because workers and shards are co-partitioned
        (``server/partition.go:59``).  A NodeHost opens its LogDB with as
        many shards as it has step workers (``open_logdb``), so a committer
        cycle is one bucket here too; updates are still bucketed explicitly
        so any geometry and any caller threading model stay correct.
        """
        buckets = {}
        for ud in updates:
            buckets.setdefault(ud.cluster_id % len(self._shards), []).append(ud)
        committed = 0
        for idx, uds in buckets.items():
            shard = self._shards[idx]
            wb = shard.kv.get_write_batch()
            committed += shard.save_raft_state(uds, wb)
        return committed

    # ---- host-plane group-commit journal (ISSUE 8) ----

    def enable_host_journal(self, fs=None):
        """Arm the cross-shard group-commit journal (durable dirs only).
        Returns the journal, or None when this DB has no directory (the
        in-memory backend has nothing to amortize).  ``fs`` routes the
        journal IO through a vfs (ErrorFS fault injection)."""
        if self.journal is not None:
            return self.journal
        if not self._dir:
            return None
        import os as _os

        from .journal import JOURNAL_NAME, HostJournal

        self.journal = HostJournal(
            _os.path.join(self._dir, JOURNAL_NAME), fs=fs
        )
        return self.journal

    def save_raft_state_journaled(self, updates: List[Update]) -> bool:
        """The group-commit flush cycle: build every shard's write batch,
        append them all to the journal under ONE fsync, then apply to the
        shard stores without their own fsync.  Requires
        ``enable_host_journal``; per-group ordering is the caller's
        (single flush leader at a time) and per-shard batches stay atomic.

        Adaptive: a cycle carrying exactly ONE shard batch while the
        journal is EMPTY has nothing to amortize — it commits through the
        shard's classic fsynced path (bit-identical cost to the
        uncompartmented committer) and returns False.  The journal-empty
        guard is a correctness rule, not a heuristic: a direct write
        landing AFTER journaled-but-unsynced writes would be regressed by
        a crash replay re-applying the older journal history over it.
        Returns True when the cycle rode the journal."""
        buckets = {}
        for ud in updates:
            buckets.setdefault(ud.cluster_id % len(self._shards), []).append(ud)
        prepared = []
        for idx, uds in buckets.items():
            shard = self._shards[idx]
            wb = shard.kv.get_write_batch()
            shard.build_raft_state(uds, wb)
            if wb.ops:
                prepared.append((idx, wb))
        if not prepared:
            return False
        with self._journal_mu:
            try:
                if len(prepared) == 1 and not self.journal.nonempty():
                    idx, wb = prepared[0]
                    self._shards[idx].kv.commit_write_batch(wb)
                    return False
                # the ONE fsync (in-process or via the hostproc WAL
                # worker sink); raises on failure
                self.journal.append(prepared)
                for idx, wb in prepared:
                    self._shards[idx].kv.commit_write_batch_nosync(wb)
                return True
            except BaseException:
                # build_raft_state advanced each shard's rdbcache for
                # the records these batches carry; a failed append /
                # commit must drop those entries or the committer's
                # RETRY rebuild suppresses them and the state silently
                # never lands (ISSUE 12 fix, caught by the WAL-worker
                # fault-injection suite)
                for idx, uds in buckets.items():
                    self._shards[idx].cache.invalidate(
                        {(u.cluster_id, u.node_id) for u in uds}
                    )
                raise

    def journal_checkpoint(self) -> None:
        """Fsync every shard store, then truncate the journal — under the
        journal mutex so an in-flight journaled cycle is never stranded
        half-applied (see ``_journal_mu``)."""
        with self._journal_mu:
            j = self.journal
            if j is not None and j.nonempty():
                j.checkpoint(self.sync_all)

    def sync_all(self) -> None:
        """Fsync every shard store (journal checkpoint half)."""
        for s in self._shards:
            sync = getattr(s.kv, "sync", None)
            if sync is not None:
                sync()

    def _journal_barrier(self) -> None:
        """Checkpoint before a DIRECT destructive mutation (snapshot
        delete, node-data removal, snapshot import): journal history
        replayed over such a mutation after a crash would resurrect the
        deleted records.  Rare operations, so the nshards-fsync cost is
        irrelevant; with the journal empty nothing happens.  A failed
        checkpoint PROPAGATES — proceeding with the mutation would
        re-create the exact replay-resurrection hazard the barrier
        exists to prevent."""
        if self.journal is not None and self.journal.nonempty():
            self.journal_checkpoint()

    def fsync_count(self) -> int:
        """Committed-write-batch fsyncs across all shards plus the host
        journal's (backends that don't count — in-memory — contribute 0).
        The host-plane bench reads this for its fsyncs/s and amortization
        columns."""
        n = sum(getattr(s.kv, "fsyncs", 0) for s in self._shards)
        if self.journal is not None:
            n += self.journal.fsyncs
        return n

    def read_raft_state(
        self, cluster_id: int, node_id: int, last_index: int
    ) -> Optional[RaftState]:
        return self._shard(cluster_id).read_raft_state(
            cluster_id, node_id, last_index
        )

    def refresh_cached_state(
        self, cluster_id: int, node_id: int, term: int, vote: int,
        commit: int, max_index: int,
    ) -> None:
        """Re-seed the write-suppression caches after an external writer
        (the native fast lane) updated the State/MaxIndex records directly —
        else a later save round would either suppress a needed write or
        re-issue a redundant one against stale assumptions."""
        from ..wire import State

        shard = self._shard(cluster_id)
        shard.cache.set_state(
            cluster_id, node_id, State(term=term, vote=vote, commit=commit)
        )
        shard.cache.set_max_index(cluster_id, node_id, max_index)

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
        return self._shard(cluster_id).iterate_entries(
            ents, size, cluster_id, node_id, low, high, max_size
        )

    # ---- snapshots ----

    def save_snapshots(self, updates: List[Update]) -> None:
        for ud in updates:
            if ud.snapshot is not None and not ud.snapshot.is_empty():
                self._shard(ud.cluster_id).save_snapshot(
                    ud.cluster_id, ud.node_id, ud.snapshot
                )

    def save_snapshot(self, cluster_id: int, node_id: int, ss: Snapshot) -> None:
        self._shard(cluster_id).save_snapshot(cluster_id, node_id, ss)

    def commit_snapshot(
        self, cluster_id: int, node_id: int, ss: Snapshot, stale: List[int],
    ) -> None:
        """``save_snapshot`` and ``delete_snapshot`` of the ``stale``
        indexes as ONE fsynced batch of the group's shard
        (``RDB.commit_snapshot``)."""
        if stale:
            self._journal_barrier()
        self._shard(cluster_id).commit_snapshot(cluster_id, node_id, ss, stale)

    def delete_snapshot(self, cluster_id: int, node_id: int, index: int) -> None:
        self._journal_barrier()
        self._shard(cluster_id).delete_snapshot(cluster_id, node_id, index)

    def list_snapshots(
        self, cluster_id: int, node_id: int, index: int = 2**64 - 1
    ) -> List[Snapshot]:
        return self._shard(cluster_id).list_snapshots(cluster_id, node_id, index)

    # ---- removal / compaction ----

    def remove_entries_to(self, cluster_id: int, node_id: int, index: int) -> None:
        """Synchronously range-delete, then queue async compaction
        (reference ``sharded_rdb.go:270-298``)."""
        self._journal_barrier()
        self._shard(cluster_id).remove_entries_to(cluster_id, node_id, index)
        self._compaction_q.put((cluster_id, node_id, index))

    def compact_entries_to(self, cluster_id: int, node_id: int, index: int):
        done = threading.Event()
        self._compaction_q.put((cluster_id, node_id, index, done))
        return done

    def remove_node_data(self, cluster_id: int, node_id: int) -> None:
        self._journal_barrier()
        self._shard(cluster_id).remove_node_data(cluster_id, node_id)

    def import_snapshot(self, ss: Snapshot, node_id: int) -> None:
        self._journal_barrier()
        self._shard(ss.cluster_id).import_snapshot(ss, node_id)

    def _compaction_main(self) -> None:
        while True:
            item = self._compaction_q.get()
            if item is _STOP:
                return
            cluster_id, node_id, index = item[0], item[1], item[2]
            try:
                self._shard(cluster_id).compact_entries_to(
                    cluster_id, node_id, index
                )
                if self.on_compaction is not None:
                    self.on_compaction(cluster_id, node_id)
            except Exception:
                # the worker must survive a failed compaction: letting the
                # exception kill this thread would silently disable ALL
                # future compaction (the queue drains nowhere) — found by
                # the RequestCompaction full-range overflow test
                plog.exception(
                    "compaction %d:%d to %d failed", cluster_id, node_id, index
                )
            finally:
                if len(item) > 3:
                    item[3].set()

    def close(self) -> None:
        self._compaction_q.put(_STOP)
        self._compaction_worker.join(timeout=5)
        if self.journal is not None:
            # shard stores may hold journal-covered, un-fsynced tails:
            # make them durable, then retire the journal cleanly
            try:
                self.journal_checkpoint()
            except OSError:
                plog.exception("host journal final checkpoint failed")
            self.journal.close()
        for s in self._shards:
            s.close()


def shards_on_disk(dirname: str) -> int:
    """How many ``shard-NN`` directories ``dirname`` holds (0: none, or no
    such directory).  The count is recorded nowhere else: a group lives in
    shard ``cluster_id % count``, so a directory has to be opened with the
    count it was written with."""
    if not dirname or not os.path.isdir(dirname):
        return 0
    found = sorted(
        int(m.group(1))
        for m in map(_SHARD_DIR.fullmatch, os.listdir(dirname))
        if m is not None
    )
    if found != list(range(len(found))):
        raise RuntimeError(
            f"LogDB directory {dirname!r} holds shards {found}: not a run "
            f"from 0, so its shard count cannot be told"
        )
    return len(found)


def open_logdb(
    dirname: str = "",
    shards: int = 0,
    batched: bool = False,
    kv_factory: Optional[Callable[[str], IKVStore]] = None,
    fsync: bool = True,
    default_shards: int = 0,
) -> ShardedDB:
    """Open (or create) a sharded LogDB.

    ``dirname == ""`` selects the in-memory backend (test/bench builds,
    analogous to the reference's memfs Pebble).  Otherwise each shard gets
    ``dirname/shard-NN`` backed by the C++ native segmented-WAL engine
    (``dragonboat_tpu/native``, the analog of the reference's default
    Pebble / optional RocksDB cgo backend) — falling back to the Python
    :class:`WalKV` only where the native library cannot be built.

    The shard count: a directory that exists is opened with the shards it
    has (``shards_on_disk``), and an explicit ``shards`` that disagrees
    raises.  A new directory, and the in-memory backend, get ``shards``,
    else ``default_shards`` (a NodeHost passes its step-worker count: a
    worker's groups then live in one shard and a committer cycle is one
    durable write batch), else ``Hard.logdb_pool_size``.
    """
    present = shards_on_disk(dirname)
    if shards and present and shards != present:
        raise RuntimeError(
            f"LogDB directory {dirname!r} was written with {present} "
            f"shards and {shards} were asked for: a group's records live "
            f"in shard cluster_id % {present}; open it with {present} (or "
            f"with no explicit count)"
        )
    n = shards or present or default_shards or Hard.logdb_pool_size
    durable_factory: Optional[Callable[[str], IKVStore]] = None
    if kv_factory is None and dirname:
        from .. import native

        if native.available():
            durable_factory = lambda d: native.NativeKV(d, fsync=fsync)
        else:
            durable_factory = lambda d: WalKV(d, fsync=fsync)
    rdbs: List[RDB] = []
    for i in range(n):
        if kv_factory is not None:
            kv = kv_factory(os.path.join(dirname, f"shard-{i:02d}") if dirname else "")
        elif dirname:
            kv = durable_factory(os.path.join(dirname, f"shard-{i:02d}"))
        else:
            kv = InMemKV()
        rdbs.append(RDB(kv, batched=batched))
    if dirname:
        # leftover host-plane group-commit journal (crash, or a restart
        # with compartments off): its writes were acked but the shard
        # stores may lag — replay before the DB is handed out
        from .journal import JOURNAL_NAME, replay

        jpath = os.path.join(dirname, JOURNAL_NAME)
        if os.path.exists(jpath):
            replay(jpath, rdbs)
    db = ShardedDB(rdbs, batched=batched, dirname=dirname)
    if db.selfcheck_failed():
        db.close()
        raise RuntimeError(
            "on-disk entry format does not match the configured format"
        )
    return db
