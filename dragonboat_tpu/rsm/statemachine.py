"""StateMachine manager: drives the user SM from committed raft entries.

Reference: ``internal/rsm/statemachine.go`` — drains the task queue into
apply batches (:599-647), applies entries with exactly-once session dedup
(:883-977), applies config changes (:979), orchestrates snapshot save /
recover including the concurrent and on-disk variants (:552-814), and tracks
the ``onDiskInitIndex`` bookkeeping for on-disk SMs (:858-881).
"""
from __future__ import annotations

import enum
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Protocol, Tuple

from ..logger import get_logger
from ..statemachine import Result, SMEntry, StopChecker
from ..wire import (
    ConfigChange,
    Entry,
    EntryType,
    Membership,
    SERIES_ID_FOR_REGISTER,
    SERIES_ID_FOR_UNREGISTER,
    Snapshot,
    StateMachineType,
    config_change_from_entry,
)
from .adapters import IManagedStateMachine
from .membership import MembershipState
from .encoded import get_entry_payload
from .session import SessionManager

plog = get_logger("rsm")


class SSReqType(enum.IntEnum):
    """Snapshot request kinds (reference ``statemachine.go:71``)."""

    PERIODIC = 0
    USER_REQUESTED = 1
    EXPORTED = 2
    STREAMING = 3


@dataclass(slots=True)
class SSRequest:
    """Reference ``statemachine.go`` ``SSRequest``."""

    type: SSReqType = SSReqType.PERIODIC
    key: int = 0
    path: str = ""
    override_compaction_overhead: bool = False
    compaction_overhead: int = 0

    @property
    def exported(self) -> bool:
        return self.type == SSReqType.EXPORTED

    @property
    def streaming(self) -> bool:
        return self.type == SSReqType.STREAMING


@dataclass(slots=True)
class SSMeta:
    """Everything captured at snapshot time (reference ``statemachine.go:92``)."""

    from_index: int = 0
    index: int = 0
    term: int = 0
    on_disk_index: int = 0
    request: SSRequest = field(default_factory=SSRequest)
    membership: Membership = field(default_factory=Membership)
    session: bytes = b""
    ctx: object = None
    type: StateMachineType = StateMachineType.REGULAR
    compression: int = 0


@dataclass(slots=True)
class Task:
    """A unit of apply/snapshot work (reference ``statemachine.go:106``)."""

    cluster_id: int = 0
    node_id: int = 0
    index: int = 0
    entries: List[Entry] = field(default_factory=list)
    save: bool = False
    stream: bool = False
    # target replica of a stream task (Task.index stays a raft index)
    stream_to: int = 0
    recover: bool = False
    initial: bool = False
    new_node: bool = False
    ss: Optional[Snapshot] = None
    ss_request: SSRequest = field(default_factory=SSRequest)
    # perf_counter at the enqueue of a save / stream task while the
    # replica instruments are attached (ReplicaObs queue_ms), else 0.0
    queued_at: float = 0.0

    def is_snapshot_task(self) -> bool:
        return self.save or self.stream or self.recover

    @property
    def periodic_sync(self) -> bool:
        # reference Task.PeriodicSync: on-disk SM fsync tick
        return False


class INodeProxy(Protocol):
    """Callbacks from the apply loop into the node runtime (reference
    ``internal/rsm/statemachine.go`` ``INode``, implemented by ``node.go``)."""

    def node_ready(self) -> None: ...

    def apply_update(
        self,
        entry: Entry,
        result: Result,
        rejected: bool,
        ignored: bool,
        notify_read: bool,
    ) -> None: ...

    def apply_config_change(
        self, cc: ConfigChange, key: int, rejected: bool
    ) -> None: ...

    def restore_remotes(self, ss: Snapshot) -> None: ...

    def should_stop(self) -> bool: ...


class ISnapshotter(Protocol):
    """Snapshot file orchestration (reference ``statemachine.go:150``
    ``ISnapshotter``, implemented by the top-level ``snapshotter.go``)."""

    def save(
        self, savable, meta: SSMeta,
        captured: Optional[Callable[[], None]] = None,
    ) -> Tuple[Snapshot, object]:
        """``captured`` is called once the image no longer depends on
        ``savable`` (a failed save may not call it)."""
        ...

    def recover(self, recoverable, ss: Snapshot) -> None: ...

    def stream(
        self, streamable, meta: SSMeta, sink, to_node_id: int,
        deployment_id: int,
    ) -> None: ...

    def get_snapshot(self, index: int) -> Snapshot: ...

    def is_no_snapshot_error(self, e: Exception) -> bool: ...


class _Held:
    """A lock taken now and released once, by whichever comes first: the
    snapshotter's ``captured`` or the end of the save."""

    __slots__ = ("mu", "scope", "t0")

    def __init__(self, mu, scope) -> None:
        mu.acquire()
        self.mu = mu
        self.scope = scope
        self.t0 = time.perf_counter() if scope is not None else 0.0

    def release(self) -> None:
        mu, self.mu = self.mu, None
        if mu is None:
            return
        mu.release()
        if self.scope is not None:
            self.scope.update_lock_held(time.perf_counter() - self.t0)


class _CaptureSavable:
    """Savable facade over a native consistent capture
    (``natr_capture_sm``): writes the pre-serialized KV image in exactly
    the framing ``NativeKVStateMachine.save_snapshot`` uses, so the
    recovery side is the shared adapter path."""

    def __init__(self, kv_image: bytes) -> None:
        self._kv = kv_image

    def save_snapshot_payload(self, meta: "SSMeta", writer) -> None:
        writer.write_session(meta.session)
        writer.write(len(self._kv).to_bytes(8, "little") + self._kv)


class StateMachine:
    """Reference ``statemachine.go:162`` ``StateMachine``."""

    def __init__(
        self,
        managed: IManagedStateMachine,
        snapshotter: Optional[ISnapshotter],
        node: INodeProxy,
        cluster_id: int,
        node_id: int,
        ordered_config_change: bool = False,
        is_witness: bool = False,
        snapshot_compression: int = 0,
    ):
        self.managed = managed
        self.snapshotter = snapshotter
        self.node = node
        self.cluster_id = cluster_id
        self.node_id = node_id
        self.is_witness = is_witness
        self.snapshot_compression = snapshot_compression
        self.sessions = SessionManager()
        # native C-ABI SM (natsm.py): dedup against the SAME store the
        # enrolled native core applies through, so enroll/eject carries no
        # session hand-off and cross-plane session hashes agree
        user = getattr(managed, "sm", None)
        if getattr(user, "natsm_sess_handle", 0):
            from ..native.natsm import NativeSessionManager

            self.sessions = NativeSessionManager(user)
        self.members = MembershipState(cluster_id, node_id, ordered_config_change)
        self._mu = threading.RLock()
        # regular (non-concurrent) SMs must not be mutated while a snapshot
        # of them is being written: the apply path and the snapshot pool
        # serialize on this lock (reference statemachine.go:761 holds the
        # SM RLock for the whole regular save).  Concurrent/on-disk SMs
        # snapshot from a prepared context and skip it.
        self._update_mu = threading.RLock()
        # serializes whole snapshot save/recover operations of this SM
        # (see save() docstring); always acquired BEFORE _update_mu
        self._save_mu = threading.RLock()
        # watermarks (reference statemachine.go index/term fields)
        self.last_applied = 0
        self.last_applied_term = 0
        self.batched_last_applied = 0
        self.snapshot_index = 0
        # on-disk SM bookkeeping (reference :858-881)
        self.on_disk_init_index = 0
        self.on_disk_index = 0
        self.stopc = StopChecker()

    # ---- identity ----

    @property
    def sm_type(self) -> StateMachineType:
        return self.managed.sm_type

    @property
    def on_disk(self) -> bool:
        return self.managed.on_disk

    @property
    def concurrent_snapshot(self) -> bool:
        return self.managed.concurrent_snapshot

    # ---- lifecycle ----

    def open(self) -> int:
        """Open an on-disk SM; returns its persisted last-applied index
        (reference ``statemachine.go`` ``OpenOnDiskStateMachine``)."""
        idx = self.managed.open(self.stopc)
        with self._mu:
            self.on_disk_init_index = idx
            self.on_disk_index = idx
        return idx

    def offloaded(self) -> None:
        self.managed.close()

    # ---- watermarks ----

    def advance_applied_native(self, index: int, term: int) -> None:
        """Acknowledge entries applied by the NATIVE plane (fast lane +
        natsm): the shared SM instance already holds their effects; only
        the watermark moves here.  Monotonic — a lagging completion batch
        arriving after an eject-time catch-up must not regress it."""
        with self._mu:
            if index > self.last_applied:
                self.last_applied = index
                self.last_applied_term = max(self.last_applied_term, term)

    def get_last_applied(self) -> int:
        with self._mu:
            return self.last_applied

    def get_batched_last_applied(self) -> int:
        with self._mu:
            return self.batched_last_applied

    def set_batched_last_applied(self, index: int) -> None:
        with self._mu:
            self.batched_last_applied = index

    def get_snapshot_index(self) -> int:
        with self._mu:
            return self.snapshot_index

    # ---- read path ----

    def lookup(self, query: object) -> object:
        if self.stopc:
            raise RuntimeError("cluster stopped")
        return self.managed.lookup(query)

    def sync(self) -> None:
        self.managed.sync()

    # ---- apply path (reference Handle :599-647) ----

    def handle(self, tasks: List[Task]) -> Optional[Task]:
        """Apply normal tasks in order; stop at and return the first
        snapshot task (save/stream/recover) for the snapshot workers."""
        for t in tasks:
            if t.is_snapshot_task():
                # entries before it must already have been applied
                return t
            self._handle_apply_task(t)
        return None

    def _handle_apply_task(self, t: Task) -> None:
        if t.cluster_id != self.cluster_id or t.node_id != self.node_id:
            raise RuntimeError("task for a different node")
        if not t.entries:
            return
        self._handle_entries(t.entries)

    def _handle_entries(self, entries: List[Entry]) -> None:
        # batch consecutive plain updates; break out entries needing
        # individual treatment (reference handleBatch :935-977)
        batch: List[Tuple[Entry, SMEntry]] = []
        with self._mu:
            expected = self.last_applied + 1
        for e in entries:
            if e.index != expected:
                raise RuntimeError(
                    f"applying out-of-order entry {e.index}, want {expected}"
                )
            expected += 1
            if e.is_config_change():
                self._flush_batch(batch)
                self._handle_config_change(e)
            elif self.is_witness or e.is_empty():
                self._flush_batch(batch)
                self._handle_noop(e)
            elif not e.is_session_managed():
                if self._on_disk_skip(e):
                    self._flush_batch(batch)
                    self._advance(e, Result(), False, True, True)
                else:
                    batch.append((e, SMEntry(index=e.index, cmd=get_entry_payload(e))))
            else:
                self._flush_batch(batch)
                self._handle_session_entry(e)
        self._flush_batch(batch)

    def _on_disk_skip(self, e: Entry) -> bool:
        """Entries already covered by an on-disk SM's own store are not
        re-applied (reference ``shouldApplyEntry``/``onDiskInitIndex``)."""
        return self.on_disk and e.index <= self.on_disk_init_index

    def _flush_batch(self, batch: List[Tuple[Entry, SMEntry]]) -> None:
        if not batch:
            return
        sm_entries = [se for _, se in batch]
        with self._update_mu:
            results = self.managed.update(sm_entries)
        if len(results) != len(sm_entries):
            raise RuntimeError("update dropped entries")
        for (e, _), se in zip(batch, results):
            self._advance(e, se.result, False, False, True)
        batch.clear()

    def _handle_noop(self, e: Entry) -> None:
        self._advance(e, Result(), False, False, True)

    def _handle_config_change(self, e: Entry) -> None:
        cc = config_change_from_entry(e)
        accepted = self.members.handle_config_change(cc, e.index)
        with self._mu:
            self.last_applied = e.index
            self.last_applied_term = max(self.last_applied_term, e.term)
        self.node.apply_config_change(cc, e.key, not accepted)

    def _handle_session_entry(self, e: Entry) -> None:
        if self._on_disk_skip(e):
            self._advance(e, Result(), False, True, True)
            return
        if e.is_new_session_request():
            r = self.sessions.register_client_id(e.client_id)
            self._advance(e, r, r.value == 0, False, True)
            return
        if e.is_end_of_session_request():
            r = self.sessions.unregister_client_id(e.client_id)
            self._advance(e, r, r.value == 0, False, True)
            return
        session = self.sessions.client_registered(e.client_id)
        if session is None:
            # session not found: reject (reference handleUpdate :1029)
            self._advance(e, Result(), True, False, True)
            return
        if session.has_responded(e.series_id):
            self._advance(e, Result(), False, True, False)
            return
        cached, ok = session.get_response(e.series_id)
        if ok:
            self._advance(e, cached, False, False, True)
            return
        with self._update_mu:
            results = self.managed.update(
                [SMEntry(index=e.index, cmd=get_entry_payload(e))]
            )
        result = results[0].result
        session.add_response(e.series_id, result)
        if e.responded_to > 0:
            session.clear_to(e.responded_to)
        self._advance(e, result, False, False, True)

    def _advance(
        self,
        e: Entry,
        result: Result,
        rejected: bool,
        ignored: bool,
        notify_read: bool,
    ) -> None:
        with self._mu:
            self.last_applied = e.index
            self.last_applied_term = max(self.last_applied_term, e.term)
            if self.on_disk and not ignored:
                self.on_disk_index = e.index
        self.node.apply_update(e, result, rejected, ignored, notify_read)

    # ---- snapshot save (reference Save :552-814) ----

    def prepare_snapshot(self, req: SSRequest) -> SSMeta:
        """Capture a consistent snapshot point.  For concurrent/on-disk SMs
        this runs on the apply thread (updates paused); the actual save can
        then proceed concurrently with new updates."""
        with self._mu:
            meta = SSMeta(
                from_index=self.snapshot_index,
                index=self.last_applied,
                term=self.last_applied_term,
                on_disk_index=self.on_disk_index,
                request=req,
                membership=self.members.get(),
                session=b"" if (self.on_disk or self.is_witness) else self.sessions.save(),
                type=self.sm_type,
                compression=self.snapshot_compression,
            )
        if self.concurrent_snapshot:
            meta.ctx = self.managed.prepare_snapshot()
        return meta

    def save_snapshot_payload(self, meta: SSMeta, writer) -> None:
        """Write sessions + SM image through ``writer`` (used by the
        snapshotter while it owns the temp file)."""
        writer.write_session(meta.session)
        if not self.is_witness:
            self.managed.save_snapshot(meta.ctx, writer, None, self.stopc)

    def save(self, req: SSRequest, scope=None) -> Tuple[Snapshot, object]:
        """Full snapshot save via the snapshotter.

        ``_save_mu`` serializes saves of this SM (a user-requested and a
        periodic save can otherwise run concurrently on two pool workers and
        clobber each other's identically-named temp dir — the reference
        serializes per group via the snapshotState single-slot handoff,
        ``snapshotstate.go:65``).  Regular SMs additionally hold
        ``_update_mu`` across BOTH the meta capture and the image's
        capture: capturing meta.index first and locking later would let
        applies land in between and the image would reflect state newer
        than its label — double-apply after recovery.  The lock goes when
        the snapshotter says the image is ``captured``: in memory for one
        that fits a block, so the group's applies run beside every disk
        call of a small save; with its last block written for one that
        spilled.  ``scope`` is the save's ``snapshot_save`` span while the
        replica instruments are attached (``ReplicaObs.save``), else None:
        it is told how long the lock was held (``update_lock_ms``)."""
        if self.snapshotter is None:
            raise RuntimeError("no snapshotter configured")
        with self._save_mu:
            if self.concurrent_snapshot or self.on_disk:
                meta = self._checked_meta(req)
                ss, env = self.snapshotter.save(self, meta)
            else:
                held = _Held(self._update_mu, scope)
                try:
                    meta = self._checked_meta(req)
                    ss, env = self.snapshotter.save(self, meta, held.release)
                finally:
                    held.release()
        with self._mu:
            if not req.exported and ss.index > self.snapshot_index:
                self.snapshot_index = ss.index
        return ss, env

    def save_from_capture(
        self,
        req: SSRequest,
        index: int,
        term: int,
        kv_image: bytes,
        session_image: bytes,
        membership=None,
    ) -> Tuple[Snapshot, object]:
        """Snapshot from a pre-captured consistent native image
        (``natr_capture_sm``): the native core serialized kv+sessions at
        exactly ``index`` under its group mutex, so — unlike :meth:`save`
        — no update lock is needed here and the fast lane keeps applying
        while the file is written.  The image framing matches
        ``NativeKVStateMachine.save_snapshot``, so recovery is the shared
        path.

        ``membership`` must be the view captured ATOMICALLY with
        ``index`` (the caller snapshots it before ``natr_capture_sm`` and
        falls back to the eject path if the config-change id moved —
        ``Node._try_capture_save``): reading live membership here would
        race a config-change apply landing between the native capture and
        this call, labeling the image with membership newer than its
        index (the reference captures both under one mutex,
        ``prepare_snapshot``).  ``None`` preserves the legacy live read
        for callers that hold applies off by construction."""
        if self.snapshotter is None:
            raise RuntimeError("no snapshotter configured")
        with self._save_mu:
            with self._mu:
                if index == 0 or index <= self.snapshot_index:
                    raise SnapshotIgnored("nothing new to snapshot")
                meta = SSMeta(
                    from_index=self.snapshot_index,
                    index=index,
                    term=term,
                    on_disk_index=0,
                    request=req,
                    membership=(
                        membership if membership is not None
                        else self.members.get()
                    ),
                    session=session_image,
                    type=self.sm_type,
                    compression=self.snapshot_compression,
                )
            ss, env = self.snapshotter.save(_CaptureSavable(kv_image), meta)
        with self._mu:
            if not req.exported and ss.index > self.snapshot_index:
                self.snapshot_index = ss.index
        return ss, env

    def stream(self, sink, to_node_id: int, deployment_id: int) -> None:
        """Stream this SM's state to a lagging follower (reference
        ``statemachine.go`` ``Stream``; on-disk SMs only).  The image is
        captured from a prepared context and written straight into the
        transport sink via the ChunkWriter — never materialized locally."""
        if self.snapshotter is None:
            raise RuntimeError("no snapshotter configured")
        # only the meta/ctx capture needs the save lock; the transfer
        # itself writes no local files and may take as long as the slowest
        # follower — holding _save_mu for it would stall periodic saves
        # and compaction (the reference streams concurrently with saves)
        with self._save_mu:
            meta = self.prepare_snapshot(SSRequest(type=SSReqType.STREAMING))
        self.snapshotter.stream(self, meta, sink, to_node_id, deployment_id)

    def _checked_meta(self, req: SSRequest) -> SSMeta:
        meta = self.prepare_snapshot(req)
        if meta.index < self.on_disk_init_index:
            raise SnapshotIgnored("nothing new to snapshot")
        if meta.index == 0 or (
            meta.from_index >= meta.index and not req.exported
        ):
            raise SnapshotIgnored("no progress since last snapshot")
        return meta

    # ---- snapshot recover (reference Recover :228-341) ----

    def recover(self, t: Task) -> Optional[Snapshot]:
        """Recover from the snapshot carried by ``t`` (install) or the newest
        local snapshot (restart).

        Lock order matches save(): ``_save_mu`` then ``_update_mu`` — an
        install arriving while a pool worker is still writing an image of
        this SM must not overwrite the state mid-serialization."""
        if self.snapshotter is None:
            raise RuntimeError("no snapshotter configured")
        ss = t.ss
        if ss is None or ss.is_empty():
            return None
        if ss.witness or ss.dummy:
            self._post_recover(ss)
            return ss
        if self.on_disk and ss.on_disk_index <= self.on_disk_init_index:
            # SM's own store already covers it; just adopt metadata
            self._post_recover(ss)
            return ss
        with self._save_mu:
            with self._update_mu:
                self.snapshotter.recover(self, ss)
        self._post_recover(ss)
        return ss

    def recover_from_payload(self, ss: Snapshot, reader) -> None:
        """Restore sessions + SM image from an open snapshot reader."""
        session_data = reader.read_session()
        if not (self.on_disk or self.is_witness):
            if hasattr(self.sessions, "recover_image"):
                # native-backed store: replace CONTENT in place — the
                # handle is shared with the enrolled native core, so
                # identity must survive recover (image format is byte-
                # compatible between the two managers)
                self.sessions.recover_image(session_data or b"\x00")
            else:
                self.sessions = (
                    SessionManager.load(session_data)
                    if session_data
                    else SessionManager()
                )
        if not ss.witness and not ss.dummy:
            self.managed.recover_from_snapshot(reader, list(ss.files), self.stopc)

    def _post_recover(self, ss: Snapshot) -> None:
        with self._mu:
            self.last_applied = max(self.last_applied, ss.index)
            self.last_applied_term = max(self.last_applied_term, ss.term)
            self.snapshot_index = max(self.snapshot_index, ss.index)
            if self.on_disk:
                self.on_disk_index = max(self.on_disk_index, ss.on_disk_index)
        self.members.set(ss.membership)
        self.node.restore_remotes(ss)

    # ---- consistency hashes (reference GetHash :578-596, monkey.go) ----

    def get_hash(self) -> int:
        data = self.sessions.save()
        h = zlib.crc32(data)
        with self._mu:
            h = zlib.crc32(
                self.last_applied.to_bytes(8, "little"), h
            )
        return zlib.crc32(self.members.hash().to_bytes(8, "little"), h)

    def get_session_hash(self) -> int:
        return self.sessions.hash()

    def get_membership_hash(self) -> int:
        return self.members.hash()

    def get_membership(self) -> Membership:
        return self.members.get()


class SnapshotIgnored(Exception):
    """Snapshot request skipped: no progress (reference ``ErrSnapshotIgnored``)."""
