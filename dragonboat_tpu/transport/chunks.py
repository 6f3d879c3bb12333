"""Inbound snapshot chunk tracker.

Reference: ``internal/transport/chunks.go`` — tracks in-flight inbound
snapshots (max 128), validates chunk ordering, writes the image into a
``.receiving`` temp dir, and on completion converts the finished set into a
local ``InstallSnapshot`` message handed to the message router.  Stale
transfers are garbage collected on ticks.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

from ..logger import get_logger
from ..settings import Soft
from ..server.snapshotenv import SSEnv, SSMode
from ..wire import Chunk, Message, MessageBatch, MessageType, Snapshot, SnapshotFile

plog = get_logger("transport")


@dataclass
class _Track:
    first_chunk: Chunk
    env: SSEnv
    next_chunk_id: int = 0
    tick: int = 0
    file: Optional[object] = None
    file_path: str = ""
    files: list = field(default_factory=list)  # completed external SnapshotFiles
    validate_current: Optional[SnapshotFile] = None


class Chunks:
    """Reference ``chunks.go:69`` ``Chunks``."""

    def __init__(
        self,
        deployment_id: int,
        snapshot_dir_fn: Callable[[int, int], str],
        message_handler: Callable[[MessageBatch], None],
        source_address: str = "",
        on_received: Optional[Callable[[int, int, int, int], None]] = None,
    ):
        self.deployment_id = deployment_id
        self.snapshot_dir_fn = snapshot_dir_fn
        self.message_handler = message_handler
        self.source_address = source_address
        self.on_received = on_received
        self._mu = threading.Lock()
        self._tracked: Dict[str, _Track] = {}
        self._tick = 0

    @staticmethod
    def key(c: Chunk) -> str:
        return f"{c.cluster_id}:{c.node_id}:{c.from_}"

    def add_chunk(self, c: Chunk) -> bool:
        """Reference ``chunks.go:103`` ``AddChunk``; returns False to poison
        the connection."""
        if c.deployment_id != self.deployment_id:
            return False
        with self._mu:
            return self._add_locked(c)

    def _add_locked(self, c: Chunk) -> bool:
        k = self.key(c)
        t = self._tracked.get(k)
        if c.chunk_id == 0:
            if t is not None:
                self._drop(k)
            if len(self._tracked) >= Soft.max_concurrent_streaming_snapshots:
                plog.warning("too many concurrent inbound snapshots")
                return False
            t = self._start_track(c)
            if t is None:
                return False
            self._tracked[k] = t
        elif t is None:
            plog.warning("ignored out-of-band chunk %d for %s", c.chunk_id, k)
            return False
        elif c.chunk_id != t.next_chunk_id:
            plog.warning(
                "unexpected chunk %d (want %d) for %s",
                c.chunk_id,
                t.next_chunk_id,
                k,
            )
            self._drop(k)
            return False
        try:
            self._save_chunk(t, c)
        except OSError as e:
            plog.error("failed to save chunk for %s: %s", k, e)
            self._drop(k)
            return False
        t.next_chunk_id = c.chunk_id + 1
        t.tick = self._tick
        if c.is_last_chunk():
            try:
                msg = self._finalize(t, c)
            except (OSError, FileExistsError) as e:
                plog.error("failed to finalize snapshot for %s: %s", k, e)
                self._drop(k)
                return False
            del self._tracked[k]
            if self.on_received is not None:
                self.on_received(c.cluster_id, c.node_id, c.index, c.from_)
            self.message_handler(
                MessageBatch(
                    requests=[msg],
                    deployment_id=self.deployment_id,
                    source_address=self.source_address,
                )
            )
        return True

    def _start_track(self, c: Chunk) -> Optional[_Track]:
        root = self.snapshot_dir_fn(c.cluster_id, c.node_id)
        if not root:
            plog.error("no snapshot dir for %d:%d", c.cluster_id, c.node_id)
            return None
        os.makedirs(root, exist_ok=True)
        env = SSEnv(root, c.index, c.from_, SSMode.RECEIVING)
        env.create_tmp_dir()
        return _Track(first_chunk=c, env=env, tick=self._tick)

    def _open_file(self, t: _Track, name: str):
        if t.file is not None:
            t.file.close()
        t.file_path = os.path.join(t.env.get_tmp_dir(), os.path.basename(name))
        t.file = open(t.file_path, "wb")

    def _save_chunk(self, t: _Track, c: Chunk) -> None:
        if c.file_chunk_id == 0:
            if c.has_file_info:
                # finishing previous file, starting an external one
                t.files.append(
                    SnapshotFile(
                        filepath=os.path.join(
                            t.env.get_tmp_dir(),
                            os.path.basename(c.file_info.filepath),
                        ),
                        file_size=c.file_info.file_size,
                        file_id=c.file_info.file_id,
                        metadata=c.file_info.metadata,
                    )
                )
                self._open_file(t, c.file_info.filepath)
            else:
                self._open_file(t, c.filepath)
        assert t.file is not None
        t.file.write(c.data)
        if c.is_last_file_chunk():
            t.file.flush()
            os.fsync(t.file.fileno())
            t.file.close()
            t.file = None

    def _finalize(self, t: _Track, last: Chunk) -> Message:
        if t.file is not None:
            # streamed transfers don't frame per-file boundaries; close on
            # the sentinel-marked last chunk
            t.file.flush()
            os.fsync(t.file.fileno())
            t.file.close()
            t.file = None
        first = t.first_chunk
        final_dir = t.env.get_final_dir()
        main_path = os.path.join(final_dir, os.path.basename(first.filepath))
        files = [
            SnapshotFile(
                filepath=os.path.join(final_dir, os.path.basename(f.filepath)),
                file_size=f.file_size,
                file_id=f.file_id,
                metadata=f.metadata,
            )
            for f in t.files
        ]
        ss = Snapshot(
            filepath=main_path,
            file_size=first.file_size,
            index=first.index,
            term=first.term,
            membership=first.membership,
            files=files,
            cluster_id=first.cluster_id,
            on_disk_index=first.on_disk_index,
            witness=first.witness,
        )
        t.env.save_ss_metadata(ss)
        try:
            t.env.finalize_snapshot()
        except FileExistsError:
            # the same snapshot was already received and promoted (an
            # earlier transfer's install message may have been lost); the
            # image on disk is identical, so delivering the install message
            # again is the idempotent repair — raft rejects it if stale
            t.env.remove_tmp_dir()
        del last
        # m.term stays 0: chunk.term is the snapshot point's ENTRY term and
        # must not be stamped on the message — the receiver's raft would
        # drop it as an old-term message (reference toMessage
        # chunks.go:375-407 builds the message without a term)
        return Message(
            type=MessageType.INSTALL_SNAPSHOT,
            to=first.node_id,
            from_=first.from_,
            cluster_id=first.cluster_id,
            snapshot=ss,
        )

    def _drop(self, k: str) -> None:
        t = self._tracked.pop(k, None)
        if t is not None:
            if t.file is not None:
                t.file.close()
            t.env.remove_tmp_dir()

    def tick(self) -> None:
        """GC stale transfers (reference ``chunks.go`` tick-based timeout)."""
        with self._mu:
            self._tick += 1
            stale = [
                k
                for k, t in self._tracked.items()
                if self._tick - t.tick > Soft.snapshot_chunk_timeout_tick
            ]
            for k in stale:
                plog.warning("inbound snapshot %s timed out", k)
                self._drop(k)

    def close(self) -> None:
        with self._mu:
            for k in list(self._tracked):
                self._drop(k)
