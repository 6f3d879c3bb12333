"""Snapshotter: snapshot directory/record lifecycle for one replica.

Reference: ``snapshotter.go`` — owns the per-node snapshot root dir,
produces snapshots through :class:`SSEnv` temp dirs, commits records to the
LogDB, keeps the 3 newest snapshots (``snapshotter.go:34``), shrinks old
images and garbage-collects orphaned dirs left behind by crashes.
Implements the RSM layer's ``ISnapshotter`` contract.
"""
from __future__ import annotations

import os
from typing import Callable, List, Optional, Tuple

from . import vfs
from .logger import get_logger
from .rsm.snapshotio import SnapshotReader, SnapshotWriter, shrink_snapshot
from .rsm.statemachine import SSMeta
from .server.snapshotenv import (
    SSEnv,
    SSMode,
    _rmtree,
    is_final_snapshot_dir,
    is_temp_snapshot_dir,
    snapshot_index_from_dir,
)
from .wire import Snapshot

plog = get_logger("snapshotter")

SNAPSHOTS_TO_KEEP = 3


class NoSnapshotError(Exception):
    pass


class Snapshotter:
    """Reference ``snapshotter.go:57``."""

    def __init__(
        self,
        root_dir: str,
        cluster_id: int,
        node_id: int,
        logdb,
        fs: vfs.IFS = vfs.DEFAULT,
    ):
        self.root_dir = root_dir
        self.cluster_id = cluster_id
        self.node_id = node_id
        self.logdb = logdb
        self.fs = fs
        fs.makedirs(root_dir, exist_ok=True)

    # ---- ISnapshotter ----

    def save(
        self, savable, meta: SSMeta,
        captured: Optional[Callable[[], None]] = None,
    ) -> Tuple[Snapshot, SSEnv]:
        """Write a snapshot image into a temp dir (reference
        ``snapshotter.go:103-150`` ``Save``).  Exported snapshots land in
        the user-provided directory instead of the node's snapshot root
        (reference custom-SSEnv path for ``Exported`` requests) and are
        never recorded in the LogDB.

        ``captured`` is called once the image no longer depends on
        ``savable``, and whoever holds updates out for the image's sake
        lets them in again there.  An image that fits a block
        (``SnapshotWriter``) is whole in memory when the payload ends, so
        that is before anything touches the disk: the temp dir, the one
        image write, the flag file and every fsync run beside the group's
        applies.  One that spilled is captured with its last block
        written.  A failed save may not have called it."""
        root = self.root_dir
        if meta.request is not None and meta.request.exported:
            if not meta.request.path:
                raise ValueError("exported snapshot request without a path")
            root = meta.request.path
        env = SSEnv(root, meta.index, self.node_id, SSMode.SNAPSHOT, self.fs)
        # whatever fails from here on, a write of the image or of the flag
        # file (ErrorFS injection, ENOSPC), must not leak the .generating
        # temp dir (tests/test_rsm.py fault table caught exactly this)
        w = SnapshotWriter(
            env.get_tmp_filepath(), self.fs, compression=meta.compression,
            before_open=env.create_tmp_dir,
        )
        try:
            savable.save_snapshot_payload(meta, w)
            env.image_buffered = w.seal()
            if captured is not None and env.image_buffered:
                captured()
            w.finalize()
            env.fsyncs += 1
            if captured is not None and not env.image_buffered:
                captured()
            ss = Snapshot(
                filepath=env.get_filepath(),
                file_size=w.file_size,
                index=meta.index,
                term=meta.term,
                membership=meta.membership,
                cluster_id=self.cluster_id,
                type=meta.type,
                on_disk_index=meta.on_disk_index,
                witness=False,
            )
            env.save_ss_metadata(ss)
        except Exception:
            w.abort()
            env.remove_tmp_dir()
            raise
        return ss, env

    def commit(self, ss: Snapshot, env: SSEnv) -> List[Snapshot]:
        """Promote the temp dir, then make the snapshot known to the LogDB
        (reference ``snapshotter.go:181`` ``Commit``): ONE atomic,
        fsynced batch, issued once ``finalize_snapshot`` made the directory
        final and its name durable, that holds the record of ``ss`` and the
        deletes of the records beyond the ``SNAPSHOTS_TO_KEEP`` newest
        (reference ``snapshotter.go`` ``Compact``).  Returns the snapshots
        whose records went: their directories are removed after, by
        :meth:`remove_dirs` (a crash between the two leaves an unrecorded
        directory, which ``process_orphans`` removes; the other order would
        leave a record without its file)."""
        env.finalize_snapshot()
        have = [
            s
            for s in self.logdb.list_snapshots(self.cluster_id, self.node_id)
            if s.index != ss.index
        ]
        have.append(ss)
        have.sort(key=lambda s: s.index)
        stale = have[:-SNAPSHOTS_TO_KEEP]
        self.logdb.commit_snapshot(
            self.cluster_id, self.node_id, ss, [s.index for s in stale]
        )
        return stale

    def remove_dirs(self, stale: List[Snapshot]) -> None:
        """The directories of the snapshots :meth:`commit` dropped the
        records of."""
        for ss in stale:
            SSEnv(
                self.root_dir, ss.index, self.node_id, SSMode.SNAPSHOT,
                self.fs,
            ).remove_final_dir()

    def recover(self, recoverable, ss: Snapshot) -> None:
        """Reference ``snapshotter.go`` recover path: open + validate the
        image and hand the payload to the RSM."""
        r = SnapshotReader(ss.filepath, self.fs)
        try:
            recoverable.recover_from_payload(ss, r)
        finally:
            r.close()

    def stream(
        self, streamable, meta: SSMeta, sink, to_node_id: int,
        deployment_id: int,
    ) -> None:
        from .rsm.chunkwriter import ChunkWriter

        cw = ChunkWriter(
            sink, meta, self.cluster_id, to_node_id, self.node_id,
            deployment_id,
        )
        streamable.save_snapshot_payload(meta, cw)
        cw.finalize()

    def get_snapshot(self, index: int = 0) -> Snapshot:
        snapshots = self.logdb.list_snapshots(self.cluster_id, self.node_id)
        if index == 0:
            if not snapshots:
                raise NoSnapshotError()
            return snapshots[-1]
        for ss in snapshots:
            if ss.index == index:
                return ss
        raise NoSnapshotError()

    def get_most_recent_snapshot(self) -> Optional[Snapshot]:
        snapshots = self.logdb.list_snapshots(self.cluster_id, self.node_id)
        return snapshots[-1] if snapshots else None

    def is_no_snapshot_error(self, e: Exception) -> bool:
        return isinstance(e, NoSnapshotError)

    # ---- retention / GC ----

    def shrink(self, shrink_to: int) -> None:
        """Shrink images older than ``shrink_to`` (reference
        ``snapshotter.go`` ``Shrink``) — used by on-disk SMs whose old full
        images are dead weight."""
        for ss in self.logdb.list_snapshots(self.cluster_id, self.node_id):
            if ss.index > shrink_to or ss.witness or ss.dummy:
                continue
            if not self.fs.exists(ss.filepath):
                continue
            tmp = ss.filepath + ".shrinking"
            shrink_snapshot(ss.filepath, tmp, self.fs)
            self.fs.replace(tmp, ss.filepath)

    def process_orphans(self) -> None:
        """Remove temp dirs and unrecorded final dirs left by crashes
        (reference ``snapshotter.go:393-408`` ``ProcessOrphans``)."""
        recorded = {
            ss.index
            for ss in self.logdb.list_snapshots(self.cluster_id, self.node_id)
        }
        try:
            names = self.fs.listdir(self.root_dir)
        except OSError:
            return
        for name in names:
            full = os.path.join(self.root_dir, name)
            if is_temp_snapshot_dir(name):
                plog.info("removing orphaned temp dir %s", full)
                _rmtree(full, self.fs)
            elif is_final_snapshot_dir(name):
                if snapshot_index_from_dir(name) not in recorded:
                    plog.info("removing unrecorded snapshot dir %s", full)
                    _rmtree(full, self.fs)
