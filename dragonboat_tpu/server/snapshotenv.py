"""Snapshot directory lifecycle.

Reference: ``internal/server/snapshotenv.go:116`` — every snapshot is built
in a mode-suffixed temp dir (``.generating`` for local saves,
``.receiving`` for streamed ones), fsync'd, then atomically renamed to the
final ``snapshot-{index:016X}`` dir containing a flag file with the snapshot
metadata.  Orphan/zombie dirs left by crashes are recognized by these
suffixes and garbage collected by the snapshotter.
"""
from __future__ import annotations

import enum
import os
import re
from typing import Optional

from .. import vfs
from ..wire import Snapshot
from ..wire.codec import decode_snapshot, encode_snapshot

GENERATING_SUFFIX = "generating"
RECEIVING_SUFFIX = "receiving"
SNAPSHOT_FLAG_FILE = "snapshot.message"
SNAPSHOT_DIR_RE = re.compile(r"^snapshot-([0-9A-F]{16})$")
TEMP_DIR_RE = re.compile(
    r"^snapshot-[0-9A-F]{16}(-[0-9A-F]+)?\.(generating|receiving)$"
)


class SSMode(enum.Enum):
    SNAPSHOT = GENERATING_SUFFIX  # created by the local SM save path
    RECEIVING = RECEIVING_SUFFIX  # streamed in from a remote replica


def snapshot_dir_name(index: int) -> str:
    return f"snapshot-{index:016X}"


class SSEnv:
    """Reference ``snapshotenv.go`` ``SSEnv``.

    ``fsyncs`` counts the fsyncs of files and directories issued for the
    snapshot in this env (the snapshotter adds the image's) and
    ``image_buffered`` says the image went out as one write: what one
    save's ``snapshot_save`` span reports."""

    def __init__(
        self,
        root_dir: str,
        index: int,
        from_node_id: int,
        mode: SSMode,
        fs: vfs.IFS = vfs.DEFAULT,
    ):
        self.fs = fs
        self.root_dir = root_dir
        self.index = index
        final = snapshot_dir_name(index)
        self.final_dir = os.path.join(root_dir, final)
        if mode == SSMode.SNAPSHOT:
            tmp = f"{final}.{GENERATING_SUFFIX}"
        else:
            tmp = f"{final}-{from_node_id:X}.{RECEIVING_SUFFIX}"
        self.tmp_dir = os.path.join(root_dir, tmp)
        self.fsyncs = 0
        self.image_buffered = False

    def _fsync_dir(self, path: str) -> None:
        self.fsyncs += 1
        try:
            self.fs.fsync_dir(path)
        except OSError:
            return

    # ---- temp stage ----

    def create_tmp_dir(self) -> None:
        """An empty temp dir: one ``mkdir``; only where a crashed save or
        transfer left one under this name is that removed first.

        The root is NOT fsynced here.  That fsync made durable only the
        NAME of a ``.generating`` / ``.receiving`` directory, and no
        recovery reads that name: ``process_orphans`` deletes every temp
        dir on sight, whatever it holds.  What a crash needs is durable
        further on: the image and the flag file by their own fsyncs, their
        names by ``fsync_dir(tmp)``, the final name by the root's fsync
        after the rename (``finalize_snapshot``), all before the LogDB
        record."""
        try:
            self.fs.mkdir(self.tmp_dir)
        except FileExistsError:
            self.remove_tmp_dir()
            self.fs.mkdir(self.tmp_dir)
        except FileNotFoundError:  # an export directory not made yet
            self.fs.makedirs(self.tmp_dir, exist_ok=False)

    def get_tmp_dir(self) -> str:
        return self.tmp_dir

    def get_final_dir(self) -> str:
        return self.final_dir

    def get_tmp_filepath(self) -> str:
        return os.path.join(self.tmp_dir, f"{snapshot_dir_name(self.index)}.ss")

    def get_filepath(self) -> str:
        return os.path.join(self.final_dir, f"{snapshot_dir_name(self.index)}.ss")

    def save_ss_metadata(self, ss: Snapshot) -> None:
        """Write the flag file into the temp dir (reference
        ``fileutil.CreateFlagFile``)."""
        flag = os.path.join(self.tmp_dir, SNAPSHOT_FLAG_FILE)
        data = encode_snapshot(ss)
        self.fsyncs += 1
        self.fs.write_file(flag, len(data).to_bytes(8, "little") + data)
        self._fsync_dir(self.tmp_dir)

    # ---- finalize ----

    def finalize_snapshot(self) -> None:
        """Atomically promote temp → final (reference
        ``finalizeSnapshot``); raises FileExistsError if another replica
        already installed this index."""
        if self.fs.exists(self.final_dir):
            raise FileExistsError(self.final_dir)
        self.fs.replace(self.tmp_dir, self.final_dir)
        self._fsync_dir(self.root_dir)

    def has_flag_file(self) -> bool:
        return self.fs.exists(os.path.join(self.final_dir, SNAPSHOT_FLAG_FILE))

    def remove_flag_file(self) -> None:
        self.fs.remove(os.path.join(self.final_dir, SNAPSHOT_FLAG_FILE))

    def remove_tmp_dir(self) -> None:
        _rmtree(self.tmp_dir, self.fs)

    def remove_final_dir(self) -> None:
        """By the layout a save leaves (the image, the flag file): two
        ``unlink`` and an ``rmdir``.  Anything else (external files, a
        half-removed directory) goes the long way, by ``rmtree``."""
        fs = self.fs
        try:
            fs.remove(self.get_filepath())
            fs.remove(os.path.join(self.final_dir, SNAPSHOT_FLAG_FILE))
            fs.rmdir(self.final_dir)
        except OSError:
            _rmtree(self.final_dir, fs)


def read_ss_metadata(
    dirname: str, fs: vfs.IFS = vfs.DEFAULT
) -> Optional[Snapshot]:
    flag = os.path.join(dirname, SNAPSHOT_FLAG_FILE)
    try:
        with fs.open(flag, "rb") as f:
            n = int.from_bytes(f.read(8), "little")
            return decode_snapshot(f.read(n))
    except (OSError, ValueError):
        return None


def is_temp_snapshot_dir(name: str) -> bool:
    return TEMP_DIR_RE.match(name) is not None


def is_final_snapshot_dir(name: str) -> bool:
    return SNAPSHOT_DIR_RE.match(name) is not None


def snapshot_index_from_dir(name: str) -> int:
    m = SNAPSHOT_DIR_RE.match(name)
    if not m:
        raise ValueError(f"not a snapshot dir {name!r}")
    return int(m.group(1), 16)


def _rmtree(path: str, fs: vfs.IFS = vfs.DEFAULT) -> None:
    try:
        fs.rmtree(path)
    except OSError:
        pass
