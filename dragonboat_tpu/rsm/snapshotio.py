"""Versioned snapshot file IO: header + crc-checked block payload.

Reference: ``internal/rsm/snapshotio.go`` (SnapshotWriter/Reader/Validator,
witness image, shrink) and ``internal/rsm/rw.go`` (v2 block writer with
per-block crc32).  Layout here:

    [1KB header][block]*[tail crc]
    header: magic(8) version(4) checksum_type(4) compression_type(4)
            session_size(8) payload_checksum(4) reserved... header_crc(4 @1020)
    block:  len(u32) crc32(u32) data[len]      (1MB data per block)

``session_size`` lets recovery split the payload into the session store image
and the user SM image without framing inside the payload.  Shrinking keeps
the header and replaces the payload with an empty image (reference
``snapshotio.go:443-516``), used by on-disk SMs whose state needs no replay.
"""
from __future__ import annotations

import os
import struct
import zlib
from typing import BinaryIO, Callable, List, Optional, Tuple

from .. import vfs
from ..settings import Hard
from ..wire import Snapshot, SnapshotFile

MAGIC = b"DBTPUSS1"
V2 = 2
BLOCK_SIZE = 1024 * 1024
_HEADER_FMT = struct.Struct("<8sIIIQI")  # magic, ver, cks, comp, session, payload_crc
_BLOCK_HDR = struct.Struct("<II")
_HEADER_CRC_OFF = 1020

EMPTY_PAYLOAD_CRC = 0

# checksum_type header values: DEFAULT has the aggregate payload crc in the
# header; STREAMED images (ChunkWriter) rely on per-block crcs because the
# aggregate cannot be known before streaming starts
CKS_DEFAULT = 0
CKS_STREAMED = 1


class SnapshotFormatError(ValueError):
    pass


class BlockWriter:
    """Buffers payload into crc'd blocks (reference ``rw.go:89-205``) and
    hands each full block to ``write``; the last, partial one goes out
    through :meth:`flush`, or stays in memory for :meth:`last_block`."""

    def __init__(self, write: Callable[[bytes], object]):
        self._write = write
        self._buf = bytearray()
        self._crc = 0  # running crc over block crcs
        self.total = 0

    def write(self, data: bytes) -> int:
        self._buf += data
        self.total += len(data)
        while len(self._buf) >= BLOCK_SIZE:
            self._flush_block(self._buf[:BLOCK_SIZE])
            del self._buf[:BLOCK_SIZE]
        return len(data)

    def _frame(self, block) -> Tuple[bytes, bytes]:
        data = bytes(block)
        crc = zlib.crc32(data)
        self._crc = zlib.crc32(crc.to_bytes(4, "little"), self._crc)
        return _BLOCK_HDR.pack(len(data), crc), data

    def _flush_block(self, block) -> None:
        hdr, data = self._frame(block)
        self._write(hdr)
        self._write(data)

    def flush(self) -> int:
        """Flush the final partial block; returns the payload checksum."""
        if self._buf:
            self._flush_block(self._buf)
            self._buf.clear()
        return self._crc

    def last_block(self) -> Tuple[bytes, int]:
        """The final partial block as it lies in the file (its header and
        its bytes, empty where there is none), not written, and the
        payload checksum."""
        framed = b""
        if self._buf:
            framed = b"".join(self._frame(self._buf))
            self._buf.clear()
        return framed, self._crc


class BlockReader:
    """Streaming reader over crc'd blocks."""

    def __init__(self, f: BinaryIO):
        self._f = f
        self._pending = bytearray()
        self._crc = 0
        self._eof = False

    def _next_block(self) -> bool:
        hdr = self._f.read(_BLOCK_HDR.size)
        if len(hdr) < _BLOCK_HDR.size:
            self._eof = True
            return False
        ln, crc = _BLOCK_HDR.unpack(hdr)
        data = self._f.read(ln)
        if len(data) != ln or zlib.crc32(data) != crc:
            raise SnapshotFormatError("corrupted snapshot block")
        self._crc = zlib.crc32(crc.to_bytes(4, "little"), self._crc)
        self._pending += data
        return True

    def read(self, n: int = -1) -> bytes:
        while not self._eof and (n < 0 or len(self._pending) < n):
            self._next_block()
        if n < 0:
            out, self._pending = bytes(self._pending), bytearray()
        else:
            out = bytes(self._pending[:n])
            del self._pending[:n]
        return out

    def checksum(self) -> int:
        return self._crc


class SnapshotWriter:
    """Reference ``snapshotio.go:163`` ``SnapshotWriter``.

    With ``compression`` set (dio.CompressionType value, recorded in the
    header's compression_type field) the payload stream — session image and
    user SM image — is compressed before blocking; ``session_size`` always
    refers to UNCOMPRESSED bytes so recovery splits after decompression.

    An image that fits its first block (``BLOCK_SIZE``) touches no file
    until :meth:`finalize`, which then writes header, block header and
    block as ONE write: the header's payload checksum is known by then.
    One that outgrows the block opens the file at that moment and streams
    (placeholder header, blocks, seek back).  The bytes on disk are the
    same either way.  ``before_open`` runs once, just before the file is
    opened (the snapshotter makes the temp directory there).
    """

    def __init__(
        self, path: str, fs: vfs.IFS = vfs.DEFAULT, compression: int = 0,
        before_open: Optional[Callable[[], None]] = None,
    ):
        from .. import dio

        self.path = path
        self._fs = fs
        self.compression = int(compression)
        self._before_open = before_open
        self._f = None
        self._bw = BlockWriter(self._stream)
        self._out = (
            dio.Compressor(dio.CompressionType(self.compression), self._bw)
            if self.compression
            else self._bw
        )
        self.session_size = 0
        #: no block went to the file: the image is all in memory, and
        #: finalize writes it as one write
        self.buffered = True
        #: bytes of the image file so far; all of it once finalized
        self.file_size = 0
        self._created = False
        self._closed = False

    def _prepare(self) -> None:
        if self._before_open is not None:
            self._before_open()
        self._created = True  # from here on abort() has a file to remove

    def _stream(self, data: bytes) -> None:
        """A full block: the image outgrew the memory it is buffered in."""
        if self._f is None:
            self.buffered = False
            self._prepare()
            self._f = self._fs.open(self.path, "wb")
            self._f.write(b"\0" * Hard.snapshot_header_size)  # placeholder
            self.file_size = Hard.snapshot_header_size
        self._f.write(data)
        self.file_size += len(data)

    def write_session(self, data: bytes) -> None:
        self.session_size = len(data)
        self._out.write(data)

    def write(self, data: bytes) -> int:
        self._out.write(data)
        return len(data)

    def seal(self) -> bool:
        """The payload is complete (the compressor's last block flushed);
        True while the whole image is still in memory."""
        if self._out is not self._bw:
            self._out.close()  # flush the final compressed block (once)
        return self.buffered

    def _header(self, payload_crc: int) -> bytes:
        header = bytearray(Hard.snapshot_header_size)
        _HEADER_FMT.pack_into(
            header, 0, MAGIC, V2, 0, self.compression, self.session_size, payload_crc
        )
        hcrc = zlib.crc32(bytes(header[:_HEADER_CRC_OFF]))
        struct.pack_into("<I", header, _HEADER_CRC_OFF, hcrc)
        return bytes(header)

    def finalize(self) -> None:
        if self.seal():
            block, payload_crc = self._bw.last_block()
            image = self._header(payload_crc) + block
            self._prepare()
            self._fs.write_file(self.path, image)
            self.file_size = len(image)
        else:
            header = self._header(self._bw.flush())
            self._f.flush()
            self._f.seek(0)
            self._f.write(header)
            self._fs.fsync(self._f)
            self._f.close()
        self._closed = True

    def abort(self) -> None:
        if not self._closed:
            self._closed = True
            if self._f is not None:
                self._f.close()
            if self._created:
                try:
                    self._fs.remove(self.path)
                except OSError:
                    pass


def read_header(f: BinaryIO) -> Tuple[int, int, int, int, int]:
    """Returns (session_size, payload_crc, version, checksum_type,
    compression_type); validates the header crc."""
    header = f.read(Hard.snapshot_header_size)
    if len(header) != Hard.snapshot_header_size:
        raise SnapshotFormatError("truncated snapshot header")
    magic, ver, cks, comp, session_size, payload_crc = _HEADER_FMT.unpack_from(
        header, 0
    )
    if magic != MAGIC:
        raise SnapshotFormatError("bad snapshot magic")
    if ver != V2:
        raise SnapshotFormatError(f"unsupported snapshot version {ver}")
    (hcrc,) = struct.unpack_from("<I", header, _HEADER_CRC_OFF)
    if zlib.crc32(header[:_HEADER_CRC_OFF]) != hcrc:
        raise SnapshotFormatError("corrupted snapshot header")
    return session_size, payload_crc, ver, cks, comp


class SnapshotReader:
    """Reference ``snapshotio.go:272`` ``SnapshotReader``."""

    def __init__(self, path: str, fs: vfs.IFS = vfs.DEFAULT):
        from .. import dio

        self.path = path
        self._f = fs.open(path, "rb")
        (
            self.session_size,
            self.payload_crc,
            self.version,
            self.checksum_type,
            self.compression,
        ) = read_header(self._f)
        self._br = BlockReader(self._f)
        try:
            ct = dio.CompressionType(self.compression)
        except ValueError as e:
            # malformed-header class of error: callers (the snapshot
            # validator, recovery) expect SnapshotFormatError
            raise SnapshotFormatError(
                f"unknown compression type {self.compression}"
            ) from e
        self._in = (
            dio.Decompressor(ct, self._br) if self.compression else self._br
        )

    def read_session(self) -> bytes:
        return self._in.read(self.session_size)

    def read(self, n: int = -1) -> bytes:
        return self._in.read(n)

    def validate_payload(self) -> None:
        self._br.read(-1)  # drain; per-block crcs verified as a side effect
        if (
            self.checksum_type != CKS_STREAMED
            and self._br.checksum() != self.payload_crc
        ):
            raise SnapshotFormatError("snapshot payload checksum mismatch")

    def close(self) -> None:
        self._f.close()


def validate_snapshot_file(path: str, fs: vfs.IFS = vfs.DEFAULT) -> bool:
    """Reference ``snapshotio.go:392`` ``SnapshotValidator``."""
    try:
        r = SnapshotReader(path, fs)
        try:
            r.validate_payload()
        finally:
            r.close()
        return True
    except (OSError, SnapshotFormatError):
        return False


def shrink_snapshot(src: str, dst: str, fs: vfs.IFS = vfs.DEFAULT) -> None:
    """Strip the payload, keep sessions-empty image (reference
    ``snapshotio.go:443-516`` ``ShrinkSnapshot``): used when an on-disk SM
    restarts — its state needs no replay, only valid metadata."""
    r = SnapshotReader(src, fs)
    try:
        r.validate_payload()
    finally:
        r.close()
    w = SnapshotWriter(dst, fs)
    w.write_session(b"")
    w.finalize()


def write_witness_snapshot(path: str, fs: vfs.IFS = vfs.DEFAULT) -> None:
    """Tiny dummy image for witness replicas (reference
    ``snapshotio.go:133``)."""
    w = SnapshotWriter(path, fs)
    w.write_session(b"")
    w.finalize()


class FileCollection:
    """External snapshot file collection (reference ``internal/rsm/files.go``
    implementing ``sm.ISnapshotFileCollection``)."""

    def __init__(self, tmpdir: str, fs: vfs.IFS = vfs.DEFAULT):
        self.tmpdir = tmpdir
        self._fs = fs
        self.files: List[SnapshotFile] = []
        self._ids = set()

    def add_file(self, file_id: int, path: str, metadata: bytes) -> None:
        if file_id in self._ids:
            raise ValueError(f"duplicated external file id {file_id}")
        self._ids.add(file_id)
        self.files.append(
            SnapshotFile(file_id=file_id, filepath=path, metadata=metadata)
        )

    def prepare_files(self, ss: Snapshot) -> None:
        """Record collected files into the snapshot metadata with their
        final names (reference ``files.go`` ``PrepareFiles``)."""
        for f in self.files:
            final = os.path.join(
                os.path.dirname(ss.filepath) or self.tmpdir,
                f"external-file-{f.file_id}",
            )
            if self._fs.exists(f.filepath):
                self._fs.replace(f.filepath, final)
            size = self._fs.getsize(final) if self._fs.exists(final) else 0
            ss.files.append(
                SnapshotFile(
                    filepath=final,
                    file_size=size,
                    file_id=f.file_id,
                    metadata=f.metadata,
                )
            )
