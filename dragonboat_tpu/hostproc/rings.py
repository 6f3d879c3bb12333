"""SPSC shared-memory staging rings for the multi-process host plane.

One :class:`ShmRing` is a byte ring over a ``multiprocessing``
``SharedMemory`` segment carrying the same length-prefixed blobs the
host plane already produces (``_pack_len``-style ``<u32 len><payload>``
framing — see :func:`dragonboat_tpu.hostplane._pack_blob`).  The cursor
discipline is seqlock-style single-writer-per-cursor:

- header byte 0:   ``tail`` (u64, total bytes ever pushed) — written
  only by the producer, AFTER the record bytes land;
- header byte 64:  ``head`` (u64, total bytes ever popped) — written
  only by the consumer, AFTER the record bytes were copied out.

The cursors live on separate cache lines and never wrap (u64 of total
bytes; ``cursor % capacity`` is the byte offset), so each side publishes
exactly one aligned 8-byte store and reads the other side's with one
aligned 8-byte load (an item of a ``memoryview.cast("Q")``; a
``Struct.pack_into`` is a zero-fill and then a write, two stores the
other process can read between).  On x86-64 (TSO) that ordering is sufficient
without explicit fences: the producer's record stores cannot sink below
its tail store, and the consumer's loads cannot hoist above its tail
load; the CPython eval loop adds further (incidental) fencing around
every buffer op.  Records may split across the physical end of the
buffer — the ring is a byte ring, not a slot ring, so wraparound is two
memcpys instead of a padding marker.

Blocking/wakeup is layered ABOVE the ring (see ``control.RingClient``
and ``workers.worker_main``): a short busy-poll first, then a
futex-backed ``multiprocessing.Event`` doorbell — the ring itself never
sleeps.  A producer that cannot place a record after its busy window
surfaces :class:`dragonboat_tpu.requests.SystemBusyError` to the caller
(the same backpressure contract as a full ingress staging ring).
"""
from __future__ import annotations

import struct
from multiprocessing import shared_memory
from typing import Optional

_U32 = struct.Struct("<I")

#: header bytes ahead of the data region: tail @0, head @64 — one cache
#: line apart so the two writers never false-share
HEADER = 128


class RingClosed(RuntimeError):
    """The ring's segment is gone (plane stopped underneath the caller)."""


class ShmRing:
    """One single-producer/single-consumer byte ring in shared memory.

    The CREATOR (host process) passes ``create=True`` and owns unlink;
    workers attach by name with ``create=False``.  Capacity is derived
    from the actual segment size on both sides (the kernel page-rounds
    the requested size), so producer and consumer always agree.
    """

    __slots__ = ("shm", "cap", "_owner", "closed", "_cur")

    def __init__(self, capacity: int = 1 << 20, name: Optional[str] = None,
                 create: bool = True):
        if create:
            self.shm = shared_memory.SharedMemory(
                create=True, size=HEADER + max(4096, capacity)
            )
            # zero the header (fresh segments are zero-filled on Linux,
            # but be explicit — reset() reuses this path)
            self.shm.buf[:HEADER] = b"\x00" * HEADER
        else:
            self.shm = shared_memory.SharedMemory(name=name)
            # CPython's resource tracker registers ATTACHED segments too
            # (bpo-38119).  The tracker PROCESS is shared with the host
            # (spawn inherits its fd) and its cache is a set, so the
            # attach-register is a no-op — and an unregister here would
            # strip the HOST's entry and make its unlink-at-stop warn.
            # Leave the shared tracker alone: the creator owns the name.
        self.cap = self.shm.size - HEADER
        self._owner = create
        self.closed = False
        # the cursors as native 64-bit items: an item of a cast view is
        # read and written in ONE aligned 8-byte move.  Struct.pack_into
        # zero-fills its target before it writes, so the other process
        # could read a cursor as 0 between the two (a consumer then
        # takes a record that is not there, a producer sees a full ring)
        self._cur = self.shm.buf[:HEADER].cast("Q")

    # ---- cursors ----

    def _load(self, off: int) -> int:
        return self._cur[off >> 3]

    def _store(self, off: int, v: int) -> None:
        self._cur[off >> 3] = v

    @property
    def tail(self) -> int:
        return self._load(0)

    @property
    def head(self) -> int:
        return self._load(64)

    def depth(self) -> int:
        """Bytes currently staged (producer-published, not yet popped)."""
        return self.tail - self.head

    # ---- byte ring IO (wraparound = two memcpys) ----

    def _write(self, pos: int, data: bytes) -> None:
        off = pos % self.cap
        first = min(len(data), self.cap - off)
        base = HEADER + off
        self.shm.buf[base : base + first] = data[:first]
        rest = len(data) - first
        if rest:
            self.shm.buf[HEADER : HEADER + rest] = data[first:]

    def _read(self, pos: int, n: int) -> bytes:
        off = pos % self.cap
        first = min(n, self.cap - off)
        base = HEADER + off
        out = bytes(self.shm.buf[base : base + first])
        rest = n - first
        if rest:
            out += bytes(self.shm.buf[HEADER : HEADER + rest])
        return out

    # ---- SPSC API ----

    def push(self, blob: bytes) -> bool:
        """Place one length-prefixed record; False when it doesn't fit
        (the caller busy-waits / escalates to SystemBusy — see module
        docstring).  Only ever called from ONE producer at a time (the
        host side serializes with a per-ring lock; logically still SPSC
        at the memory level)."""
        if self.closed:
            raise RingClosed()
        n = 4 + len(blob)
        if n > self.cap:
            raise ValueError(
                f"record of {len(blob)} bytes exceeds ring capacity {self.cap}"
            )
        tail = self._load(0)
        if self.cap - (tail - self._load(64)) < n:
            return False
        self._write(tail, _U32.pack(len(blob)))
        if blob:
            self._write(tail + 4, blob)
        # publish: the ONE producer-side store consumers order on
        self._store(0, tail + n)
        return True

    def pop(self) -> Optional[bytes]:
        """Take one record, or None when the ring is empty."""
        if self.closed:
            raise RingClosed()
        head = self._load(64)
        if self._load(0) == head:
            return None
        (ln,) = _U32.unpack(self._read(head, 4))
        blob = self._read(head + 4, ln) if ln else b""
        # release: the ONE consumer-side store producers order on
        self._store(64, head + 4 + ln)
        return blob

    def reset(self) -> None:
        """Zero both cursors (host side, with the worker KNOWN dead —
        a respawned worker must not replay the dead one's backlog)."""
        self.shm.buf[:HEADER] = b"\x00" * HEADER

    # ---- lifecycle ----

    @property
    def name(self) -> str:
        return self.shm.name

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        try:
            self._cur.release()
            self.shm.close()
        except Exception:
            pass
        if self._owner:
            try:
                self.shm.unlink()
            except Exception:
                pass
