"""ISSUE 38: a save makes fewer calls around the same bytes.

The image file, the flag file and the LogDB's snapshot record a save leaves
are, byte for byte, what the parent commit (60d7ce7) wrote for the same
state: the digests below were taken from the parent's code
(``SnapshotWriter`` with its placeholder header and seek back, the flag file
as two writes, ``logdb.save_snapshot``), and a plain encoder of the
documented layout stands beside them.  And the number of fs calls, fsyncs
and LogDB commits of a steady-state buffered save is pinned, so that a PR
that adds a call to the save sees it.
"""
import hashlib
import os
import random
import struct
import zlib

import pytest

from dragonboat_tpu import dio, vfs
from dragonboat_tpu.logdb import keys, open_logdb
from dragonboat_tpu.rsm.snapshotio import (
    BLOCK_SIZE,
    MAGIC,
    SnapshotReader,
    SnapshotWriter,
    shrink_snapshot,
    validate_snapshot_file,
    write_witness_snapshot,
)
from dragonboat_tpu.rsm.statemachine import SSMeta
from dragonboat_tpu.server.snapshotenv import (
    SNAPSHOT_FLAG_FILE,
    SSEnv,
    SSMode,
    read_ss_metadata,
)
from dragonboat_tpu.snapshotter import Snapshotter
from dragonboat_tpu.wire import Membership, Snapshot

# sha256 and size of what the PARENT wrote (60d7ce7; generated from its
# code, not from this tree's)
PARENT = {
    "buffered-0": ("19af5356b9eb3a3eba41dc6b0553522b589b398a9cf1c09c3ca546534482beff", 9824),
    "buffered-1": ("3459e105d339e787a412485eb32ab2c829300a327ce8c18ae814708771337394", 5268),
    "spilled-0": ("75685877f5249c37dac4d49c3d3cc95470e8b3103e816940a9a12e5aee7e62f2", 2622545),
    "block_edge-0": ("16006073926936a994451431402b1b1350b79cba317a2f77034de2ef1155faa3", 1049608),
    "spilled_incompressible-1": ("5f9a6a6a06f633597142253699c68feac1adb9189603d6a16c9b1fb075c30c40", 1311857),
    "witness": ("e179fbddd230bfe8b392c94847020fcba95b442a558ada57fbef512814523cfa", 1024),
    "shrunk": ("e179fbddd230bfe8b392c94847020fcba95b442a558ada57fbef512814523cfa", 1024),
    "flag": ("821c7fcb10eee779578aec13098cab0b0ce398450e2af75f00953ccc91a4b3fc", 96),
    "record": ("aaaa5644e535e4c9598d924eaba2945aabf54dedf7f8ae81a6560fcf601e7726", 88),
}
#: which of them fit a block, so went out as one write
BUFFERED = {"buffered-0", "buffered-1", "witness", "shrunk"}
FIXED_PATH = "/snap/snapshot-000000000000002A/snapshot-000000000000002A.ss"


def payload(name):
    if name == "buffered":
        return b"sess" * 3, repr(
            sorted((f"k{i}", f"v{i}") for i in range(500))).encode()
    if name == "spilled":
        return b"s" * 40, random.Random(7).randbytes(
            2 * BLOCK_SIZE + BLOCK_SIZE // 2 + 17)
    if name == "block_edge":
        return b"", b"\x5a" * BLOCK_SIZE
    if name == "spilled_incompressible":
        return b"s" * 40, random.Random(11).randbytes(
            BLOCK_SIZE + BLOCK_SIZE // 4)
    raise KeyError(name)


def fixed_snapshot():
    return Snapshot(
        filepath=FIXED_PATH, file_size=12345, index=0x2A, term=3,
        membership=Membership(addresses={1: "a1", 2: "a2", 3: "a3"}),
        cluster_id=7,
    )


def plain_image(session, data, compression):
    """The documented layout, written down plainly: ``[1KB header][block]*``
    with ``len crc32 data`` blocks of 1 MB and the crc over block crcs in
    the header."""

    class Sink:
        def __init__(self):
            self.buf = bytearray()

        def write(self, b):
            self.buf += b
            return len(b)

    stream = session + data
    if compression:
        sink = Sink()
        c = dio.Compressor(dio.CompressionType(compression), sink)
        c.write(session)
        for i in range(0, len(data), 300_000):
            c.write(data[i:i + 300_000])
        c.close()
        stream = bytes(sink.buf)
    blocks, running = b"", 0
    for i in range(0, len(stream), BLOCK_SIZE):
        block = stream[i:i + BLOCK_SIZE]
        crc = zlib.crc32(block)
        running = zlib.crc32(crc.to_bytes(4, "little"), running)
        blocks += struct.pack("<II", len(block), crc) + block
    header = bytearray(1024)
    struct.pack_into("<8sIIIQI", header, 0, MAGIC, 2, 0, compression,
                     len(session), running)
    struct.pack_into("<I", header, 1020, zlib.crc32(bytes(header[:1020])))
    return bytes(header) + blocks


def write_image(path, name, compression, fs=vfs.DEFAULT):
    w = SnapshotWriter(path, fs, compression=compression)
    session, data = payload(name)
    w.write_session(session)
    for i in range(0, len(data), 300_000):
        w.write(data[i:i + 300_000])
    w.finalize()
    return w


def digest(data):
    return hashlib.sha256(data).hexdigest()


@pytest.mark.parametrize("case", [
    "buffered-0", "buffered-1", "spilled-0", "block_edge-0",
    "spilled_incompressible-1",
])
def test_image_bytes_are_the_parents(tmp_path, case):
    name, comp = case.rsplit("-", 1)
    path = str(tmp_path / "img.ss")
    w = write_image(path, name, int(comp))
    got = open(path, "rb").read()
    assert (digest(got), len(got)) == PARENT[case]
    assert got == plain_image(*payload(name), int(comp))
    assert w.buffered == (case in BUFFERED)
    assert w.file_size == len(got) == os.path.getsize(path)
    # and the readers read it
    assert validate_snapshot_file(path)
    r = SnapshotReader(path)
    session, data = payload(name)
    assert r.read_session() == session
    assert r.read(-1) == data
    r.close()


@pytest.mark.parametrize("case", ["witness", "shrunk"])
def test_witness_and_shrunk_images_are_the_parents(tmp_path, case):
    path = str(tmp_path / f"{case}.ss")
    if case == "witness":
        write_witness_snapshot(path)
    else:
        src = str(tmp_path / "src.ss")
        write_image(src, "buffered", 0)
        shrink_snapshot(src, path)
    got = open(path, "rb").read()
    assert (digest(got), len(got)) == PARENT[case]
    assert got == plain_image(b"", b"", 0)
    assert validate_snapshot_file(path)


def test_flag_file_bytes_are_the_parents(tmp_path):
    env = SSEnv(str(tmp_path), 0x2A, 1, SSMode.SNAPSHOT)
    env.create_tmp_dir()
    env.save_ss_metadata(fixed_snapshot())
    got = open(os.path.join(env.get_tmp_dir(), SNAPSHOT_FLAG_FILE), "rb").read()
    assert (digest(got), len(got)) == PARENT["flag"]
    assert read_ss_metadata(env.get_tmp_dir()) == fixed_snapshot()


@pytest.mark.parametrize("how", ["save_snapshot", "commit_snapshot"])
def test_logdb_record_bytes_are_the_parents(how):
    db = open_logdb("", shards=1)
    try:
        ss = fixed_snapshot()
        if how == "save_snapshot":
            db.save_snapshot(7, 1, ss)
        else:
            db.commit_snapshot(7, 1, ss, [])
        rec = db._shards[0].kv.get(keys.snapshot_key(7, 1, 0x2A))
        assert (digest(rec), len(rec)) == PARENT["record"]
        assert db.list_snapshots(7, 1) == [ss]
    finally:
        db.close()


def test_commit_snapshot_is_two_calls_in_one_batch():
    """``commit_snapshot`` leaves the LogDB as ``save_snapshot`` +
    ``delete_snapshot`` do, under one commit, and touches no entry."""
    from dragonboat_tpu.wire import Entry, State, Update

    def world():
        db = open_logdb("", shards=1)
        ents = [Entry(term=1, index=i, cmd=b"c%d" % i) for i in range(1, 31)]
        db.save_raft_state([Update(
            cluster_id=7, node_id=1, entries_to_save=ents,
            state=State(term=1, vote=1, commit=30))])
        for idx in (5, 10, 15):
            db.save_snapshot(7, 1, Snapshot(index=idx, term=1, cluster_id=7))
        return db

    new = Snapshot(index=20, term=1, cluster_id=7)
    one, two = world(), world()
    try:
        commits = []
        kv = one._shards[0].kv
        real = kv.commit_write_batch
        kv.commit_write_batch = lambda wb: (commits.append(len(wb)), real(wb))
        one.commit_snapshot(7, 1, new, [5])
        assert commits == [2]  # one batch: a put, a delete
        two.save_snapshot(7, 1, new)
        two.delete_snapshot(7, 1, 5)
        a, b = one._shards[0].kv, two._shards[0].kv
        assert a._data == b._data and a._keys == b._keys
        assert [s.index for s in one.list_snapshots(7, 1)] == [10, 15, 20]
        got, _ = one.iterate_entries([], 0, 7, 1, 1, 31, 1 << 62)
        assert [e.index for e in got] == list(range(1, 31))
    finally:
        one.close()
        two.close()


# ---- the calls of one steady-state buffered save, pinned -----------------


class CountingFS(vfs.IFS):
    """Every call of the surface by name, over a real FS."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = []

    def __getattribute__(self, name):
        if name in ("inner", "calls") or name.startswith("__"):
            return object.__getattribute__(self, name)
        target = getattr(object.__getattribute__(self, "inner"), name)
        calls = object.__getattribute__(self, "calls")

        def counted(*a, **k):
            calls.append(name)
            return target(*a, **k)

        return counted


class _KV:
    def __init__(self, n):
        self.data = {f"k{i}": f"v{i}" for i in range(n)}

    def save_snapshot_payload(self, meta, w):
        w.write_session(b"")
        w.write(repr(sorted(self.data.items())).encode())


#: fs calls of ONE steady-state periodic save of an image that fits a
#: block, the fourth of a group (it drops the oldest snapshot), in order.
#: A PR that changes this list changes what a save costs (PERF.md §6,
#: PR 38): say so there.
STEADY_STATE_CALLS = [
    "mkdir",        # the temp dir
    "write_file",   # the image: open, ONE write, fsync, close
    "write_file",   # the flag file: the same
    "fsync_dir",    # the temp dir: the two names
    "exists",       # finalize: another replica's install at this index
    "replace",      # temp -> final
    "fsync_dir",    # the root: the final name
    "remove",       # the oldest snapshot's image
    "remove",       # ... its flag file
    "rmdir",        # ... its directory
]


def test_calls_fsyncs_and_commits_of_a_steady_state_save_are_pinned(tmp_path):
    from dragonboat_tpu.wire import Entry, State, Update

    fs = CountingFS(vfs.OSFS())
    db = open_logdb("", shards=1)
    try:
        ents = [Entry(term=1, index=i, cmd=b"x") for i in range(1, 101)]
        db.save_raft_state([Update(
            cluster_id=1, node_id=1, entries_to_save=ents,
            state=State(term=1, vote=1, commit=100))])
        snap = Snapshotter(str(tmp_path / "snaps"), 1, 1, db, fs=fs)
        kv = db._shards[0].kv
        commits = []
        real = kv.commit_write_batch
        kv.commit_write_batch = lambda wb: (commits.append(len(wb)), real(wb))
        for n, index in enumerate((20, 40, 60, 80), start=1):
            del fs.calls[:], commits[:]
            meta = SSMeta(index=index, term=1,
                          membership=Membership(addresses={1: "a"}))
            ss, env = snap.save(_KV(index), meta)
            stale = snap.commit(ss, env)
            db.remove_entries_to(1, 1, index - 5)
            snap.remove_dirs(stale)
            assert env.image_buffered
            # image, flag file, temp dir, root; a LogDB batch is one more
            assert env.fsyncs == 4
            assert [s.index for s in stale] == ([20] if n == 4 else [])
        assert fs.calls == STEADY_STATE_CALLS
        # two batches: the record + the stale record's delete; the range
        assert commits == [2, 1]
        assert [s.index for s in db.list_snapshots(1, 1)] == [40, 60, 80]
        for s in db.list_snapshots(1, 1):
            assert validate_snapshot_file(s.filepath)
        assert sorted(os.listdir(str(tmp_path / "snaps"))) == [
            "snapshot-%016X" % i for i in (40, 60, 80)]
    finally:
        db.close()


def test_write_file_on_the_descriptor_writes_what_a_file_object_would(tmp_path):
    data = random.Random(3).randbytes(70_000)
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    vfs.OSFS().write_file(a, data)
    vfs.IFS.write_file(vfs.OSFS(), b, data)  # the plain form: open/write/fsync
    assert open(a, "rb").read() == open(b, "rb").read() == data
    vfs.OSFS().write_file(a, b"short")  # truncates what was there
    assert open(a, "rb").read() == b"short"
    m = vfs.MemFS()
    m.makedirs("/d")
    m.write_file("/d/x", data)
    assert m.getsize("/d/x") == len(data)
    efs = vfs.ErrorFS(m, vfs.Injector.after_n(0, ops={"fsync"}))
    with pytest.raises(OSError, match="injected"):
        efs.write_file("/d/y", data)  # a wrapper sees every step
