"""ISSUE 38: a regular state machine's update lock is held for the capture
of an image, not for its disk calls.

``rsm.StateMachine.save`` holds ``_update_mu`` across the meta capture and
the image's capture, and lets go where the snapshotter says ``captured``:
with the image in memory for one that fits a block (every disk call of the
save then runs beside the group's applies), with its last block written
for one that spilled.
"""
import ast
import threading
import time

import pytest

from dragonboat_tpu import vfs
from dragonboat_tpu.logdb import open_logdb
from dragonboat_tpu.rsm import StateMachine, Task, from_regular_sm
from dragonboat_tpu.rsm.snapshotio import BLOCK_SIZE, SnapshotReader
from dragonboat_tpu.rsm.statemachine import SSReqType, SSRequest
from dragonboat_tpu.snapshotter import Snapshotter
from dragonboat_tpu.statemachine import IStateMachine, Result
from dragonboat_tpu.wire import Entry


class SeqSM(IStateMachine):
    """Every command is its entry's index; the image says the last one it
    holds, and ``pad`` bytes more (over a block: a spilled image)."""

    def __init__(self, pad=0):
        self.seen = 0
        self.count = 0
        self.pad = pad

    def update(self, cmd):
        self.seen = int(cmd)
        self.count += 1
        return Result(value=self.seen)

    def lookup(self, query):
        return self.seen

    def save_snapshot(self, w, files, done):
        w.write(repr((self.seen, self.count)).encode().ljust(64))
        for i in range(0, self.pad, 300_000):
            w.write(b"\x11" * min(300_000, self.pad - i))

    def recover_from_snapshot(self, r, files, done):
        self.seen, self.count = ast.literal_eval(r.read(64).decode().strip())
        r.read(-1)


class Proxy:
    def node_ready(self):
        pass

    def apply_update(self, *a):
        pass

    def apply_config_change(self, *a):
        pass

    def restore_remotes(self, ss):
        pass

    def should_stop(self):
        return False


class GateFS(vfs.OSFS):
    """The real filesystem with a gate in front of one call on the image:
    the save's thread waits there until the test opens it."""

    def __init__(self, gate_at):
        self.gate_at = gate_at  # "write_file" | "open" | "fsync"
        self.reached = threading.Event()
        self.go = threading.Event()

    def _gate(self, what, path):
        if what == self.gate_at and str(path).endswith(".ss"):
            self.reached.set()
            assert self.go.wait(20)

    def write_file(self, path, data):
        self._gate("write_file", path)
        super().write_file(path, data)

    def open(self, path, mode):
        if "w" in mode:
            self._gate("open", path)
        return super().open(path, mode)

    def fsync(self, f):
        self._gate("fsync", getattr(f, "name", ""))
        super().fsync(f)


def make(tmp_path, fs, pad=0):
    db = open_logdb("", shards=1)
    user = SeqSM(pad)
    snap = Snapshotter(str(tmp_path / "snaps"), 1, 1, db, fs=fs)
    sm = StateMachine(from_regular_sm(user), snap, Proxy(), 1, 1)
    return sm, user, snap, db


def apply(sm, lo, hi):
    ents = [Entry(term=1, index=i, cmd=b"%d" % i) for i in range(lo, hi + 1)]
    sm.handle([Task(cluster_id=1, node_id=1, entries=ents)])


def periodic():
    return SSRequest(type=SSReqType.PERIODIC)


@pytest.mark.parametrize("image,gate_at,held", [
    ("small", "write_file", False),  # the one write: the lock is gone
    ("spilled", "open", True),       # the spill: the payload is mid-way
    ("spilled", "fsync", True),      # its last block: still held
], ids=["small-image_write", "spilled-first_block", "spilled-last_block"])
def test_applies_wait_for_the_capture_not_for_the_disk(
        tmp_path, image, gate_at, held):
    fs = GateFS(gate_at)
    sm, user, snap, db = make(
        tmp_path, fs, pad=0 if image == "small" else BLOCK_SIZE + 999)
    try:
        apply(sm, 1, 10)
        out = {}
        saver = threading.Thread(
            target=lambda: out.update(saved=sm.save(periodic())))
        saver.start()
        assert fs.reached.wait(20)  # the save sits in its disk call
        applied = threading.Event()

        def one_more():
            apply(sm, 11, 11)
            applied.set()

        applier = threading.Thread(target=one_more)
        applier.start()
        if held:
            assert not applied.wait(0.5)  # the group's applies are held out
            assert sm.get_last_applied() == 10
        else:
            assert applied.wait(10)  # the apply ran beside the disk call
            assert sm.get_last_applied() == 11 and user.seen == 11
        fs.go.set()
        saver.join(20)
        applier.join(20)
        assert applied.is_set() and not saver.is_alive()
        ss, env = out["saved"]
        assert ss.index == 10 and env.image_buffered == (image == "small")
        # the image is the state at its label, whatever ran beside it
        snap.commit(ss, env)
        r = SnapshotReader(ss.filepath)
        r.read_session()
        assert ast.literal_eval(r.read(64).decode().strip()) == (10, 10)
        r.close()
    finally:
        fs.go.set()
        db.close()


@pytest.mark.parametrize("image", ["small", "spilled"])
def test_an_images_label_is_its_contents_index_under_concurrent_applies(
        tmp_path, image):
    """The double-apply case ``save``'s docstring names: an image newer
    than its label would apply entries twice after a recovery."""
    sm, user, snap, db = make(
        tmp_path, vfs.OSFS(), pad=0 if image == "small" else BLOCK_SIZE + 999)
    saves = 12 if image == "small" else 4
    try:
        stop = threading.Event()
        top = [0]

        def applier():
            i = 0
            while not stop.is_set():
                apply(sm, i + 1, i + 5)
                i += 5
                top[0] = i
                time.sleep(0)

        t = threading.Thread(target=applier)
        t.start()
        labels = []
        deadline = time.time() + 30
        while len(labels) < saves and time.time() < deadline:
            before = top[0]
            while top[0] < before + 10 and time.time() < deadline:
                time.sleep(0.001)  # progress between two saves
            ss, env = sm.save(periodic())
            snap.commit(ss, env)
            r = SnapshotReader(ss.filepath)
            r.read_session()
            seen, count = ast.literal_eval(r.read(64).decode().strip())
            r.close()
            labels.append((ss.index, seen, count))
        stop.set()
        t.join(20)
        assert len(labels) == saves
        for index, seen, count in labels:
            assert index == seen == count, labels
        assert [i for i, _, _ in labels] == sorted({i for i, _, _ in labels})
        # and a recovery from the newest one replays nothing twice
        user2 = SeqSM(user.pad)
        sm2 = StateMachine(from_regular_sm(user2), snap, Proxy(), 1, 1)
        newest = snap.get_most_recent_snapshot()
        sm2.recover(Task(recover=True, ss=newest))
        assert (user2.seen, user2.count) == (newest.index, newest.index)
        assert sm2.get_last_applied() == newest.index
    finally:
        stop.set()
        db.close()


def test_a_failed_save_gives_the_lock_back(tmp_path):
    """Whatever fails, before or after the capture, the update lock is
    released once and applies go on."""
    for after_n in (0, 1, 2, 3):
        fs = vfs.ErrorFS(vfs.OSFS(), vfs.Injector.after_n(
            after_n, substr=".generating"))
        sm, user, snap, db = make(tmp_path / str(after_n), fs)
        try:
            apply(sm, 1, 5)
            with pytest.raises(OSError, match="injected"):
                sm.save(periodic())
            done = threading.Event()
            threading.Thread(
                target=lambda: (apply(sm, 6, 6), done.set())).start()
            assert done.wait(10)
            assert not sm._update_mu._is_owned()
        finally:
            db.close()
