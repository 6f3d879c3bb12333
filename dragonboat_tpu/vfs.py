"""Virtual filesystem abstraction + fault injection.

Reference: ``internal/vfs/vfs.go:28-45`` (``IFS`` wrapper over goutils vfs),
``internal/vfs/memfs.go`` (in-memory FS for whole-stack single-process
tests) and ``internal/vfs/error.go:25-52`` (``ErrorFS``/``Injector``
wrapping an FS to inject I/O errors, auto-detected by NodeHost to enable
panic capture, ``nodehost.go:321-327``).

Three implementations:

- :class:`OSFS` — the real filesystem (module default :data:`DEFAULT`).
- :class:`MemFS` — fully in-memory; lets snapshot/logdb paths run without
  touching disk, the analog of the reference memfs test builds.
- :class:`ErrorFS` — wraps another FS and consults an :class:`Injector`
  before every operation; used by fault-injection tests to prove failed
  saves leave no partial state behind.
"""
from __future__ import annotations

import io
import os
import threading
from typing import Callable, Dict, List, Optional


class IFS:
    """Operation surface the framework's file IO goes through."""

    def open(self, path: str, mode: str):  # "rb" | "wb" | "ab" | "r+b"
        raise NotImplementedError

    def remove(self, path: str) -> None:
        raise NotImplementedError

    def replace(self, src: str, dst: str) -> None:
        raise NotImplementedError

    def makedirs(self, path: str, exist_ok: bool = True) -> None:
        raise NotImplementedError

    def mkdir(self, path: str) -> None:
        """One directory under a parent that is there: FileExistsError
        where ``path`` is, FileNotFoundError where the parent is not."""
        raise NotImplementedError

    def rmdir(self, path: str) -> None:
        raise NotImplementedError

    def rmtree(self, path: str) -> None:
        raise NotImplementedError

    def listdir(self, path: str) -> List[str]:
        raise NotImplementedError

    def exists(self, path: str) -> bool:
        raise NotImplementedError

    def isdir(self, path: str) -> bool:
        raise NotImplementedError

    def getsize(self, path: str) -> int:
        raise NotImplementedError

    def fsync(self, f) -> None:
        raise NotImplementedError

    def fsync_dir(self, path: str) -> None:
        raise NotImplementedError

    def write_file(self, path: str, data: bytes) -> None:
        """A new file that holds ``data``, fsynced and closed: open, ONE
        write, fsync, close.  Here through this FS's own ``open`` /
        ``fsync``, so a wrapper (``ErrorFS``) sees every step."""
        with self.open(path, "wb") as f:
            f.write(data)
            self.fsync(f)


class OSFS(IFS):
    """Pass-through to the real filesystem."""

    def open(self, path: str, mode: str):
        return open(path, mode)

    def remove(self, path: str) -> None:
        os.unlink(path)

    def replace(self, src: str, dst: str) -> None:
        os.replace(src, dst)

    def makedirs(self, path: str, exist_ok: bool = True) -> None:
        os.makedirs(path, exist_ok=exist_ok)

    def mkdir(self, path: str) -> None:
        os.mkdir(path)

    def rmdir(self, path: str) -> None:
        os.rmdir(path)

    def rmtree(self, path: str) -> None:
        import shutil

        shutil.rmtree(path)

    def listdir(self, path: str) -> List[str]:
        return os.listdir(path)

    def exists(self, path: str) -> bool:
        return os.path.exists(path)

    def isdir(self, path: str) -> bool:
        return os.path.isdir(path)

    def getsize(self, path: str) -> int:
        return os.path.getsize(path)

    def fsync(self, f) -> None:
        f.flush()
        os.fsync(f.fileno())

    def fsync_dir(self, path: str) -> None:
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)

    def write_file(self, path: str, data: bytes) -> None:
        # on the descriptor: four calls that let the interpreter go, where
        # a buffered file object makes eight (open is open + fstat +
        # isatty)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o666)
        try:
            view = memoryview(data)
            while view:
                view = view[os.write(fd, view):]
            os.fsync(fd)
        finally:
            os.close(fd)


class _MemFile(io.BytesIO):
    """File handle over a MemFS entry; content lands in the FS on flush."""

    def __init__(self, fs: "MemFS", path: str, data: bytes, append: bool):
        super().__init__(data)
        if append:
            self.seek(0, io.SEEK_END)
        self._fs = fs
        self._path = path

    def flush(self) -> None:
        super().flush()
        # store only while the entry still exists: a handle left open
        # across remove()/rmtree() must not resurrect the file when it is
        # eventually flushed or GC-closed (BytesIO.__del__ calls close →
        # flush) — POSIX writes to an unlinked file vanish with the inode.
        # Without this, an abandoned writer handle (e.g. a fault-injected
        # SnapshotWriter kept alive by the exception traceback) re-created
        # its file AFTER the snapshot temp-dir cleanup had removed it.
        self._fs._store_if_tracked(self._path, self.getvalue())

    def close(self) -> None:
        if not self.closed:
            self.flush()
        super().close()

    def fileno(self) -> int:  # keep os.fsync() off memfs handles
        raise io.UnsupportedOperation("memfs file has no fd")


class MemFS(IFS):
    """In-memory filesystem (reference ``internal/vfs/memfs.go``)."""

    def __init__(self) -> None:
        self._files: Dict[str, bytes] = {}
        self._dirs = {"/"}
        self._mu = threading.RLock()

    @staticmethod
    def _norm(path: str) -> str:
        return os.path.normpath(path)

    def _store(self, path: str, data: bytes) -> None:
        with self._mu:
            self._files[self._norm(path)] = bytes(data)

    def _store_if_tracked(self, path: str, data: bytes) -> None:
        """Flush-path store: a no-op once the entry was removed (the
        unlinked-inode semantics _MemFile.flush relies on).  ``open``
        registers the entry up front, so live handles always store."""
        path = self._norm(path)
        with self._mu:
            if path in self._files:
                self._files[path] = bytes(data)

    def open(self, path: str, mode: str):
        path = self._norm(path)
        with self._mu:
            if "r" in mode and "+" not in mode:
                if path not in self._files:
                    raise FileNotFoundError(path)
                f = io.BytesIO(self._files[path])
                return f
            existing = self._files.get(path, b"")
            if "w" in mode:
                existing = b""
            parent = os.path.dirname(path)
            if parent and parent not in self._dirs:
                raise FileNotFoundError(f"no directory {parent}")
            mf = _MemFile(self, path, existing, append="a" in mode)
            self._files.setdefault(path, existing)
            return mf

    def remove(self, path: str) -> None:
        path = self._norm(path)
        with self._mu:
            if path not in self._files:
                raise FileNotFoundError(path)
            del self._files[path]

    def replace(self, src: str, dst: str) -> None:
        src, dst = self._norm(src), self._norm(dst)
        with self._mu:
            if src in self._dirs:  # directory rename moves the subtree
                prefix = src + os.sep
                self._files = {
                    (dst + k[len(src) :] if k.startswith(prefix) else k): v
                    for k, v in self._files.items()
                }
                self._dirs = {
                    (dst + d[len(src) :] if d == src or d.startswith(prefix) else d)
                    for d in self._dirs
                }
                return
            if src not in self._files:
                raise FileNotFoundError(src)
            self._files[dst] = self._files.pop(src)

    def makedirs(self, path: str, exist_ok: bool = True) -> None:
        path = self._norm(path)
        with self._mu:
            if path in self._dirs and not exist_ok:
                raise FileExistsError(path)
            parts = path.split(os.sep)
            cur = "" if not path.startswith(os.sep) else os.sep
            for p in parts:
                if not p:
                    continue
                cur = os.path.join(cur, p) if cur else p
                self._dirs.add(cur)

    def mkdir(self, path: str) -> None:
        path = self._norm(path)
        with self._mu:
            if path in self._dirs or path in self._files:
                raise FileExistsError(path)
            if os.path.dirname(path) not in self._dirs:
                raise FileNotFoundError(path)
            self._dirs.add(path)

    def rmdir(self, path: str) -> None:
        path = self._norm(path)
        with self._mu:
            if self.listdir(path):
                raise OSError(f"directory not empty: {path}")
            self._dirs.discard(path)

    def rmtree(self, path: str) -> None:
        path = self._norm(path)
        prefix = path + os.sep
        with self._mu:
            self._files = {
                k: v for k, v in self._files.items() if not k.startswith(prefix)
            }
            self._dirs = {
                d for d in self._dirs if d != path and not d.startswith(prefix)
            }

    def listdir(self, path: str) -> List[str]:
        path = self._norm(path)
        with self._mu:
            if path not in self._dirs:
                raise FileNotFoundError(path)
            prefix = path + os.sep
            out = set()
            for k in list(self._files) + list(self._dirs):
                if k.startswith(prefix):
                    rest = k[len(prefix) :]
                    out.add(rest.split(os.sep)[0])
            return sorted(out)

    def exists(self, path: str) -> bool:
        path = self._norm(path)
        with self._mu:
            return path in self._files or path in self._dirs

    def isdir(self, path: str) -> bool:
        with self._mu:
            return self._norm(path) in self._dirs

    def getsize(self, path: str) -> int:
        path = self._norm(path)
        with self._mu:
            if path not in self._files:
                raise FileNotFoundError(path)
            return len(self._files[path])

    def fsync(self, f) -> None:
        f.flush()

    def fsync_dir(self, path: str) -> None:
        pass


class Injector:
    """Decides which operations fail (reference ``error.go`` ``Injector``).

    ``policy(op, path) -> bool`` returns True to inject.  Helpers build the
    common shapes: fail every op matching a substring, or start failing
    after N matching ops (to hit the middle of a multi-write sequence).
    """

    def __init__(self, policy: Callable[[str, str], bool]):
        self._policy = policy
        self.injected = 0

    def maybe_fail(self, op: str, path: str) -> None:
        if self._policy(op, path):
            self.injected += 1
            raise OSError(f"injected error: {op} {path}")

    @classmethod
    def on_path(cls, substr: str, ops: Optional[set] = None) -> "Injector":
        return cls(
            lambda op, path: substr in path and (ops is None or op in ops)
        )

    @classmethod
    def after_n(
        cls, n: int, ops: Optional[set] = None, substr: str = ""
    ) -> "Injector":
        count = [0]

        def policy(op: str, path: str) -> bool:
            if (ops is None or op in ops) and substr in path:
                count[0] += 1
                return count[0] > n
            return False

        return cls(policy)


class _ErrorFile:
    """Wraps a file handle so write/fsync go through the injector."""

    def __init__(self, efs: "ErrorFS", path: str, f):
        self._efs = efs
        self._path = path
        self._f = f

    def write(self, data):
        self._efs.injector.maybe_fail("write", self._path)
        return self._f.write(data)

    def read(self, *a):
        self._efs.injector.maybe_fail("read", self._path)
        return self._f.read(*a)

    def __getattr__(self, name):
        return getattr(self._f, name)

    # dunder lookups bypass __getattr__ (type-level resolution), so the
    # context-manager protocol must be explicit — without it every
    # `with fs.open(...)` in the snapshot path fails under ErrorFS,
    # which silently exempted that whole path from fault injection
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()  # through the wrapper: keeps close injectable
        return False


class ErrorFS(IFS):
    """FS wrapper injecting errors per an :class:`Injector`."""

    def __init__(self, fs: IFS, injector: Injector):
        self.fs = fs
        self.injector = injector

    def open(self, path: str, mode: str):
        self.injector.maybe_fail("open", path)
        return _ErrorFile(self, path, self.fs.open(path, mode))

    def remove(self, path: str) -> None:
        self.injector.maybe_fail("remove", path)
        self.fs.remove(path)

    def replace(self, src: str, dst: str) -> None:
        self.injector.maybe_fail("replace", dst)
        self.fs.replace(src, dst)

    def makedirs(self, path: str, exist_ok: bool = True) -> None:
        self.injector.maybe_fail("makedirs", path)
        self.fs.makedirs(path, exist_ok=exist_ok)

    def mkdir(self, path: str) -> None:
        self.injector.maybe_fail("mkdir", path)
        self.fs.mkdir(path)

    def rmdir(self, path: str) -> None:
        self.injector.maybe_fail("rmdir", path)
        self.fs.rmdir(path)

    def rmtree(self, path: str) -> None:
        self.injector.maybe_fail("rmtree", path)
        self.fs.rmtree(path)

    def listdir(self, path: str) -> List[str]:
        self.injector.maybe_fail("listdir", path)
        return self.fs.listdir(path)

    def exists(self, path: str) -> bool:
        return self.fs.exists(path)

    def isdir(self, path: str) -> bool:
        return self.fs.isdir(path)

    def getsize(self, path: str) -> int:
        self.injector.maybe_fail("getsize", path)
        return self.fs.getsize(path)

    def fsync(self, f) -> None:
        path = getattr(f, "_path", "")
        self.injector.maybe_fail("fsync", path)
        inner = getattr(f, "_f", f)
        self.fs.fsync(inner)

    def fsync_dir(self, path: str) -> None:
        self.injector.maybe_fail("fsync_dir", path)
        self.fs.fsync_dir(path)


DEFAULT = OSFS()


def is_error_fs(fs: IFS) -> bool:
    """NodeHost auto-detects an ErrorFS to enable engine panic capture
    (reference ``nodehost.go:321-327``)."""
    return isinstance(fs, ErrorFS)
