"""The heavy-test lock of ``tests/conftest.py``, held to its two promises
under the distribution mode the driver uses (``-n 2 --dist loadfile``):
two tests carrying ``xdist_group("heavy-multiprocess")`` never overlap in
time, and a test without the mark takes no lock.

One inner pytest run, in a temp directory with a temp directory of its
own (``TMPDIR``), so its lock file is not the one the outer run's heavy
tests hold.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CONFTEST = "from tests.conftest import pytest_runtest_protocol  # noqa\n"

_HEAVY = '''
import fcntl, json, os, time
import pytest
from tests.conftest import HEAVY_LOCK

@pytest.mark.xdist_group("heavy-multiprocess")
def test_heavy():
    t0 = time.time()
    with open(HEAVY_LOCK, "w") as f:
        try:
            fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
            held = False
        except BlockingIOError:
            held = True
    time.sleep(1.0)
    with open(os.path.join(os.environ["OUT"], "{name}.json"), "w") as f:
        json.dump({{"t0": t0, "t1": time.time(), "held": held}}, f)
'''

_LIGHT = '''
import fcntl, json, os
from tests.conftest import HEAVY_LOCK

def test_light():
    with open(HEAVY_LOCK, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)  # raises if held
    with open(os.path.join(os.environ["OUT"], "light.json"), "w") as f:
        json.dump({"took_it": True}, f)
'''


@pytest.fixture(scope="module")
def inner_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("heavy_lock")
    out = root / "out"
    out.mkdir()
    (root / "tmp").mkdir()
    (root / "conftest.py").write_text(_CONFTEST)
    for name in ("a", "b"):
        (root / f"test_heavy_{name}.py").write_text(_HEAVY.format(name=name))
    env = dict(os.environ, PYTHONPATH=REPO, OUT=str(out),
               TMPDIR=str(root / "tmp"), JAX_PLATFORMS="cpu")
    env.pop("PYTEST_XDIST_WORKER", None)

    def run(*args):
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             "-p", "xdist", *args, str(root)],
            cwd=str(root), env=env, capture_output=True, text=True,
            timeout=300,
        )
        assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])

    run("-n", "2", "--dist", "loadfile")
    # alone in its session, so that no heavy test of this run holds the lock
    (root / "test_light.py").write_text(_LIGHT)
    run("-k", "test_light")
    return {p.stem: json.loads(p.read_text()) for p in out.iterdir()}


def test_two_heavy_tests_never_overlap_under_loadfile(inner_run):
    a, b = inner_run["a"], inner_run["b"]
    assert a["held"] and b["held"], "a marked test ran without the lock"
    first, second = sorted((a, b), key=lambda r: r["t0"])
    assert first["t1"] <= second["t0"], (first, second)


def test_unmarked_test_takes_no_lock(inner_run):
    assert inner_run["light"] == {"took_it": True}
