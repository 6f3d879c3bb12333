"""The driver's two hooks in ``__graft_entry__.py``: the single-chip
``entry()`` and the multi-chip ``dryrun_multichip``."""
import os
import subprocess
import sys

import numpy as np

import jax

import __graft_entry__ as graft

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_entry_jits_and_commits_what_the_engine_commits():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    # the same staged acks through the engine's own dispatch
    eng = graft._example_engine(n_groups=256)
    eng.step(do_tick=True)
    want = np.asarray(eng.dev.committed)
    assert want.max() > 0, "the example stages no ack that commits"
    assert np.array_equal(np.asarray(out.committed), want)


def test_dryrun_multichip_passes_on_the_virtual_mesh(capsys):
    assert len(jax.devices()) >= 8  # tests/conftest.py
    graft.dryrun_multichip(8)
    assert "dryrun_multichip ok: 8 devices" in capsys.readouterr().out


def test_import_leaves_jax_alone_until_a_hook_is_called():
    """One process per chip: whoever imports the hooks (the driver, a
    parent that starts a child for the chip) must not hold a backend
    before it calls one."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, __graft_entry__; sys.exit('jax' in sys.modules)"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-500:]
