"""One platform decision that fails loudly, and a placeable compile cache.

All on the CPU backend: ``hostplatform.require_tpu`` raises here (there is
no TPU), the launch scripts exit non-zero without their explicit
rehearsal setting, ``chip_smoke.py --rehearse-cpu`` passes at tiny size
on one and on four virtual devices, a mesh wider than the device list
raises instead of running unsharded, the ``auto`` engine probe runs in
process and raises on error, and the persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says or else to one fixed in-checkout path.
"""
import json
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

from dragonboat_tpu import hostplatform  # noqa: E402
from dragonboat_tpu.ops import engine as ops_engine  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    _BENCHMARK = json.load(_f)


def _run(args, timeout=600, **env):
    """Run a repo script the way a user would, on the CPU backend."""
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=full, capture_output=True,
        text=True, timeout=timeout,
    )


def test_require_tpu_raises_on_the_cpu_backend():
    with pytest.raises(RuntimeError) as ei:
        hostplatform.require_tpu()
    # the error names what jax.devices() returned
    assert "cpu" in str(ei.value) and "jax.devices()" in str(ei.value)


@pytest.mark.parametrize("script", [
    ["chip_smoke.py"],
    ["chip_smoke.py", "--chips", "4"],
    *(["benchmark/run.py", "--workload", w["name"], "--seed", "1",
       "--seconds", "4", "--trace", "0"] for w in _BENCHMARK["workloads"]),
], ids=lambda a: " ".join(a[:3]))
def test_scripts_exit_nonzero_without_a_tpu(script):
    r = _run(script, timeout=300)
    assert r.returncode != 0, r.stdout[-500:]
    # no result line, no device metric under any name
    assert '"ok"' not in r.stdout and "ops_per_s" not in r.stdout
    assert "need 1 TPU" in r.stderr or "need 4 TPU" in r.stderr, r.stderr[-800:]


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal_passes_on_cpu(chips):
    r = _run(["chip_smoke.py", "--rehearse-cpu", "--chips", str(chips)])
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= chips
    phases = [x["phase"] for x in lines[:-1]]
    want = (["native", "mesh", "live_mesh", "total"] if chips == 4
            else ["native", "live", "engine", "total"])
    assert phases == want
    # a rehearsal never passes for a chip run
    assert all(x["rehearsal"] and x["platform"] == "cpu" for x in lines[:-1])


def test_mesh_wider_than_the_device_list_raises(monkeypatch):
    from dragonboat_tpu.tpuquorum import TpuQuorumCoordinator

    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    with pytest.raises(ValueError, match="engine_mesh_devices=4"):
        TpuQuorumCoordinator(capacity=64, mesh_devices=4)


def test_auto_probe_runs_in_process_and_raises_on_error(monkeypatch):
    from dragonboat_tpu.nodehost import NodeHost

    # a dispatch that fits (any budget) / does not fit (no budget)
    assert NodeHost._dispatch_within_budget(budget_ms=60_000.0) is True
    assert NodeHost._dispatch_within_budget(budget_ms=-1.0) is False

    def boom(self, do_tick=True):
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(ops_engine.BatchedQuorumEngine, "step", boom)
    with pytest.raises(RuntimeError, match="backend init failed"):
        NodeHost._dispatch_within_budget()


_CACHE_PROBE = """
import json, os, sys
import jax
updates = []
_orig = jax.config.update
def spy(name, val):
    updates.append(name)
    return _orig(name, val)
jax.config.update = spy
from dragonboat_tpu.ops.engine import (
    compilation_cache_stats, enable_persistent_compilation_cache)
d = enable_persistent_compilation_cache(sys.argv[1])
jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(7)).block_until_ready()
print(json.dumps({
    "dir": d, "config_dir": jax.config.jax_compilation_cache_dir,
    "dir_set_in_code": "jax_compilation_cache_dir" in updates,
    "entries": len(os.listdir(d)), "stats": compilation_cache_stats(),
}))
sys.stdout.flush(); os._exit(0)
"""


def _cache_probe(configured="", **env):
    r = _run(["-c", _CACHE_PROBE, configured], **env)
    assert r.returncode == 0, r.stderr[-800:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_goes_where_the_env_var_says(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: used as is (no kernel-hash
    sub-directory), no directory set from code, even when a configured
    directory is passed; the hit/miss listener still installs."""
    where = str(tmp_path / "from-env")
    got = _cache_probe(
        str(tmp_path / "configured"), JAX_COMPILATION_CACHE_DIR=where
    )
    assert got["dir"] == where == got["config_dir"]
    assert got["dir_set_in_code"] is False
    assert got["entries"] > 0, "the program did not write its cache there"
    assert got["stats"]["misses"] > 0
    assert not (tmp_path / "configured").exists()


def test_cache_default_is_one_fixed_path_inside_the_checkout():
    """Env unset, nothing configured: <repo>/.jax_cache/xla-<kernel hash>,
    the same from two processes (never a temp name, pid or time)."""
    a, b = _cache_probe(), _cache_probe()
    want = os.path.join(
        REPO, ".jax_cache", "xla-" + ops_engine.kernel_source_hash()[:16]
    )
    assert a["dir"] == b["dir"] == want == a["config_dir"]
    assert a["dir_set_in_code"] is True
    assert ops_engine.DEFAULT_COMPILATION_CACHE_DIR == os.path.join(
        REPO, ".jax_cache"
    )
    # the second process found what the first one compiled
    assert b["stats"]["hits"] > 0 and b["stats"]["misses"] == 0


def test_cache_configured_directory_is_versioned_by_kernel_hash(tmp_path):
    got = _cache_probe(str(tmp_path / "cc"))
    assert got["dir"] == str(
        tmp_path / "cc" / ("xla-" + ops_engine.kernel_source_hash()[:16])
    )
    assert got["entries"] > 0
