"""These tests run on the CPU backend: the chip belongs to the benchmark."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def add_configuration(tmp_path):
    """What a later PR does to add a deployment and its cell: a copy of the
    benchmark's data directories gains ``configs/<name>.json`` (an existing
    configuration with ``changes``), the reference beside it, one ``configs``
    and one ``workloads`` entry and the cell's name on the end-to-end lists of
    the cell it is modelled on; nothing that exists is edited.  Returns the
    copy's root and the cell's name."""
    def add(name, like_cell, **changes):
        from benchmark import run as harness

        for sub in ("configs", "traffic", "layers"):
            shutil.copytree(os.path.join(ROOT, "benchmark", sub),
                            tmp_path / "benchmark" / sub)
        bench = harness.load_json(ROOT, "BENCHMARK.json")
        like = next(w for w in bench["workloads"] if w["name"] == like_cell)
        conf = tmp_path / "benchmark" / "configs"
        old = harness.load_json(conf, like["config"] + ".json")
        (conf / f"{name}.json").write_text(
            json.dumps(dict(old, name=name, **changes)))
        shutil.copy(conf / f"{like['config']}_reference.py",
                    conf / f"{name}_reference.py")
        cell = f"{name}.{like['traffic']}"
        bench["configs"].append({
            "name": name, "source": "a test", "reduced": [],
            "file": f"benchmark/configs/{name}.json", "why": "a test"})
        bench["workloads"].append(dict(like, name=cell, config=name))
        for m in bench["end_to_end"]:
            if like_cell in m.get("workloads", ()):
                m["workloads"].append(cell)
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        return str(tmp_path), cell
    return add
