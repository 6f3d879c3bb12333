"""The controls: runs that the comparison has to fail, and sound runs on
many seeds in one process.

    python3 benchmark/control.py --workload <cell> --control <name> \
        --seeds 1 2 3 --seconds 8

``--control`` names what stands in the program's place:

* ``none``: the program itself (sound; ``correct`` has to be true).
* ``reference``: the plain reference, sound (the harness's self-check).
* ``reference:ack_before_quorum`` / ``reference:stale_read``: the plain
  reference with one stated guarantee given up.
* ``program:stale_read``: the program with its own weaker read path
  switched on: reads skip ReadIndex and are served by the local state
  machine of the host they were sent to (``NodeHost.stale_read``), the step
  that would tempt a later PR.  Breaks "reads are linearizable".
* ``program:dropped_apply``: the program with the timed path broken
  underneath: one replica's state machine skips one update in every 8.
  Breaks "every replica applies the same log".

Every seed gets a cluster of its own in the one process (the chip's start
and the imports are paid once), so each seed's verdict stands alone.  One
JSON line per seed; the benchmark's own runs never come here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import run as harness  # noqa: E402
from benchmark.cluster import KV, LiveCluster  # noqa: E402
from benchmark.reference.kv import Done  # noqa: E402

CONTROLS = ("none", "reference", "reference:ack_before_quorum",
            "reference:stale_read", "program:stale_read",
            "program:dropped_apply")


class StaleReadCluster(LiveCluster):
    """Reads without ReadIndex, from the host they were sent to."""

    def submit_read(self, host, cid, timeout_s):
        return Done()

    def lookup(self, host, cid, key):
        return self.nhs[host].stale_read(cid, key)


class DroppingKV(KV):
    """The last replica loses one update in every 8."""

    def __init__(self, cluster_id, node_id):
        super().__init__(cluster_id, node_id)
        self.node_id = node_id

    def update(self, cmd):
        result = super().update(cmd)
        if self.node_id == 3 and self.updates % 8 == 0:
            del self.kv[bytes(cmd[:self.key_bytes])]
        return result


def build(control: str, cell, seed: int):
    if control.startswith("reference"):
        broken = control.partition(":")[2] or None
        return cell.reference.cluster(cell.config, seed, broken)
    kind = {"none": LiveCluster, "program:stale_read": StaleReadCluster,
            "program:dropped_apply": LiveCluster}[control]
    sm = DroppingKV if control == "program:dropped_apply" else KV
    return kind(cell.config, harness.CACHE_DIR, sm_class=sm)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", choices=CONTROLS, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)

    device, d0 = harness.open_device(cell.entry["chips"], args.rehearse_cpu)
    for seed in args.seeds:
        cluster = build(args.control, cell, seed)
        try:
            result = harness.run(cell, cluster, seed, args.seconds, False,
                                 device, args.rehearse_cpu, d0)
        finally:
            cluster.stop()
        print(json.dumps({
            "control": args.control, "workload": cell.name, "seed": seed,
            "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "compared": {k: v["value"]
                         for k, v in result["compared"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    os._exit(code)
