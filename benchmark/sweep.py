"""Find a knee once, on the chip: one cluster, one traffic parameter swept.

    python3 benchmark/sweep.py --config upstream48x3 --traffic write_closed \
        --param inflight_per_group --values 1 2 4 8 16 --seconds 8

One line of JSON per value.  A cell's rate or in-flight count is then
written into its traffic file as a number; nothing searches at run time.
For an open loop the knee is the highest rate at which the second half of
the window is no slower than the first and nothing is left in flight beyond
what the rate times the latency explains.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import reduce  # noqa: E402
from benchmark.generator import READ, WRITE, Generator  # noqa: E402
from benchmark.run import CACHE_DIR, load_json, open_device  # noqa: E402


def halves(out, kind, seconds):
    pairs = list(zip(out.start[kind], out.lat[kind]))
    a = [lat for s, lat in pairs if s < seconds / 2]
    b = [lat for s, lat in pairs if s >= seconds / 2]
    return [round(reduce.percentile(x, 50) * 1e3, 2) if x else None
            for x in (a, b)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--param", required=True)
    ap.add_argument("--values", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--keep-going", action="store_true",
                    help="do not stop at the first value with failures")
    args = ap.parse_args()

    open_device(1, args.rehearse_cpu)
    from benchmark.cluster import LiveCluster

    config = load_json(HERE, "configs", args.config + ".json")
    base = load_json(HERE, "traffic", args.traffic + ".json")
    cluster = LiveCluster(config, CACHE_DIR)
    try:
        serials, known = {}, {}
        Generator(cluster, base, args.seed, 0, serials, known).prefill()
        for value in args.values:
            traffic = dict(base)
            traffic[args.param] = type(base[args.param])(value)
            before = [c.health_snapshot() for c in cluster.coords]
            out = Generator(cluster, traffic, args.seed, args.seconds,
                            serials, known).run()
            after = [c.health_snapshot() for c in cluster.coords]
            line = {
                args.param: traffic[args.param],
                "attempted": out.attempted, "failed": out.failed,
                "acks_per_s": round(out.acks_in_window / args.seconds, 1),
                "retries": out.retries,
                "not_completed": len(out.events),
                "leader_changes": len(cluster.leader_changes(out.t0,
                                                             out.t_end)),
                "inflight_at_window_end": out.inflight_at_end,
                **{k: sum(a[k] - b[k] for a, b in zip(after, before))
                   for k in ("fused_dispatches", "read_confirms",
                             "read_fallbacks")},
                "late_p95_ms": round(
                    reduce.percentile(out.late, 95) * 1e3, 2)
                if out.late else None,
            }
            for kind, tag in ((WRITE, "write"), (READ, "read")):
                if out.lat[kind]:
                    line[tag + "_p50_ms"] = round(
                        reduce.percentile(out.lat[kind], 50) * 1e3, 2)
                    line[tag + "_p95_ms"] = round(
                        reduce.percentile(out.lat[kind], 95) * 1e3, 2)
                    line[tag + "_p50_ms_by_half"] = halves(
                        out, kind, args.seconds)
            print(json.dumps(line), flush=True)
            if out.failed and not args.keep_going:
                break  # past the knee the cluster stays wedged for a while
    finally:
        cluster.stop()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
