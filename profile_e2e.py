"""Sampling profiler for the e2e NodeHost hot path (VERDICT r2 item 2).

cProfile is per-thread and the runtime's work happens on step/apply/sender
worker threads, so this uses a wall-clock sampler over
``sys._current_frames()``: every ``interval`` seconds it records the
innermost N frames of every live thread and aggregates inclusive sample
counts per function.  GIL-serialized Python work shows up in proportion to
the time it holds the interpreter, which is exactly the budget we are
spending (reference perf bar: BASELINE.md).

Run:  python profile_e2e.py [groups] [duration_s]
Emits a sorted report to stdout and PROFILE_e2e.txt.
"""
from __future__ import annotations

import collections
import os
import sys
import threading
import time


class Sampler:
    def __init__(self, interval: float = 0.002, depth: int = 40):
        self.interval = interval
        self.depth = depth
        self.inclusive = collections.Counter()  # func -> samples anywhere on stack
        self.leaf = collections.Counter()  # func -> samples as innermost frame
        self.samples = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._main, daemon=True)
        self._me = threading.get_ident()

    def _main(self) -> None:
        while not self._stop.is_set():
            frames = sys._current_frames()
            self.samples += 1
            for tid, frame in frames.items():
                if tid == self._me:
                    continue
                seen = set()
                f = frame
                depth = 0
                is_leaf = True
                while f is not None and depth < self.depth:
                    code = f.f_code
                    key = f"{code.co_filename.split('/')[-1]}:{code.co_firstlineno}:{code.co_name}"
                    if is_leaf:
                        self.leaf[key] += 1
                        is_leaf = False
                    if key not in seen:
                        self.inclusive[key] += 1
                        seen.add(key)
                    f = f.f_back
                    depth += 1
            time.sleep(self.interval)

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=2)

    def report(self, top: int = 40) -> str:
        lines = [f"samples: {self.samples} (interval {self.interval*1e3:.1f}ms)"]
        lines.append("\n== leaf (time spent IN the function) ==")
        for k, v in self.leaf.most_common(top):
            lines.append(f"{v:7d}  {k}")
        lines.append("\n== inclusive (function anywhere on stack) ==")
        for k, v in self.inclusive.most_common(top):
            lines.append(f"{v:7d}  {k}")
        return "\n".join(lines)


def main() -> None:
    groups = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    duration = float(sys.argv[2]) if len(sys.argv) > 2 else 8.0
    os.environ.setdefault("E2E_GROUPS", str(groups))
    os.environ.setdefault("E2E_DURATION", str(duration))
    os.environ.setdefault("E2E_ENGINE", "scalar")
    # the sampler only sees THIS process — force the single-process bench
    # (for multiprocess profiles use E2E_PROFILE_DIR, sampled per rank)
    os.environ.setdefault("E2E_PROCS", "1")
    import bench_e2e

    bench_e2e._decide_platform()
    s = Sampler()
    s.start()
    res = bench_e2e.run_quick()
    s.stop()
    rep = s.report()
    rep += (
        f"\n\nwrites_per_sec={res['writes_per_sec']}"
        f" commit_latency_ms={res['commit_latency_ms']}"
    )
    print(rep)
    with open("PROFILE_e2e.txt", "w") as f:
        f.write(rep + "\n")


if __name__ == "__main__":
    main()
