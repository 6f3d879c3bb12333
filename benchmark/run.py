"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``: ``configs/<config>.json`` with ``<config>_reference.py``
beside it, ``traffic/<traffic>.json``, ``layers/<metric family>.py`` (the
part of a per-layer metric's name before the first dot).  Adding a cell or a
metric adds files and entries and edits nothing here.

Set-up (counted in ``setup_s``): three NodeHosts on the one chip, leaders
placed, the device programs warm, the prefill (an acknowledged write per key
the reads will ask for, and the first use of what the engine runs once reads
have been seen), then ``warmup_s`` of the cell's own traffic that runs on
into the window without a pause.  Then the window; then every operation is drained to its outcome,
the device's peak memory is read, and the replicas are held against the
plain reference (``check.py``).  The last line of standard output is the
result; every non-``COMPLETED`` attempt and every leader change is on an
earlier line.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import check, reduce, roofline  # noqa: E402
from benchmark.generator import READ, WRITE, Generator  # noqa: E402

#: the persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
PROFILE_DIR = os.path.join(ROOT, ".bench_profile")
#: the traced run: one request in this many carries stage stamps, and the
#: profiler runs over this long a stretch at the window's end.  It is
#: stopped only once the window has closed: stopping it holds the
#: interpreter for longer than an election timeout, and that stall has to
#: fall into the drain, not into what is measured.
TRACE_SAMPLE_EVERY = 8
PROFILE_SECONDS = 6.0
MAX_EVENT_LINES = 1000


def process_age_s() -> float:
    """Seconds since this process was started (its set-up so far)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with the files its names point at."""

    def __init__(self, workload: str, root: str = ROOT):
        self.bench = load_json(root, "BENCHMARK.json")
        self.dir = os.path.join(root, self.bench["paths"][0])
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        conf = next(c for c in self.bench["configs"]
                    if c["name"] == self.entry["config"])
        self.config = load_json(root, conf["file"])
        self.reference = load_module(
            os.path.join(root, conf["file"][: -len(".json")] + "_reference.py"),
            f"bench_reference_{conf['name']}",
        )
        self.traffic = load_json(self.dir, "traffic",
                                 self.entry["traffic"] + ".json")

    def metrics(self, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports: a
        metric that lists ``workloads`` where it is listed, an end-to-end
        metric without the key everywhere, a per-layer metric without it
        wherever the end-to-end metric it moves is reported."""
        e2e = {m["name"] for m in self.bench["end_to_end"]
               if self.name in m.get("workloads", [self.name])}
        return [m for m in self.bench[kind]
                if (self.name in m["workloads"] if "workloads" in m
                    else kind == "end_to_end" or m["moves"] in e2e)]

    def readers(self) -> list:
        out = []
        for m in self.metrics("per_layer"):
            family = m["name"].split(".", 1)[0]
            path = os.path.join(self.dir, "layers", family + ".py")
            out.append((m, load_module(path, f"bench_layer_{family}")))
        return out


class Ctx:
    """What a per-layer reader may read."""

    WRITE, READ = WRITE, READ
    percentile = staticmethod(reduce.percentile)
    roofline = roofline

    def __init__(self, **kw):
        self.__dict__.update(kw)


def instrument_dispatch(cluster) -> None:
    """The benchmark's own spans around the calls into the engine, on the
    profiler's clock (spans inside the program are a later PR's)."""
    import jax

    depth = threading.local()

    def wrap(fn):
        def spanned(*a, **k):
            if getattr(depth, "n", 0):
                return fn(*a, **k)  # ``step`` may run ``step_rounds``
            depth.n = 1
            try:
                with jax.profiler.TraceAnnotation(reduce.DISPATCH_SPAN):
                    return fn(*a, **k)
            finally:
                depth.n = 0
        return spanned

    for c in cluster.coords:
        c.eng.step = wrap(c.eng.step)
        c.eng.step_rounds = wrap(c.eng.step_rounds)


def start_profile() -> None:
    import jax

    shutil.rmtree(PROFILE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host TraceMe events, no bytecode
    jax.profiler.start_trace(PROFILE_DIR, profiler_options=opts)


def prime_profiler() -> None:
    """The profiler's first start loads and initialises it: pay that before
    a cluster with election timers exists."""
    import jax

    start_profile()
    jax.profiler.stop_trace()


class CompileLines:
    """While the window is open, every program jax compiles or loads from
    the persistent cache becomes an earlier line, so a ``compiles_in_window``
    that is not 0 says what was not warm.  Wraps jax's one compile entry
    point (which the engine wraps too, and pxla resolves at call time)."""

    def __init__(self):
        self.on = False
        try:
            from jax._src import compiler

            inner = compiler.compile_or_get_cached

            def named(backend, computation, *a, **k):
                if self.on:
                    try:
                        name = str(computation.operation.attributes[
                            "sym_name"]).strip('"')
                    except Exception:
                        name = "?"
                    print(json.dumps({
                        "event": "compile_in_window", "program": name,
                        "thread": threading.current_thread().name}), flush=True)
                return inner(backend, computation, *a, **k)

            compiler.compile_or_get_cached = named
        except Exception as e:  # the count is still taken
            print(json.dumps({"event": "compiles_not_named", "why": repr(e)}))


class Watcher:
    """Reads the compile counters at the window's edges, has jax name what
    it compiles in between and, in a traced run, runs the profiler over
    the last seconds of the window."""

    def __init__(self, trace: bool, seconds: float):
        self.trace = trace
        self.profile_s = min(PROFILE_SECONDS, seconds / 2)
        self.compiles = [0, 0]
        self.profiled = None  # (perf_counter start, end) of the profile
        self.thread = None

    @staticmethod
    def _compiles() -> int:
        from dragonboat_tpu.ops.engine import compilation_cache_stats

        cc = compilation_cache_stats()
        return cc["hits"] + cc["misses"]

    def start(self, t0: float, t_end: float) -> None:
        self.thread = threading.Thread(
            target=self._main, args=(t0, t_end), name="bench-watcher"
        )
        self.thread.start()

    def _sleep_until(self, t: float) -> None:
        while True:
            left = t - time.perf_counter()
            if left <= 0:
                return
            time.sleep(min(left, 0.25))

    def _main(self, t0: float, t_end: float) -> None:
        import jax

        named = CompileLines()
        self._sleep_until(t0)
        self.compiles[0] = self._compiles()
        named.on = True
        if self.trace:
            self._sleep_until(t_end - self.profile_s)
            start_profile()
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation(reduce.WINDOW_MARK):
                self._sleep_until(t_end)
            b = time.perf_counter()
        self._sleep_until(t_end)
        self.compiles[1] = self._compiles()
        named.on = False
        if self.trace:
            jax.profiler.stop_trace()  # in the drain: see PROFILE_SECONDS
            self.profiled = (a, b)

    def join(self) -> None:
        self.thread.join()


def end_to_end(name: str, out, seconds: float, setup_s: float):
    if name == "setup_s":
        return setup_s
    if name == "ops_per_s":
        return out.acks_in_window / seconds
    # ``write_p50_ms``, ``read_p50_ms``, ...: a percentile over all the
    # window's operations of one kind
    m = re.fullmatch(r"(write|read)_p(\d+)_ms", name)
    if m:
        kind = WRITE if m.group(1) == "write" else READ
        return reduce.percentile(out.lat[kind], int(m.group(2))) * 1e3
    raise KeyError(f"no end-to-end metric {name!r}")


def open_device(chips: int, rehearse_cpu: bool):
    """The one platform decision: the chip, or the explicit CPU rehearsal.
    Returns the result's ``device`` object and the jax device (for its peak
    memory).  Without the chips, and not rehearsing, raises RuntimeError."""
    from dragonboat_tpu import hostplatform

    if rehearse_cpu:
        hostplatform.force_cpu()
    else:
        hostplatform.require_tpu(chips)
    import jax

    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices())}, d0


def print_events(cell: Cell, cluster, out, seed: int, seconds: float,
                 leader_changes: list) -> None:
    """The earlier lines: every attempt that did not complete, every leader
    change, and one summary of the window."""
    codes = {}
    for ev in out.events:
        codes[ev[1]] = codes.get(ev[1], 0) + 1
    for ev in out.events[:MAX_EVENT_LINES]:
        print(json.dumps(dict(zip(
            ("event", "t_s", "code", "op", "group", "host", "attempt"),
            ("attempt_not_completed",) + tuple(ev)))))
    for t, cid, term, leader in leader_changes[:MAX_EVENT_LINES]:
        print(json.dumps({"event": "leader_change", "t_s": round(t - out.t0, 4),
                          "group": cid, "term": term, "leader": leader}))
    for what, n in (("attempt_not_completed", len(out.events)),
                    ("leader_change", len(leader_changes))):
        if n > MAX_EVENT_LINES:
            print(json.dumps({"event": "lines_cut", "of": what, "printed":
                              MAX_EVENT_LINES, "total": n}))
    print(json.dumps({
        "summary": cell.name, "seed": seed, "seconds": seconds,
        "attempted": out.attempted, "failed": out.failed,
        "acks_in_window": out.acks_in_window,
        "not_completed_attempts": codes, "retries": out.retries,
        "leader_changes": len(leader_changes),
        "inflight_at_window_end": out.inflight_at_end,
        "drain_s": round(time.perf_counter() - out.t_end, 3),
        "writes": len(out.lat[WRITE]), "reads": len(out.lat[READ]),
        "p50_p95_ms": {
            tag: [round(reduce.percentile(out.lat[kind], q) * 1e3, 3)
                  for q in (50, 95)]
            for kind, tag in ((WRITE, "write"), (READ, "read"))
            if out.lat[kind]},
        "set_up": {k: round(v, 3) for k, v in cluster.phases.items()},
    }), flush=True)


def per_layer(cell: Cell, cluster, out, watcher: Watcher, seconds: float,
              leader_changes: list, device_kind: str, rehearsal: bool):
    """The traced run's metrics: ({name: {value, unit}}, reduced trace)."""
    readers = cell.readers()
    kernels = {k for _m, mod in readers for k in getattr(mod, "KERNELS", ())}
    reduced, acks_in_trace = None, 0
    if watcher.profiled is not None:
        reduced = reduce.reduce_trace(reduce.load_xplane(PROFILE_DIR), kernels)
        a, b = watcher.profiled
        acks_in_trace = sum(1 for t in out.ack_at if a <= t < b)
    ctx = Ctx(outcome=out, traffic=cell.traffic, seconds=seconds,
              leader_changes=leader_changes, trace=reduced,
              acks_in_trace=acks_in_trace,
              stages=reduce.stage_p50_ms(cluster.sampled_traces()),
              compiles_in_window=watcher.compiles[1] - watcher.compiles[0],
              state_leaves=cluster.state_leaves(), device_kind=device_kind)
    metrics = {}
    for m, mod in readers:
        if rehearsal and m["source"] == "device_trace":
            continue  # never a CPU number under a device metric's name
        value = mod.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, reduced


def run(cell: Cell, cluster, seed: int, seconds: float, trace: bool,
        device: dict, rehearsal: bool, jax_device=None,
        setup_clock=process_age_s) -> dict:
    """Drive one window on a cluster that is up and has served nothing yet;
    returns the result object.  ``cluster`` is the program or anything with
    its surface (tests and the controls put the plain reference and broken
    programs in its place)."""
    serials, known = {}, {}
    prefill = Generator(cluster, cell.traffic, seed, 0, serials,
                        known).prefill()
    gen = Generator(cluster, cell.traffic, seed, seconds, serials, known)
    watcher = Watcher(trace, seconds)
    setup = {}

    def on_window(t0, t_end):
        setup["s"] = setup_clock() + (t0 - time.perf_counter())
        watcher.start(t0, t_end)

    out = gen.run(on_window)
    watcher.join()
    peak = 0
    if jax_device is not None:
        peak = (jax_device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    leader_changes = cluster.leader_changes(out.t0, out.t_end)
    print_events(cell, cluster, out, seed, seconds, leader_changes)

    # the comparison, once the window has closed and the peak is read
    t_check = time.perf_counter()
    compared = check.compare(cluster, [prefill, out], cell.reference,
                             cell.reference.LIMITS)
    print(json.dumps({"check_s": round(time.perf_counter() - t_check, 3)}),
          flush=True)

    dev = dict(device, memory_peak_bytes=int(peak))
    result = {"correct": check.is_correct(compared),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": {}, "device": dev}
    if rehearsal:
        result["rehearsal"] = True
    if not trace:
        for m in cell.metrics("end_to_end"):
            value = end_to_end(m["name"], out, seconds, setup["s"])
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        result["metrics"], reduced = per_layer(
            cell, cluster, out, watcher, seconds, leader_changes, dev["kind"],
            rehearsal)
        if reduced is not None and reduced["busy_s"] is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"][:10],
                "idle_gaps": reduced["idle_gaps"][:10],
            }
    result["compared"] = compared  # each number beside its limit, last
    return result


def print_compared(result: dict) -> None:
    """Each number compared beside its limit, as standard error's last
    lines."""
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU backend; the result says so and "
                         "carries no device metric")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    try:
        device, d0 = open_device(cell.entry["chips"], args.rehearse_cpu)
    except RuntimeError as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    from benchmark.cluster import LiveCluster

    if args.trace:
        prime_profiler()
    cluster = LiveCluster(
        cell.config, CACHE_DIR,
        trace_sample_every=TRACE_SAMPLE_EVERY if args.trace else 0,
    )
    try:
        if cluster.state_platforms() != {d0.platform}:
            raise RuntimeError(
                f"engine state not on {d0.platform}: {cluster.state_platforms()}")
        if args.trace:
            instrument_dispatch(cluster)
        result = run(cell, cluster, args.seed, args.seconds, bool(args.trace),
                     device, args.rehearse_cpu, jax_device=d0)
    finally:
        cluster.stop()
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
    print_compared(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # jaxlib can crash in destructor order at interpreter teardown after the
    # persistent cache was read; everything this run owns is stopped by now
    os._exit(code)
