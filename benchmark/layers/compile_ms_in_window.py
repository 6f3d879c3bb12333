"""Milliseconds the program spent compiling or loading programs inside the
window (``ops.engine.compilation_log()``: every entry of jax's one compile
entry point with its interval, program, thread and cache verdict).  Has to
read 0; each entry is also an earlier line."""
import json


def read(ctx, log=None):
    if log is None:
        try:
            from dragonboat_tpu.ops.engine import compilation_log as log
        except Exception:
            return None  # a program without the log
    lo, hi = ctx.outcome.t0, ctx.outcome.t_end
    ms = 0.0
    for t0, t1, program, thread, verdict in log():
        if lo <= t0 < hi:
            ms += (t1 - t0) * 1e3
            print(json.dumps({
                "event": "compile_in_window_program", "program": program,
                "thread": thread, "cache": verdict,
                "t_s": round(t0 - lo, 4), "ms": round((t1 - t0) * 1e3, 3)}),
                flush=True)
    return ms
