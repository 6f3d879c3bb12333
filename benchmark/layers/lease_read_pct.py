"""Share of the window's ``read_ctx`` spans that the leader answered under
its lease (``path: lease``: no heartbeat, no echo, no device slot, no round)
against those that took the ReadIndex plane because the lease was not valid
(``lease_fallback``) or for any other reason; the paths of the rest go on an
earlier line.  ``None`` where the program writes no such span (a parent
without the leased read's span leaves only the reads that fell back)."""
import json

from benchmark.layers import read_legs as rl

PATH = "lease"


def read(ctx):
    spans = rl.select(ctx)["spans"]
    if not any(s.get("path") == PATH or s.get("lease_fallback")
               for s in spans):
        return None  # no lease group, or a program that does not say
    rest = {}
    for s in spans:
        if s.get("path") != PATH:
            key = s.get("path") or "?"
            if s.get("lease_fallback"):
                key += ":lease_fallback"
            rest[key] = rest.get(key, 0) + 1
    leased = len(spans) - sum(rest.values())
    print(json.dumps({"event": "lease_reads", "leased": leased,
                      "not_leased": rest}), flush=True)
    return 100.0 * leased / len(spans)
