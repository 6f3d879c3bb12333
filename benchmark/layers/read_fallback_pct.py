"""Share of the heartbeat read echoes that the coordinator tallied on the
scalar side instead of the device, from the window's ``coord_round`` spans
(``read_acks`` against ``read_fallback_<cause>``); the causes go on an
earlier line."""
import json

from benchmark.layers import program_spans as ps

CAUSES = ("slot_overflow", "after_confirm", "purged")


def read(ctx):
    if not ps.spans(ctx, ps.ROUND):
        return None
    by_cause = {c: ps.total(ctx, ps.ROUND, "read_fallback_" + c)
                for c in CAUSES}
    device = ps.total(ctx, ps.ROUND, "read_acks")
    scalar = sum(by_cause.values())
    if not device + scalar:
        return None
    print(json.dumps({"event": "read_echoes", "device": device,
                      "scalar": by_cause}), flush=True)
    return 100.0 * scalar / (device + scalar)
