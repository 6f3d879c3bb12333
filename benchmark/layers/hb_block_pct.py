"""Share of the heartbeats and heartbeat responses that went by the block
(one message a peer host a tick) instead of the per-group message, from the
window's ``coord_round`` spans (``hb_block_rows`` against ``hb_single``);
what took the per-group message goes on an earlier line by cause.  ``None``
where the program has no such plane or no heartbeat fell in the window."""
import json

from benchmark.layers import program_spans as ps

CAUSE = "hb_single_"


def read(ctx):
    rounds = [s for s in ps.spans(ctx, ps.ROUND)
              if s.get("hb_block_rows") is not None]
    block = sum(s["hb_block_rows"] for s in rounds)
    single = sum(s.get("hb_single") or 0 for s in rounds)
    if not block + single:
        return None
    causes = {}
    for s in rounds:
        for k, n in s.items():
            if k.startswith(CAUSE):
                causes[k[len(CAUSE):]] = causes.get(k[len(CAUSE):], 0) + n
    print(json.dumps({"event": "heartbeats", "block": block,
                      "single": causes}), flush=True)
    return 100.0 * block / (block + single)
