"""queue_wait_p90_ms (scheduler): 90th percentile, over the window's
requests, of the time from a request's due time to its admission (the
program's ``admit`` span event, which ``ContinuousBatcher`` stamps when
it gives the request a slot and pages)."""

import numpy as np


def read(ctx):
    due = {r.rid: r.due for r in ctx.records}
    waits = [e["ts"] - due[e["rid"]] for e in ctx.events
             if e["name"] == "admit" and e["rid"] in due]
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e3
