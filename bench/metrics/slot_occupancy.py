"""slot_occupancy (scheduler): the mean share of the batcher's slots
that were live in each decode step of the window, from the program's
``decode_step`` spans (``n_live``)."""


def read(ctx):
    live = [e["args"]["n_live"] for e in ctx.events
            if e["name"] == "decode_step" and ctx.t0 <= e["ts"] < ctx.t_end]
    if not live:
        return None
    return 100.0 * sum(live) / (len(live) * ctx.n_slots)
