"""device_idle_share.<cell> (device): the share of the traced slice in
which no operation ran on the device: 1 - (union of device operation
intervals) / (slice length), averaged over the chips."""


def read(ctx):
    red = ctx.reduced
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
