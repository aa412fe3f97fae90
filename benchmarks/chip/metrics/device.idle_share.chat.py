"""device.idle_share: share of the traced window in which no operation ran
on the device (device trace)."""


def read(ctx):
    t = ctx.traced
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
