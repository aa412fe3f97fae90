"""device.hbm_gb.batch: device memory in use after the window, in GB (the
device's memory_stats; the peak is set-up's)."""


def read(ctx):
    b = ctx.memory.get("bytes_in_use")
    return b / 1e9 if b else None
