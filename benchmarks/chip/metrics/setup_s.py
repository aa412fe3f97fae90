"""setup_s: seconds from the start of run.py to the first request of the
window: weights, quantization, engine build and warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
