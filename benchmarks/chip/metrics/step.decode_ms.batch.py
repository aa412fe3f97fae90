"""step.decode_ms.batch: device time of the decode programs in the traced
window over the decode steps they ran (device trace, engine counters)."""
from chipbench.kernels import is_decode


def read(ctx):
    steps = ctx.delta("decode_steps")
    ns = ctx.module_ns(is_decode)
    return ns * 1e-6 / steps if steps and ns else None
