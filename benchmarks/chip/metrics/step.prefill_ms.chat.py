"""step.prefill_ms.chat: device time of the prefill programs in the traced
window over the prefill dispatches (device trace, engine counters)."""
from chipbench.kernels import is_prefill


def read(ctx):
    n = ctx.delta("prefill_dispatches")
    ns = ctx.module_ns(is_prefill)
    return ns * 1e-6 / n if n and ns else None
