"""A Pallas kernel's share of its roofline in the decode program, read from
the trace: the least time its calls need (the decode step's calls, at the
peak each kernel's operations are held to; ``flops.decode_step_calls`` or
the architecture file's own) over the device time the trace gives them."""
from __future__ import annotations

from . import flops, trace as trace_lib

# the kernels of the default decode plan and their peaks, by which
# ``tests/test_tpu_compile.py`` names the kernels it looks for
RATE = flops.RATE


def is_decode(module_name: str) -> bool:
    return "decode" in module_name


def is_prefill(module_name: str) -> bool:
    return "prefill" in module_name


def matches(event, kernel: str) -> bool:
    """Whether a device op is a call of ``kernel``: the op's own name is the
    Pallas call's, ``<kernel>_pallas.<n>``."""
    return trace_lib.op_name(event).startswith(kernel + "_pallas")


def roofline(ctx, kernel: str):
    tr = ctx.traced
    p = tr["planes"][0]
    ops = trace_lib.ops_in(tr["ops"][p], tr["modules"].get(p, []), is_decode)
    evs = [e for e in ops if matches(e, kernel)]
    plan = ctx.decode_step_calls().get(kernel)
    if not evs or not plan or ctx.peaks is None:
        return None
    calls = plan["calls"]
    steps = len(evs) / len(calls)
    bounds = [flops.least_time(o, b, ctx.peaks, plan["rate"])
              for o, b in calls]
    least = steps * sum(t for t, _ in bounds)
    took = sum(e.end - e.start for e in evs) * 1e-9
    kinds = sorted({b for _, b in bounds})
    ctx.note(f"{kernel}: {len(evs)} calls in the traced decode programs "
             f"({steps:.2f} steps of {len(calls)}), {took!r} s on the "
             f"device, least {least!r} s, bound by {'/'.join(kinds)}")
    return 100.0 * least / took
