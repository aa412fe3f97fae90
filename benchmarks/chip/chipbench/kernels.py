"""A Pallas kernel's share of its roofline in the decode program, read from
the trace: the least time its calls need (``flops.decode_step_calls``, at
the chip's peaks) over the device time the trace gives them."""
from __future__ import annotations

from . import flops, trace as trace_lib

# the peak each kernel's operations are held to
RATE = {"qmatmul_w8a8": "int8", "qmatmul_w8a16": "bf16",
        "quantize_act": "bf16", "fused_decode": "bf16"}


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
    calls = flops.decode_step_calls(
        ctx.dims, ctx.serving["num_slots"], ctx.serving["max_len"],
        ctx.serving["recipe"]).get(kernel)
    if not evs or not calls or ctx.peaks is None:
        return None
    steps = len(evs) / len(calls)
    bounds = [flops.least_time(o, b, ctx.peaks, RATE[kernel])
              for o, b in calls]
    least = steps * sum(t for t, _ in bounds)
    took = sum(e.end - e.start for e in evs) * 1e-9
    kinds = sorted({b for _, b in bounds})
    ctx.note(f"{kernel}: {len(evs)} calls in the traced decode programs "
             f"({steps:.2f} steps of {len(calls)}), {took!r} s on the "
             f"device, least {least!r} s, bound by {'/'.join(kinds)}")
    return 100.0 * least / took
