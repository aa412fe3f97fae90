"""step.vocab_ms.batch: device time, per decode step, of the ops in the
traced decode programs that have the vocabulary as a whole dimension of one
of their shapes: the tables' conversions, the embedding lookup, the head's
logits and the sampling over them (device trace, engine counters). A
loop's op carries the tables in its state, and its time is its body's:
it is left out."""
from chipbench import trace as trace_lib
from chipbench.kernels import is_decode


def read(ctx):
    steps = ctx.delta("decode_steps")
    tr = ctx.traced
    p = tr["planes"][0]
    ops = trace_lib.ops_in(tr["ops"][p], tr["modules"].get(p, []), is_decode)
    hit = [e for e in ops if not trace_lib.is_container(e)
           and trace_lib.has_dim(e, ctx.dims["V"])]
    if not steps or not hit:
        return None
    ns = sum(e.end - e.start for e in hit)
    ctx.note(f"step.vocab_ms.batch: {len(hit)} ops with a {ctx.dims['V']}-"
             f"wide dimension in the traced decode programs, {ns * 1e-9!r} s "
             f"over {steps} steps; by name: "
             f"{trace_lib.top_ops(hit, n=len(hit))}")
    return ns * 1e-6 / steps
