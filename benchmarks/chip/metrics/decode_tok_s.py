"""decode_tok_s: every token delivered to the client inside the window,
from finished requests or not, over the window's seconds (host clock)."""


def read(ctx):
    lo, hi = ctx.window
    n = sum(k for r in ctx.records.values() for t, k in r.stamps
            if lo < t <= hi)
    ctx.note(f"decode_tok_s: {n} tokens in {hi - lo!r} s")
    return n / (hi - lo)
