"""tpot_p95_ms: 95th percentile, over every request that got two tokens or
more by the window's close, finished or not, of (last token - first token)
/ (tokens - 1), counting the tokens it got by the close (host clock)."""
from chipbench.client import tpot_s
from chipbench.context import percentile


def read(ctx):
    close = ctx.window[1]
    v = [t * 1e3 for t in (tpot_s(r, close) for r in ctx.records.values())
         if t is not None]
    if not v:
        return None
    limit = ctx.cell.pinned.get("tpot_limit_ms")
    met = "" if limit is None else f", {sum(x <= limit for x in v)} within"
    ctx.note(f"tpot_ms: {len(v)} requests, median {percentile(v, 50)!r}, "
             f"p95 {percentile(v, 95)!r}, limit {limit}{met}")
    return percentile(v, 95)
