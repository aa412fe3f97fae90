"""ttft_p95_ms: 95th percentile, over every request due in the window, of
the time from its scheduled arrival to its first token (host clock). A
request that never got one counts with the whole wait, a miss of any
limit."""
from chipbench.client import DRAIN_S
from chipbench.context import percentile


def read(ctx):
    end = ctx.window[1] + DRAIN_S
    v = [((r.first if r.first is not None else end) - r.due) * 1e3
         for r in ctx.records.values()]
    if not v:
        return None
    limit = ctx.cell.pinned.get("ttft_limit_ms")
    met = "" if limit is None else f", {sum(x <= limit for x in v)} within"
    ctx.note(f"ttft_ms: {len(v)} requests, median {percentile(v, 50)!r}, "
             f"p95 {percentile(v, 95)!r}, limit {limit}{met}")
    return percentile(v, 95)
