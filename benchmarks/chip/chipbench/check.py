"""How ``correct`` is decided: after the window, a sample of the requests
the engine finished is run through the plain reference, and every served
token's reference logit is compared with the reference's best at that
position.

The sample is drawn from the seed among the requests finished with all
their tokens, and always holds the longest of them. The number compared is
the widest gap, over every sampled position, by which a served token's
logit lies below the reference's best (``max_logit_gap``); its limit is the
cell's. A request that failed, or one due in an open loop that never got
its first token, fails the run too. With the control, the same test judges
the control's gap in the program's place (``control_correct``), which has
to come out false.
"""
from __future__ import annotations

import time

import numpy as np

from . import reference


def pick(recs: list, n: int, seed: int) -> list:
    """The longest request and n - 1 others drawn from the seed."""
    if not recs:
        return []
    recs = sorted(recs, key=lambda r: r.spec.rid)
    longest = max(recs, key=lambda r: len(r.spec.prompt) + len(r.tokens))
    rest = [r for r in recs if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    k = min(n - 1, len(rest))
    return [longest] + [rest[i] for i in sorted(rng.choice(len(rest), k,
                                                           replace=False))]


def outcome(ctx) -> tuple:
    """(attempted, failed, finished requests the sample may come from)."""
    recs = list(ctx.records.values())
    closed = ctx.cell.mix["loop"] == "closed"
    failed = [r for r in recs
              if (r.status is not None and r.status != "ok")
              or (not closed and r.first is None)]
    done = [r for r in recs if r.status == "ok"
            and len(r.tokens) == r.spec.max_new_tokens]
    return len(recs), len(failed), done


def judge(ctx, with_control: bool = False) -> dict:
    attempted, failed, done = outcome(ctx)
    sample = pick(done, int(ctx.cell.mix["sample"]["requests"]), ctx.seed)
    checks = {"failed_requests": {"value": failed, "limit": 0}}
    widest = control = float("inf")
    n_tok = 0
    if sample:
        t = time.perf_counter()
        gaps = reference.seeded_logit_gaps(
            ctx.config, ctx.seed, [(r.spec.prompt, r.tokens) for r in sample],
            ctx.serving["max_len"], with_control=with_control, arch=ctx.arch)
        widest = float(max(g["served"].max() for g in gaps))
        if with_control:
            control = float(max(g["control"].max() for g in gaps))
        n_tok = sum(len(r.tokens) for r in sample)
        ctx.note(f"reference: {len(sample)} requests, {n_tok} served tokens "
                 f"(longest: prompt {len(sample[0].spec.prompt)} + "
                 f"{len(sample[0].tokens)} served), in "
                 f"{time.perf_counter() - t:.3f} s")
    limit = float(ctx.cell.pinned["max_logit_gap"])
    checks["max_logit_gap"] = {"value": widest, "limit": limit}
    verdict = {"correct": bool(sample) and failed == 0 and widest <= limit,
               "attempted": attempted, "failed": failed}
    if with_control:
        # the control in the program's place, through the same test
        checks["control_logit_gap"] = {"value": control, "limit": limit}
        verdict["control_correct"] = bool(sample) and control <= limit
    for c in checks.values():           # JSON has no infinity
        if c["value"] == float("inf"):
            c["value"] = None
    return {**verdict, "checks": checks, "sampled_tokens": n_tok}
