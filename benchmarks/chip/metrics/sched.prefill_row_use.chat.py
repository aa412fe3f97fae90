"""sched.prefill_row_use.chat: prompt positions prefilled over the rows the
prefill programs computed (every dispatch runs all slots x one chunk), in
the traced window (engine counters and client records)."""


def read(ctx):
    n = ctx.delta("prefill_dispatches")
    if not n:
        return None
    rows = n * ctx.serving["num_slots"] * ctx.serving["prefill_chunk"]
    return 100.0 * ctx.work_in_trace()["prefill"] / rows
