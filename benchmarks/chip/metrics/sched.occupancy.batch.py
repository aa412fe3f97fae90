"""sched.occupancy.batch: share of slots holding a request, averaged over
the engine steps of the traced window (engine counters)."""


def read(ctx):
    steps = ctx.delta("engine_steps")
    return 100.0 * ctx.delta("occupancy_sum") / steps if steps else None
