"""scenario_rounds_per_s: scenario-rounds completed in the window over the
time from window start to the completion of its last dispatch.  Host clock
around whole `GridRunner.run` calls, which return host arrays."""


def read(ctx):
    if ctx.kind != "grid" or ctx.window_s <= 0:
        return None
    return ctx.scenario_rounds / ctx.window_s
