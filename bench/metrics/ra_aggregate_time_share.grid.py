"""ra_aggregate_time_share.grid: the aggregation kernel's summed device
time over the traced window (times the chips used), in percent."""
from bench import trace as tr
from bench.metrics import _shared


def read(ctx):
    if ctx.kind != "grid":
        return None
    events = _shared.kernel_events(ctx)
    if not events:
        return None
    t0, t1 = tr.window(ctx.trace)
    return 100.0 * sum(d for _, _, d in events) / ((t1 - t0) * ctx.chips)
