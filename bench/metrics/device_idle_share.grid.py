"""device_idle_share.grid: share of the traced window in which no op ran
on the device, averaged over the cell's chips, in percent."""
from bench.metrics import _shared


def read(ctx):
    return _shared.idle_share(ctx) if ctx.kind == "grid" else None
