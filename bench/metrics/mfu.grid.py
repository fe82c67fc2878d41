"""mfu.grid: model FLOPs per scenario-round (the configuration's counter:
local training, train-loss pass, test evaluation) times scenario-rounds/s
of the traced window, over chips x the chip's bf16 peak, in percent."""
from bench.metrics import _shared


def read(ctx):
    return _shared.mfu(ctx) if ctx.kind == "grid" else None
