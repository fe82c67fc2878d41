"""ra_aggregate_roofline.grid: the least time the chip could take for the
kernel calls of the traced window (per call the larger of FLOPs over the
bf16 peak and bytes over HBM bandwidth, from the algorithm's shapes) over
the kernel's summed device time, in percent.  Nothing is read when the
trace's kernel events do not match the calls the window made."""
from bench.metrics import _shared


def read(ctx):
    peak, bw = ctx.peaks.get("flops_bf16"), ctx.peaks.get("hbm_bytes_per_s")
    if ctx.kind != "grid" or not peak or not bw or not ctx.kernel_calls:
        return None
    events = _shared.kernel_events(ctx)
    if len(events) != len(ctx.kernel_calls) * ctx.chips:
        return None
    least = 0.0
    for call in ctx.kernel_calls:
        flops, nbytes = _shared.kernel_work(call)
        least += max(flops / peak, nbytes / bw)
    spent = sum(d for _, _, d in events) * 1e-9
    return 100.0 * least / spent
