"""Arithmetic shared by the per-layer readers (not a metric of its own)."""
from bench import trace as tr

KERNEL = r"^(_ra_call|ra_aggregate)"    # the kernel's op name in the trace


def device_planes(ctx):
    if ctx.trace is None:
        return []
    return sorted(ctx.trace["devices"])[:ctx.chips]


def idle_share(ctx):
    """100 x (1 - busy / window), averaged over the chips used."""
    planes = device_planes(ctx)
    if not planes:
        return None
    t0, t1 = tr.window(ctx.trace)
    busy = [tr.busy_ns(ctx.trace["devices"][p], t0, t1) for p in planes]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (t1 - t0))


def mfu(ctx):
    """Model FLOP/s of the completed scenario-rounds over chips x the
    chip's bf16 peak, in percent."""
    peak = ctx.peaks.get("flops_bf16")
    if not peak or ctx.window_s <= 0 or not ctx.scenario_rounds:
        return None
    rate = ctx.scenario_rounds * ctx.flops_per_scenario_round / ctx.window_s
    return 100.0 * rate / (ctx.chips * peak)


def kernel_events(ctx):
    """The kernel's events in the window, on every chip used."""
    planes = device_planes(ctx)
    t0, t1 = tr.window(ctx.trace) if planes else (0.0, 0.0)
    return [ev for p in planes
            for ev in tr.matching(ctx.trace["devices"][p], KERNEL, t0, t1)]


def kernel_work(call):
    """FLOPs and HBM bytes the algorithm needs for one ra_aggregate call
    (B, N, L, K, mode): 2 B N^2 L K FLOPs; w read once, out written once,
    the receiver's own segments read again under substitution, the int8
    success mask and the float32 weights read once."""
    b, n, l, k, mode = call
    flops = 2 * b * n * n * l * k
    seg = b * n * l * k * 4
    nbytes = 2 * seg + (seg if mode == "substitution" else 0) \
        + b * n * n * l + b * n * 4
    return flops, nbytes
