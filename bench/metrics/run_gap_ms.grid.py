"""run_gap_ms.grid: mean device-idle time between the last op of one
`GridRunner.run` and the first op of the next, from the device trace and
the benchmark's own ``bench.run`` host spans; averaged over the chips."""
from bench import trace as tr
from bench.metrics import _shared


def read(ctx):
    planes = _shared.device_planes(ctx)
    if ctx.kind != "grid" or not planes:
        return None
    runs = tr.spans(ctx.trace["host"], tr.HOST_PREFIX + "run")
    gaps = []
    for p in planes:
        ops = tr.merge((s, s + d) for _, s, d in ctx.trace["devices"][p])
        for (prev_start, _), (start, _) in zip(runs, runs[1:]):
            before = [e for s, e in ops if prev_start <= s < start]
            after = [s for s, _ in ops if s >= start]
            if before and after:
                gaps.append(max(0.0, min(after) - max(before)))
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
