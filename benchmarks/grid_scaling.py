"""Sharded grid scaling: scenarios/sec vs device count (DESIGN.md §7).

Reruns the fig3 sweep (27 scenarios: 3 densities x 3 packet lengths x 3
protocol rows) through `GridRunner.run(devices=...)` on 1, 2, 4, and 8
devices and reports warm-dispatch throughput per device count, verifying
each sharded result equal (within float32 round-off) to the
single-device reference.

Device counts are forced host (CPU) devices unless XLA_FLAGS is already
set (on a real accelerator, export XLA_FLAGS= and the machine's devices
are used as-is).  On CPU the forced devices share the same cores, so
scenarios/sec measures dispatch/partitioning overhead rather than real
speedup — the accelerator-facing number comes from running this same
script on multi-chip hardware.

Runs standalone (needs its own device count):

  PYTHONPATH=src:. python benchmarks/grid_scaling.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import numpy as np

DEVICE_COUNTS = (1, 2, 4, 8)


def main() -> None:
    from benchmarks import common, fig3_sweep
    from repro.fl import scenarios

    grid = fig3_sweep.build_grid()
    data = common.standard_data()
    init, apply_fn = common.standard_model()
    cfg = common.standard_cfg(n_rounds=fig3_sweep.N_ROUNDS)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)

    ref = runner.run(grid)          # single-device vmap reference
    mismatched = []
    for d in DEVICE_COUNTS:
        if d > jax.device_count():
            print(f"grid_scaling/d{d},0.0,skipped=only_"
                  f"{jax.device_count()}_devices")
            continue
        devs = jax.devices()[:d]
        t0 = time.time()
        res = runner.run(grid, devices=devs)
        t_cold = time.time() - t0
        t0 = time.time()
        runner.run(grid, devices=devs)
        t_warm = time.time() - t0
        # equal_nan: bias is NaN for non-R&A rows.
        matches = all(
            np.allclose(got, want, rtol=1e-5, atol=1e-6, equal_nan=True)
            for got, want in ((res.acc, ref.acc), (res.loss, ref.loss),
                              (res.bias, ref.bias))
        )
        if not matches:
            mismatched.append(d)
        common.emit(
            f"grid_scaling/d{d}", t_warm * 1e6 / len(grid),
            f"devices={d};scenarios={len(grid)};"
            f"scenarios_per_s={len(grid) / max(t_warm, 1e-9):.2f};"
            f"cold_s={t_cold:.2f};warm_s={t_warm:.2f};"
            f"matches_direct={matches}",
        )
    if mismatched:
        raise SystemExit(
            f"grid_scaling: sharded results diverged from the "
            f"single-device reference at device counts {mismatched}"
        )


if __name__ == "__main__":
    main()
