"""Figs. 3-7: converged accuracy vs edge density x packet length.

(The paper runs 5 task/model pairs; structure is identical — we sweep the
CPU-scale task and record the same protocol ordering.)

The full 27-point sweep (3 densities x 3 packet lengths x 3 protocol rows)
runs as ONE `scenarios.run_grid` call: one jit compilation for the whole
grid (the three equal-sized protocol groups share the compiled program) and
one batched dispatch per protocol row.  The timing printout compares:

  * batched        — run_grid (compile once, 3 grouped dispatches),
  * per-scenario   — the same compiled scalar program dispatched 27 times,
  * legacy retrace — the seed-code behavior (static protocol/mode config:
                     every sweep point re-traced + re-compiled), measured
                     on a subset and extrapolated.

The round-loop compute diet (DESIGN.md §9) is measured on the same grid:
``eval_every`` thins the per-round test-set evaluation inside the scan and
``track_bias=False`` drops the ||Lambda||^2 diagnostic — the warm before /
after wall-clock lands in ``BENCH_grid.json`` (`common.write_bench`), the
repo's grid-dispatch perf baseline.

`REPRO_GRID_DEVICES=k` shards the batched dispatch over k devices;
benchmarks/grid_scaling.py sweeps this grid over device counts.
"""
import dataclasses
import time

from benchmarks import common
from repro.fl import scenarios


DENSITIES = (0.35, 0.5, 0.8)
PKT_BITS = (25_000, 100_000, 400_000)
PROTOCOLS = (("ra", "ra_normalized"), ("ra", "substitution"),
             ("aayg", "ra_normalized"))
N_ROUNDS = 12


def build_grid() -> scenarios.ScenarioGrid:
    networks = [
        (f"rho{density}/K{pkt // 1000}k",
         common.standard_net(packet_len_bits=pkt,
                             tx_power_dbm=common.HARSH_TX_DBM,
                             edge_density=density))
        for density in DENSITIES
        for pkt in PKT_BITS
    ]
    return scenarios.ScenarioGrid.product(networks=networks,
                                          protocols=PROTOCOLS)


def main() -> None:
    grid = build_grid()
    data = common.standard_data()
    init, apply_fn = common.standard_model()
    cfg = common.standard_cfg(n_rounds=N_ROUNDS)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg,
                                  devices=common.grid_devices())

    t0 = time.time()
    res = runner.run(grid)                      # single run_grid call
    t_batched = time.time() - t0

    per_scenario_us = t_batched * 1e6 / len(grid)
    for label, one in res.items():
        common.emit(f"fig3/{label}", per_scenario_us,
                    f"final_acc={one.mean_acc[-1]:.3f}")

    # Warm re-dispatch: the runner's compiled programs serve new grids free.
    t0 = time.time()
    runner.run(grid)
    t_warm = time.time() - t0

    # Baseline 1: per-scenario dispatch, each row as a one-point grid.
    t0 = time.time()
    for i in range(len(grid)):
        runner.run(grid.take([i]))
    t_seq = time.time() - t0

    # Baseline 2: seed-code behavior — static protocol/mode configs forced a
    # full retrace + compile per sweep point.  Measure 3 points, scale.
    n_probe = 3
    t0 = time.time()
    for density, pkt, (proto, mode) in (
        (0.35, 25_000, ("ra", "ra_normalized")),
        (0.5, 100_000, ("ra", "substitution")),
        (0.8, 400_000, ("aayg", "ra_normalized")),
    ):
        common.standard_fl(protocol=proto, mode=mode, edge_density=density,
                           packet_len_bits=pkt, n_rounds=N_ROUNDS,
                           tx_power_dbm=common.HARSH_TX_DBM)
    t_legacy = (time.time() - t0) * len(grid) / n_probe

    common.emit(
        "fig3/timing", t_batched * 1e6,
        f"scenarios={len(grid)};batched_s={t_batched:.2f};"
        f"warm_redispatch_s={t_warm:.2f};"
        f"per_scenario_dispatch_s={t_seq:.2f};"
        f"legacy_retrace_est_s={t_legacy:.2f};"
        f"speedup_vs_legacy={t_legacy / max(t_batched, 1e-9):.1f}x",
    )

    # Round-loop compute diet: same grid, eval thinned to every 4th round
    # and the bias diagnostic off.  Warm-vs-warm is the honest comparison
    # (compile time excluded on both sides).
    cfg_diet = dataclasses.replace(common.standard_cfg(n_rounds=N_ROUNDS),
                                   eval_every=4, track_bias=False)
    runner_diet = scenarios.GridRunner(init, apply_fn, data, cfg_diet,
                                       devices=common.grid_devices())
    t0 = time.time()
    runner_diet.run(grid)
    t_diet_cold = time.time() - t0
    t0 = time.time()
    runner_diet.run(grid)
    t_diet_warm = time.time() - t0
    common.emit(
        "fig3/compute_diet", t_diet_warm * 1e6,
        f"eval_every=4;track_bias=0;warm_s={t_diet_warm:.2f};"
        f"baseline_warm_s={t_warm:.2f};"
        f"warm_speedup={t_warm / max(t_diet_warm, 1e-9):.2f}x",
    )

    common.write_bench("grid", [
        {"name": "fig3/grid_cold", "us_per_call": round(t_batched * 1e6, 1),
         "scenarios": len(grid), "n_rounds": N_ROUNDS},
        {"name": "fig3/grid_warm", "us_per_call": round(t_warm * 1e6, 1),
         "scenarios": len(grid), "n_rounds": N_ROUNDS,
         "eval_every": 1, "track_bias": True},
        {"name": "fig3/grid_warm_diet",
         "us_per_call": round(t_diet_warm * 1e6, 1),
         "scenarios": len(grid), "n_rounds": N_ROUNDS,
         "eval_every": 4, "track_bias": False,
         "cold_us": round(t_diet_cold * 1e6, 1),
         "warm_speedup_vs_baseline":
             round(t_warm / max(t_diet_warm, 1e-9), 3)},
        {"name": "fig3/per_scenario_dispatch",
         "us_per_call": round(t_seq * 1e6, 1), "scenarios": len(grid)},
        {"name": "fig3/legacy_retrace_est",
         "us_per_call": round(t_legacy * 1e6, 1), "scenarios": len(grid)},
    ])


if __name__ == "__main__":
    main()
