"""Batched scenario sweeps in one dispatch: `repro.fl.scenarios` demo.

Builds a grid crossing two packet lengths x three protocol rows x two seeds
(12 scenarios) and runs the whole thing through ONE vmapped, jitted training
loop — the same engine the figure benchmarks use — then prints a small
per-scenario table, the dispatch-cost comparison, and a sharded dispatch
over every visible device (`devices=jax.devices()`; results equal within
float32 round-off, DESIGN.md §16).

Run:  PYTHONPATH=src python examples/sweep_grid.py
To see real grid sharding on CPU, force host devices first:
      XLA_FLAGS=--xla_force_host_platform_device_count=4 \
          PYTHONPATH=src python examples/sweep_grid.py
"""
import time

import jax
import numpy as np

from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.models import smallnets


def main() -> None:
    data = synthetic.fed_image_classification(
        n_clients=10, samples_per_client=60, seed=0
    )
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=32)
    apply_fn = smallnets.apply_mlp_clf

    networks = [
        (f"K{pkt // 1000}k",
         topology.paper_network(packet_len_bits=pkt))
        for pkt in (25_000, 400_000)
    ]
    grid = scenarios.ScenarioGrid.product(
        networks=networks,
        protocols=[("ra", "ra_normalized"), ("ra", "substitution"),
                   ("aayg", "ra_normalized")],
        seeds=[0, 1],
    )
    cfg = simulator.SimConfig(n_rounds=10, local_epochs=3, seg_len=256)

    print(f"running {len(grid)} scenarios in one batched dispatch...")
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    t0 = time.time()
    res = runner.run(grid)
    t_batched = time.time() - t0

    print(f"\n{'scenario':<36} {'final acc':>9} {'spread':>8} {'bias':>10}")
    for i, label in enumerate(res.labels):
        bias = res.bias[i, -1]
        bias_s = f"{bias:>10.4f}" if bias == bias else f"{'n/a':>10}"
        print(f"{label:<36} {res.mean_acc[i, -1]:>9.3f} "
              f"{res.acc[i, -1].std():>8.4f} {bias_s}")

    # A second sweep (new seeds) reuses the runner's compiled programs.
    grid2 = scenarios.ScenarioGrid.product(
        networks=networks,
        protocols=[("ra", "ra_normalized"), ("ra", "substitution"),
                   ("aayg", "ra_normalized")],
        seeds=[2, 3],
    )
    t0 = time.time()
    runner.run(grid2)
    t_warm = time.time() - t0

    # Per-scenario baseline: each row dispatched as its own one-point grid.
    t0 = time.time()
    for i in range(len(grid)):
        runner.run(grid.take([i]))
    t_seq = time.time() - t0

    # Sharded dispatch: the same grid spread over every visible device
    # (a 1-device mesh on a default CPU — same API, same results).
    devs = jax.devices()
    t0 = time.time()
    sharded = runner.run(grid, devices=devs)
    t_sharded = time.time() - t0
    # Another program of the same math: equal within float32 round-off.
    np.testing.assert_allclose(sharded.acc, res.acc, rtol=1e-5, atol=1e-6)

    print(f"\nbatched, cold (compile + dispatch):    {t_batched:6.2f} s")
    print(f"batched, warm (new seeds, no compile): {t_warm:6.2f} s")
    print(f"per-scenario loop (incl. compile):     {t_seq:6.2f} s")
    print(f"sharded over {len(devs)} device(s), cold:      {t_sharded:6.2f} s"
          f"  (same results within float32 round-off)")


if __name__ == "__main__":
    main()
