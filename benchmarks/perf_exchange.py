"""§Perf hillclimb #3 (paper's technique): collective schedule of the R&A
exchange (core/dfl_step.ra_exchange) on a client mesh axis.

Part 1 compares the routed-unicast analogue (all_to_all of
destination-weighted segments) against the naive masked-psum schedule, by
collective bytes in the lowered SPMD module.

Part 2 measures the batched scenario engine on the exchange-heavy regime:
a 16-point PER sweep dispatched once via `scenarios.run_grid` vs each
scenario dispatched as its own one-point grid, plus
the sharded path (`devices=`) spreading the 16 scenarios over the 16
forced host devices — one scenario per device.

Runs standalone (needs its own device count):

  PYTHONPATH=src:. python benchmarks/perf_exchange.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map


def collective_schedules() -> None:
    from repro.core import dfl_step
    from repro.launch.dryrun import collective_bytes

    n = 16
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:n]), ("clients",))
    m_params = 4_194_304          # 4M params (16 MB f32) per client
    seg_len = 1024

    print("name,us_per_call,derived")
    results = {}
    for comm in ("all_to_all", "reduce_scatter", "psum"):
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P("clients"), P(), P(), P()),
            out_specs=P("clients"),
        )
        def exchange(stacked, p, rho, k, _comm=comm):
            mine = stacked[0]
            out = dfl_step.ra_exchange(
                mine, p, rho, k, axis="clients", seg_len=seg_len, comm=_comm
            )
            return out[None]

        lowered = jax.jit(exchange).lower(
            jax.ShapeDtypeStruct((n, m_params), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n, n), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        compiled = lowered.compile()
        coll = collective_bytes(compiled.as_text())
        total = sum(coll.values())
        per_chip_model_bytes = m_params * 4
        results[comm] = total
        print(
            f"perf_exchange/{comm},0.0,"
            f"collective_bytes={total:.3e};"
            f"x_model_size={total / per_chip_model_bytes:.2f};"
            f"breakdown={coll}"
        )
    ratio = results["psum"] / max(results["all_to_all"], 1)
    rs = results["reduce_scatter"] / max(results["all_to_all"], 1)
    print(f"perf_exchange/summary,0.0,psum_vs_a2a_ratio={ratio:.2f};"
          f"rs_vs_a2a_ratio={rs:.2f}")


def grid_dispatch() -> None:
    """Batched vs per-scenario dispatch of an exchange-dominated workload."""
    from benchmarks import common
    from repro.fl import scenarios, simulator

    data = common.standard_data(samples_per_client=40)
    init, apply_fn = common.standard_model(d_hidden=32)
    # 16 TX-power points spanning broken -> clean channels: a pure link-PER
    # axis (exchange-heavy: 2 local epochs, 10 rounds).
    networks = [
        (f"tx{tx:.1f}", common.standard_net(packet_len_bits=100_000,
                                            tx_power_dbm=tx))
        for tx in np.linspace(15.0, 20.0, 16)
    ]
    grid = scenarios.ScenarioGrid.product(networks=networks)
    cfg = simulator.SimConfig(n_rounds=10, local_epochs=2, seg_len=256)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)

    t0 = time.time()
    res = runner.run(grid)
    t_cold = time.time() - t0
    t0 = time.time()
    runner.run(grid)
    t_warm = time.time() - t0
    t0 = time.time()
    for i in range(len(grid)):
        runner.run(grid.take([i]))
    t_seq = time.time() - t0

    # Sharded path: one scenario per forced host device (same runner, the
    # per-mesh program cache keeps both variants warm).  Cap the mesh at
    # the grid size — collective_schedules' dryrun import forces 512 host
    # devices, and a mesh wider than the grid is pure filler.
    devs = jax.devices()[:min(len(grid), jax.device_count())]
    t0 = time.time()
    sharded = runner.run(grid, devices=devs)
    t_shard_cold = time.time() - t0
    t0 = time.time()
    runner.run(grid, devices=devs)
    t_shard_warm = time.time() - t0
    np.testing.assert_allclose(sharded.acc, res.acc, rtol=1e-5, atol=1e-6)

    acc_lo, acc_hi = res.mean_acc[0, -1], res.mean_acc[-1, -1]
    print(
        f"perf_exchange/grid_dispatch,{t_warm * 1e6:.1f},"
        f"scenarios={len(grid)};batched_cold_s={t_cold:.2f};"
        f"batched_warm_s={t_warm:.2f};"
        f"per_scenario_dispatch_s={t_seq:.2f};"
        f"warm_speedup={t_seq / max(t_warm, 1e-9):.2f}x;"
        f"sharded{len(devs)}_cold_s={t_shard_cold:.2f};"
        f"sharded{len(devs)}_warm_s={t_shard_warm:.2f};"
        f"acc_worst_channel={acc_lo:.3f};acc_best_channel={acc_hi:.3f}"
    )
    common.write_bench("exchange", [
        {"name": "perf_exchange/grid_warm",
         "us_per_call": round(t_warm * 1e6, 1), "scenarios": len(grid)},
        {"name": "perf_exchange/grid_cold",
         "us_per_call": round(t_cold * 1e6, 1), "scenarios": len(grid)},
        {"name": "perf_exchange/per_scenario_dispatch",
         "us_per_call": round(t_seq * 1e6, 1), "scenarios": len(grid)},
        {"name": f"perf_exchange/sharded{len(devs)}_warm",
         "us_per_call": round(t_shard_warm * 1e6, 1),
         "scenarios": len(grid), "devices": len(devs)},
    ])


def main() -> None:
    collective_schedules()
    grid_dispatch()


if __name__ == "__main__":
    main()
