"""Shared benchmark utilities: timing + CSV emission + standard FL setup.

Sweep benchmarks build a `ScenarioGrid` from the same standard setup and run
the whole figure in ONE `scenarios.run_grid` dispatch (the batched scenario
engine); `standard_fl` keeps the scalar one-scenario path for benchmarks that
genuinely need a single run.

Setting ``REPRO_GRID_DEVICES=k`` shards every figure's grid dispatch over
the first k jax devices (see `grid_devices`); combine with
``XLA_FLAGS=--xla_force_host_platform_device_count=k`` on CPU.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

# Repo root: BENCH_*.json perf baselines land here (see `write_bench`).
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.launch import compile_cache
from repro.models import smallnets

compile_cache.enable()

# Harsher channel than the paper default so error effects are visible at
# CPU-tractable scale (recorded in EXPERIMENTS.md): at 17 dBm the Table-II
# network's min-PER routes span rho in [0, 1] with mean ~0.44-0.76 depending
# on packet length — the moderate-error regime of the paper's figures.
HARSH_TX_DBM = 17.0


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def write_bench(name: str, rows: list[dict], *, path: str | None = None) -> str:
    """Write machine-readable perf rows to ``BENCH_<name>.json`` (repo root).

    The shared emission path for every benchmark's perf trajectory: each row
    is a flat dict (at minimum ``{"name": ..., "us_per_call": ...}``, plus
    free-form derived fields), wrapped with the environment needed to
    compare runs (backend, device count, jax version).  Committed baselines
    give later PRs a number to beat; CI's perf-smoke job uploads them as
    artifacts.
    """
    import jax

    payload = {
        "bench": name,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "rows": rows,
    }
    path = path or os.path.join(ROOT, f"BENCH_{name}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.relpath(path, ROOT)} ({len(rows)} rows)")
    return path


def grid_devices():
    """The benchmark-wide grid-sharding knob (`REPRO_GRID_DEVICES=k`).

    Returns the first k jax devices when the env var is a positive int,
    else None (single-device vmap path).  Every figure that dispatches a
    `ScenarioGrid` routes this through `scenarios.run_grid(devices=...)`.
    """
    raw = os.environ.get("REPRO_GRID_DEVICES", "").strip() or "0"
    try:
        k = int(raw)
    except ValueError:
        raise ValueError(
            f"REPRO_GRID_DEVICES must be an integer device count, got {raw!r}"
        ) from None
    if k <= 0:
        return None
    import jax

    if k > jax.device_count():
        raise ValueError(
            f"REPRO_GRID_DEVICES={k} but only {jax.device_count()} device(s) "
            "visible — on CPU combine with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={k}"
        )
    return jax.devices()[:k]


def timed(fn, *args, repeats: int = 1, **kw):
    t0 = time.time()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.time() - t0) / repeats
    return out, dt * 1e6


def standard_data(seed=0, samples_per_client=80):
    """Paper Sec. V data at CPU scale: 10-client label-skew non-iid shards."""
    return synthetic.fed_image_classification(
        n_clients=10, samples_per_client=samples_per_client, seed=seed
    )


def standard_net(packet_len_bits=25_000, tx_power_dbm=None, edge_density=0.5,
                 n_relays=0):
    """Table-II network (optionally with Fig. 9 routing-only relays)."""
    tx = tx_power_dbm if tx_power_dbm is not None else topology.TX_POWER_DBM
    if n_relays > 0:
        return topology.paper_network_with_relays(
            n_relays, edge_density=edge_density,
            packet_len_bits=packet_len_bits, tx_power_dbm=tx,
        )
    return topology.make_network(
        topology.TABLE_II_COORDS, edge_density=edge_density,
        packet_len_bits=packet_len_bits, n_clients=10, tx_power_dbm=tx,
    )


def standard_model(d_hidden=48):
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=d_hidden)
    return init, smallnets.apply_mlp_clf


def standard_cfg(n_rounds=15, seg_len=256, aayg_mixes=1, seed=0, **kw):
    return simulator.SimConfig(
        n_rounds=n_rounds, local_epochs=3, seg_len=seg_len,
        aayg_mixes=aayg_mixes, seed=seed, **kw,
    )


# run_standard_grid's devices default: resolve the REPRO_GRID_DEVICES knob
# (so an explicit devices=None still forces the single-device path).
_ENV_DEVICES = object()


def run_standard_grid(grid: scenarios.ScenarioGrid, *, n_rounds=15,
                      seg_len=256, aayg_mixes=1, data_seed=0,
                      samples_per_client=80,
                      devices=_ENV_DEVICES) -> scenarios.GridResult:
    """One batched dispatch of `grid` on the standard data/model.

    ``data_seed`` seeds the shared dataset only; model-init / channel seeds
    are per-scenario and live in the grid (ScenarioGrid.product(seeds=...)).
    ``devices`` shards the grid axis; by default the REPRO_GRID_DEVICES
    knob decides, and an explicit ``devices=None`` forces the
    single-device vmap path regardless of the environment.
    """
    data = standard_data(seed=data_seed, samples_per_client=samples_per_client)
    init, apply_fn = standard_model()
    cfg = standard_cfg(n_rounds=n_rounds, seg_len=seg_len,
                       aayg_mixes=aayg_mixes)
    if devices is _ENV_DEVICES:
        devices = grid_devices()
    return scenarios.run_grid(init, apply_fn, data, grid, cfg,
                              devices=devices)


def standard_fl(n_rounds=15, protocol="ra", mode="ra_normalized",
                packet_len_bits=25_000, tx_power_dbm=None, seg_len=256,
                edge_density=0.5, n_relays=0, aayg_mixes=1, seed=0,
                samples_per_client=80):
    """Paper Sec. V setup at CPU scale: 10 clients, MLP on synthetic
    label-skew non-iid data, Table-II network (scalar, one scenario)."""
    data = standard_data(seed=seed, samples_per_client=samples_per_client)
    net = standard_net(packet_len_bits=packet_len_bits,
                       tx_power_dbm=tx_power_dbm, edge_density=edge_density,
                       n_relays=n_relays)
    cfg = standard_cfg(n_rounds=n_rounds, seg_len=seg_len,
                       aayg_mixes=aayg_mixes, seed=seed,
                       protocol=protocol, mode=mode)
    init, apply_fn = standard_model()
    res = scenarios.simulate(init, apply_fn, data, net, cfg)
    return res, net, data
