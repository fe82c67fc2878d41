"""Serving-tier benchmark: open-loop arrivals through ScenarioServer.

Drives a synthetic Poisson arrival process (DESIGN.md §11) over a pool of
single-scenario requests (3 topologies x {ra, aayg}), measures
requests/sec and p50/p99 request latency in a steady-state phase (after a
priming pass that doubles as the equivalence check against a direct
`GridRunner.run` of the same scenarios), and writes the snapshot to
``BENCH_serve.json`` (benchmarks/common.write_bench).

Tiny mode for CI smoke: ``REPRO_BENCH_TINY=1`` shrinks rounds/requests so
the whole process takes seconds.

  PYTHONPATH=src:. python benchmarks/bench_serve.py
"""
from __future__ import annotations

import os
import time

import numpy as np


def _tiny() -> bool:
    return os.environ.get("REPRO_BENCH_TINY", "").strip() not in ("", "0")


def main() -> None:
    from benchmarks import common
    from repro.fl import scenarios, simulator
    from repro.launch import serving

    tiny = _tiny()
    n_rounds = 3 if tiny else 5
    n_requests = 10 if tiny else 48
    rate = 100.0          # mean arrivals/sec of the open-loop process

    data, nets, init, apply_fn = serving._demo_setup(
        n_clients=5, samples=20, seed=0
    )
    cfg = simulator.SimConfig(n_rounds=n_rounds, local_epochs=2, seg_len=64)
    pool = [
        scenarios.ScenarioGrid.product(
            networks=[(lbl, net)], protocols=[(proto, "ra_normalized")],
            seeds=[0],
        )
        for lbl, net in nets
        for proto in ("ra", "aayg")
    ]

    server = serving.ScenarioServer(init, apply_fn, data, cfg)
    t0 = time.monotonic()
    compiled = server.warmup(*pool, scenarios.ScenarioGrid.concat(*pool))
    t_warm = time.monotonic() - t0

    # Direct warm-runner reference for the equivalence contract.
    ref_runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    refs = [ref_runner.run(g) for g in pool]

    with server:
        # Priming burst (back-to-back submits coalesce) + correctness:
        # batched serving must match the direct runner within float32
        # round-off (DESIGN.md §16).
        got = server.serve(pool)
        mismatched = [
            g.labels[0]
            for g, r in zip(got, refs)
            if not all(
                np.allclose(a, b, rtol=1e-5, atol=1e-6, equal_nan=True)
                for a, b in ((g.acc, r.acc), (g.loss, r.loss),
                             (g.bias, r.bias))
            )
        ]
        server.tracker.reset()

        # Measured steady-state phase: open-loop Poisson arrivals.
        rng = np.random.default_rng(0)
        t0 = time.monotonic()
        futures = []
        for i in range(n_requests):
            time.sleep(rng.exponential(1.0 / rate))
            futures.append(server.submit(pool[i % len(pool)]))
        for f in futures:
            f.result()
        dt = time.monotonic() - t0

    snap = server.tracker.snapshot()
    cache = server.runner.programs.stats
    row = {
        "name": "serve/open_loop",
        "us_per_call": dt * 1e6 / n_requests,
        "requests": n_requests,
        "requests_per_s": n_requests / max(dt, 1e-9),
        "latency_p50_s": snap.get("serve/latency_s_p50", float("nan")),
        "latency_p99_s": snap.get("serve/latency_s_p99", float("nan")),
        "batch_fill_mean": snap.get("grid/batch_fill_mean", float("nan")),
        "coalesced_scenarios_mean": snap.get(
            "serve/coalesced_scenarios_mean", float("nan")),
        "dispatches": snap.get("serve/dispatches", 0),
        "cache_hit": snap.get("cache/hit", 0),
        "cache_miss": snap.get("cache/miss", 0),
        "cache_evict": snap.get("cache/evict", 0),
        "cache_programs": cache["programs"],
        "warmup_programs": compiled,
        "warmup_s": t_warm,
        "tiny": tiny,
        "matches_direct": not mismatched,
    }
    common.emit(
        "serve/open_loop", row["us_per_call"],
        f"req_per_s={row['requests_per_s']:.2f};"
        f"p50_s={row['latency_p50_s']:.4f};p99_s={row['latency_p99_s']:.4f};"
        f"fill={row['batch_fill_mean']:.3f};"
        f"cache_hit={row['cache_hit']};cache_miss={row['cache_miss']};"
        f"matches_direct={row['matches_direct']}",
    )
    common.write_bench("serve", [row])
    if mismatched:
        raise SystemExit(
            f"bench_serve: batched serving diverged from the direct "
            f"GridRunner reference on {mismatched}"
        )


if __name__ == "__main__":
    main()
