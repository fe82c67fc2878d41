"""Sharded serving scaling: req/s and p99 latency vs device count.

Drives the same mixed-priority open-loop Poisson load (DESIGN.md §12)
through a `ScenarioServer` sharded over 1, 2, 4, and 8 devices
(`devices=jax.devices()[:d]` — the ('grid',) mesh dispatch path) and
reports requests/sec, p50/p99 latency, and batch fill per device count,
verifying every sharded result equal (within float32 round-off) to a
direct single-device `GridRunner.run` of the same scenarios.  Rows land
in ``BENCH_serve_scaling.json`` (benchmarks/common.write_bench).

Device counts are forced host (CPU) devices unless XLA_FLAGS is already
set (on a real accelerator, export XLA_FLAGS= and the machine's devices
are used as-is).  On CPU the forced devices share the same cores, so
req/s measures dispatch/partitioning overhead rather than real speedup —
the accelerator-facing curve comes from running this same script on
multi-chip hardware.

Tiny mode for CI smoke: ``REPRO_BENCH_TINY=1`` shrinks rounds/requests so
the whole sweep takes tens of seconds.

Runs standalone (needs its own device count):

  PYTHONPATH=src:. python benchmarks/serve_scaling.py
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import numpy as np

DEVICE_COUNTS = (1, 2, 4, 8)


def _tiny() -> bool:
    return os.environ.get("REPRO_BENCH_TINY", "").strip() not in ("", "0")


def main() -> None:
    import jax

    from benchmarks import common
    from repro.fl import scenarios, simulator
    from repro.launch import serving

    tiny = _tiny()
    n_rounds = 2 if tiny else 5
    n_requests = 8 if tiny else 40
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
    # Single-device reference for the equivalence contract: EVERY mesh
    # width must reproduce these results exactly.
    ref_runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    refs = [ref_runner.run(g) for g in pool]

    rows, mismatched = [], []
    for d in DEVICE_COUNTS:
        if d > jax.device_count():
            common.emit(f"serve_scaling/d{d}", 0.0,
                        f"skipped=only_{jax.device_count()}_devices")
            continue
        server = serving.ScenarioServer(
            init, apply_fn, data, cfg,
            serve=serving.ServeConfig(
                tenant_weights={"gold": 3.0, "bronze": 1.0},
            ),
            devices=jax.devices()[:d],
        )
        t0 = time.monotonic()
        compiled = server.warmup(*pool, scenarios.ScenarioGrid.concat(*pool))
        t_warm = time.monotonic() - t0
        with server:
            # Priming pass doubles as the per-mesh equivalence check.
            got = server.serve(pool)
            bad = [
                g.labels[0]
                for g, r in zip(got, refs)
                if not all(
                    np.allclose(a, b, rtol=1e-5, atol=1e-6,
                                   equal_nan=True)
                    for a, b in ((g.acc, r.acc), (g.loss, r.loss),
                                 (g.bias, r.bias))
                )
            ]
            if bad:
                mismatched.append((d, bad))
            server.tracker.reset()

            # Measured steady state: open-loop Poisson arrivals, 25%
            # priority traffic, two weighted tenants.
            rng = np.random.default_rng(0)
            t0 = time.monotonic()
            futures = []
            for i in range(n_requests):
                time.sleep(rng.exponential(1.0 / rate))
                futures.append(server.submit(
                    pool[i % len(pool)],
                    priority=int(rng.random() < 0.25),
                    tenant="gold" if i % 2 else "bronze",
                ))
            for f in futures:
                f.result()
            dt = time.monotonic() - t0

        snap = server.tracker.snapshot()
        row = {
            "name": f"serve_scaling/d{d}",
            "us_per_call": dt * 1e6 / n_requests,
            "devices": d,
            "requests": n_requests,
            "requests_per_s": n_requests / max(dt, 1e-9),
            "latency_p50_s": snap.get("serve/latency_s_p50", float("nan")),
            "latency_p99_s": snap.get("serve/latency_s_p99", float("nan")),
            "batch_fill_mean": snap.get("grid/batch_fill_mean", float("nan")),
            "dispatches": snap.get("serve/dispatches", 0),
            "warmup_programs": compiled,
            "warmup_s": t_warm,
            "tiny": tiny,
            "matches_direct": not bad,
        }
        rows.append(row)
        common.emit(
            row["name"], row["us_per_call"],
            f"devices={d};req_per_s={row['requests_per_s']:.2f};"
            f"p50_s={row['latency_p50_s']:.4f};"
            f"p99_s={row['latency_p99_s']:.4f};"
            f"fill={row['batch_fill_mean']:.3f};"
            f"matches_direct={row['matches_direct']}",
        )
    common.write_bench("serve_scaling", rows)
    if mismatched:
        raise SystemExit(
            f"serve_scaling: sharded serving diverged from the "
            f"single-device reference: {mismatched}"
        )


if __name__ == "__main__":
    main()
