"""Multi-replica failover: req/s and p99 before/during/after a replica
kill.

Drives an open-loop request stream through a `ScenarioRouter` over three
in-process `ScenarioServer` replicas (DESIGN.md §14), hard-kills the
replica carrying the traffic mid-stream, and reports throughput and
client-observed latency for three windows — before the kill, during it
(the failover transient: retries, breaker trip, re-route), and after
(steady state on the survivors).  Every delivered result is checked
equal (within float32 round-off) to a direct `GridRunner.run` of the
same scenarios; any mismatch or undelivered request fails the run.
Rows land in ``BENCH_serve_failover.json``; the headline acceptance
number is a FINITE post-failover p99 — the fleet keeps serving correctly
with a replica dead.

Tiny mode for CI smoke: ``REPRO_BENCH_TINY=1``.

Runs standalone:

  PYTHONPATH=src:. python benchmarks/serve_failover.py
"""
from __future__ import annotations

import os
import time

import numpy as np


def _tiny() -> bool:
    return os.environ.get("REPRO_BENCH_TINY", "").strip() not in ("", "0")


def _phase(rt, pool, refs, n_requests, rate, rng, *, kill=None):
    """Submit ``n_requests`` open-loop, recording per-request client
    latency at COMPLETION time (not result() order).  ``kill``, if set,
    is a zero-arg callable fired after half the submissions — the
    mid-stream fault.  Returns (duration_s, latencies, mismatched_labels,
    failed)."""
    lats, done_flags = [], []
    futures = []
    t0 = time.monotonic()
    for i in range(n_requests):
        time.sleep(rng.exponential(1.0 / rate))
        if kill is not None and i == n_requests // 2:
            kill()
            kill = None
        t_sub = time.monotonic()
        f = rt.submit(pool[i % len(pool)])
        f.add_done_callback(
            lambda fut, t=t_sub: lats.append(time.monotonic() - t)
        )
        futures.append((i, f))
    mismatched, failed = [], []
    for i, f in futures:
        g = pool[i % len(pool)]
        try:
            got = f.result(timeout=600)
        except Exception as e:
            failed.append((g.labels[0], repr(e)))
            continue
        ref = refs[i % len(pool)]
        if not all(
            np.allclose(a, b, rtol=1e-5, atol=1e-6, equal_nan=True)
            for a, b in ((got.acc, ref.acc), (got.loss, ref.loss),
                         (got.bias, ref.bias))
        ):
            mismatched.append(g.labels[0])
    return time.monotonic() - t0, lats, mismatched, failed


def main() -> None:
    from benchmarks import common
    from repro.fl import scenarios, simulator
    from repro.launch import router, serving

    tiny = _tiny()
    n_rounds = 2 if tiny else 5
    per_phase = 6 if tiny else 24
    rate = 50.0           # mean arrivals/sec of the open-loop process

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
    ref_runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    refs = [ref_runner.run(g) for g in pool]

    rt = router.ScenarioRouter.in_process(
        init, apply_fn, data, cfg, n_replicas=3,
        # Single-row dispatch: coalescing variety would smear ad-hoc
        # compile costs across the windows; this benchmark isolates the
        # FAILOVER transient (batching throughput is serve_scaling.py's
        # story).
        serve=serving.ServeConfig(max_batch=1, max_delay_s=0.005),
        route=router.RouterConfig(
            max_attempts=4, attempt_timeout_s=60.0, backoff_base_s=0.02,
            heartbeat_s=0.05, breaker_failures=3, breaker_cooldown_s=0.5,
        ),
    )
    t0 = time.monotonic()
    # Warm every replica (fanout=3): failover lands on warm survivors.
    compiled = rt.warmup(pool, fanout=3)
    t_warm = time.monotonic() - t0
    victim = rt._ring.preference(router.grid_signature(pool[0]))[0]

    def kill_victim() -> None:
        # Hard-kill the loaded replica: its in-flight requests fail with
        # ServerStopped and must fail over to the warm survivors.
        rt.replicas[victim].server.stop(drain=False)

    rng = np.random.default_rng(0)
    rows, problems = [], []
    with rt:
        # Priming pass: absorbs any residual first-dispatch compiles so
        # the three measured windows are comparable.
        for got, ref in zip(rt.serve(pool), refs):
            if not np.allclose(got.acc, ref.acc, rtol=1e-5, atol=1e-6):
                problems.append(("prime", "mismatch", "priming pass"))
        phases = (
            ("before", None),
            ("during_kill", kill_victim),
            ("after", None),
        )
        for phase_name, kill in phases:
            dt, lats, mismatched, failed = _phase(
                rt, pool, refs, per_phase, rate, rng, kill=kill
            )
            if mismatched:
                problems.append((phase_name, "mismatch", mismatched))
            if failed:
                problems.append((phase_name, "failed", failed))
            p50, p99 = (
                (float(np.percentile(lats, 50)),
                 float(np.percentile(lats, 99)))
                if lats else (float("nan"), float("nan"))
            )
            row = {
                "name": f"serve_failover/{phase_name}",
                "us_per_call": dt * 1e6 / per_phase,
                "phase": phase_name,
                "replicas_alive": 2 if phase_name != "before" else 3,
                "requests": per_phase,
                "delivered": per_phase - len(failed),
                "requests_per_s": per_phase / max(dt, 1e-9),
                "latency_p50_s": p50,
                "latency_p99_s": p99,
                "matches_direct": not mismatched,
                "warmup_programs": compiled,
                "warmup_s": t_warm,
                "tiny": tiny,
            }
            rows.append(row)
            common.emit(
                row["name"], row["us_per_call"],
                f"phase={phase_name};req_per_s={row['requests_per_s']:.2f};"
                f"p50_s={p50:.4f};p99_s={p99:.4f};"
                f"delivered={row['delivered']}/{per_phase};"
                f"matches_direct={row['matches_direct']}",
            )
        snap = rt.tracker.snapshot()
    rows.append({
        "name": "serve_failover/router_counters",
        "us_per_call": 0.0,
        "victim": victim,
        "retries": snap.get("router/retries", 0),
        "timeouts": snap.get("router/timeouts", 0),
        "breaker_opens": snap.get("router/breaker_opens", 0),
        "replica_errors": snap.get("router/replica_errors", 0),
        "results_discarded": snap.get("router/results_discarded", 0),
        "tiny": tiny,
    })
    common.write_bench("serve_failover", rows)

    # Acceptance: recovery is real — the post-failover window delivered
    # everything with a finite p99, equal to direct runs.
    after = rows[2]
    if not (np.isfinite(after["latency_p99_s"])
            and after["delivered"] == per_phase):
        problems.append(("after", "no_recovery", after))
    if problems:
        raise SystemExit(f"serve_failover: {problems}")


if __name__ == "__main__":
    main()
