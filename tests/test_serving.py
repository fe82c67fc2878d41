"""Serving tier: queued batching == direct dispatch + cache/tracker units.

Also covers the PR-7 surface: sharded serving (devices= end-to-end,
bit-identical to the unsharded path and to direct run_grid), the
priority / SLA scheduling rules, ScenarioGrid.take (the cancellation
re-slice primitive), and _FairQueue scheduling units (DESIGN.md §12).
"""
import dataclasses
import os
import subprocess
import sys
import time
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.launch import serving, tracker
from repro.models import smallnets

# Packet length consistent with seg_len=64 float32 segments so the
# server's strict admission check passes by default.
_PACKET_BITS = 32 * 64


def _setup(n_clients=3):
    data = synthetic.fed_image_classification(
        n_clients=n_clients, samples_per_client=20, seed=0
    )
    coords = topology.TABLE_II_COORDS[:n_clients]
    nets = [
        topology.make_network(
            coords, edge_density=d, packet_len_bits=_PACKET_BITS,
            n_clients=n_clients, tx_power_dbm=17.0,
        )
        for d in (0.6, 0.8)
    ]
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=16)
    return data, nets, init, smallnets.apply_mlp_clf


@pytest.fixture(scope="module")
def toy():
    return _setup()


def _cfg(**kw):
    kw.setdefault("n_rounds", 3)
    kw.setdefault("local_epochs", 2)
    kw.setdefault("seg_len", 64)
    return simulator.SimConfig(**kw)


def _grid(net, proto="ra", label="g", seed=0):
    return scenarios.ScenarioGrid.product(
        networks=[(label, net)], protocols=[(proto, "ra_normalized")],
        seeds=[seed],
    )


def _assert_same(got: scenarios.GridResult, want: scenarios.GridResult):
    np.testing.assert_array_equal(np.asarray(got.acc), np.asarray(want.acc))
    np.testing.assert_array_equal(np.asarray(got.loss),
                                  np.asarray(want.loss))
    # bias is NaN for non-R&A rows; bitwise NaN == NaN is intended.
    assert np.array_equal(np.asarray(got.bias), np.asarray(want.bias),
                          equal_nan=True)


# ---------------------------------------------------------------------
# ProgramCache / tracker units (no jax dispatch)
# ---------------------------------------------------------------------

def test_program_cache_lru_eviction_order():
    t = tracker.StatsTracker()
    built = []
    cache = scenarios.ProgramCache(max_programs=2, tracker=t)
    get = lambda k: cache.lookup(k, lambda: built.append(k) or f"prog-{k}")

    assert get("a") == "prog-a" and get("b") == "prog-b"
    assert get("a") == "prog-a"          # refresh: "a" is now most recent
    get("c")                             # evicts "b", the LRU entry
    assert built == ["a", "b", "c"]
    get("a")                             # still cached
    get("b")                             # rebuilt: was evicted
    assert built == ["a", "b", "c", "b"]
    assert cache.stats["programs"] == 2
    assert cache.evictions == 2          # b then a
    assert t.counter("cache/evict") == 2
    assert t.counter("cache/hit") == cache.hits
    assert t.counter("cache/miss") == cache.misses == 4


def test_program_cache_unbounded_by_default():
    cache = scenarios.ProgramCache()
    for i in range(64):
        cache.lookup(i, lambda i=i: i)
    assert cache.stats["programs"] == 64 and cache.evictions == 0


def test_stats_tracker_snapshot_and_reset():
    t = tracker.StatsTracker()
    t.count("req", 2)
    t.count("req")
    t.gauge("depth", 7)
    for v in (1.0, 2.0, 3.0, 4.0):
        t.observe("lat", v)
    snap = t.snapshot()
    assert snap["req"] == 3 and snap["depth"] == 7
    assert snap["lat_count"] == 4 and snap["lat_mean"] == 2.5
    assert snap["lat_p50"] == 2.5 and snap["lat_max"] == 4.0
    assert t.percentile("lat", 50) == 2.5
    assert np.isnan(t.percentile("missing", 50))
    t.reset()
    assert t.snapshot() == {}


def test_composite_tracker_fans_out():
    a, b = tracker.StatsTracker(), tracker.StatsTracker()
    c = tracker.CompositeTracker([a, b])
    c.count("n")
    c.observe("x", 1.5)
    assert a.counter("n") == b.counter("n") == 1
    assert a.samples("x") == b.samples("x") == [1.5]


def test_first_token_slices_both_logit_ranks():
    from repro.launch.serve import first_token

    last = jnp.asarray([[0.1, 0.9, 0.0], [0.8, 0.1, 0.1]])      # (B, V)
    stacked = jnp.stack([last * 0 - 1.0, last], axis=1)          # (B, 2, V)
    want = np.asarray([[1], [0]])
    np.testing.assert_array_equal(np.asarray(first_token(last)), want)
    np.testing.assert_array_equal(np.asarray(first_token(stacked)), want)
    assert first_token(last).dtype == jnp.int32


# ---------------------------------------------------------------------
# Admission validation
# ---------------------------------------------------------------------

def test_bad_eval_every_fails_at_server_construction(toy):
    data, nets, init, apply_fn = toy
    with pytest.raises(ValueError, match="eval_every"):
        serving.ScenarioServer(init, apply_fn, data,
                               _cfg(n_rounds=3, eval_every=2))


def test_admission_rejects_malformed_grid_and_keeps_serving(toy):
    data, nets, init, apply_fn = toy
    good = _grid(nets[0], label="ok")
    bad = _grid(nets[0], label="broken")
    bad = dataclasses.replace(
        bad,
        scenarios=bad.scenarios._replace(
            protocol_id=np.asarray([99], np.int32)),
    )
    empty = dataclasses.replace(
        good, labels=[],
        scenarios=jax.tree.map(lambda l: l[:0], good.scenarios),
    )
    with serving.ScenarioServer(init, apply_fn, data, _cfg()) as server:
        with pytest.raises(scenarios.AdmissionError,
                           match=r"protocol_id.*'broken"):
            server.submit(bad)
        with pytest.raises(scenarios.AdmissionError, match="empty"):
            server.submit(empty)
        res = server.submit(good).result(timeout=300)
    assert res.labels == good.labels     # warm server survived the reject


def test_strict_packet_mismatch_is_an_admission_error(toy):
    data, nets, init, apply_fn = toy
    mismatched_net = topology.make_network(
        topology.TABLE_II_COORDS[:3], edge_density=0.8,
        packet_len_bits=25_000, n_clients=3, tx_power_dbm=17.0,
    )
    server = serving.ScenarioServer(init, apply_fn, data, _cfg())
    with server:
        with pytest.raises(scenarios.AdmissionError, match="packet"):
            server.submit(_grid(mismatched_net))


def test_grid_runner_validate_raises_out_of_range_lr(toy):
    data, nets, init, apply_fn = toy
    g = _grid(nets[0], label="nan-lr")
    g = dataclasses.replace(
        g, scenarios=g.scenarios._replace(
            lr=np.asarray([np.nan], np.float32)),
    )
    runner = scenarios.GridRunner(init, apply_fn, data, _cfg())
    with pytest.raises(scenarios.AdmissionError, match=r"lr.*'nan-lr"):
        runner.validate(g)


# ---------------------------------------------------------------------
# Bit-identity: queued serving == direct run_grid
# ---------------------------------------------------------------------

def test_coalesced_mixed_protocol_serving_bit_identical(toy):
    """Back-to-back requests (mixed protocols, distinct topologies)
    coalesce into ONE dispatch and still match per-request run_grid."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    requests = [
        _grid(nets[0], "ra", "r0"),
        _grid(nets[1], "aayg", "r1"),
        _grid(nets[1], "ra", "r2"),
    ]
    refs = [scenarios.run_grid(init, apply_fn, data, g, cfg)
            for g in requests]
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=8, max_delay_s=0.25),
    )
    with server:
        got = server.serve(requests)
    for g, r in zip(got, refs):
        _assert_same(g, r)
        assert g.labels == r.labels
    snap = server.tracker.snapshot()
    assert snap["serve/dispatches"] == 1          # genuinely coalesced
    assert snap["serve/requests"] == 3


def test_serving_times_queue_wait_and_dispatch(toy):
    """Each request's wait in the queue is observed once, when a batch
    takes it; each dispatch once, through the grid runner's spans."""
    data, nets, init, apply_fn = toy
    server = serving.ScenarioServer(
        init, apply_fn, data, _cfg(),
        serve=serving.ServeConfig(max_batch=8, max_delay_s=0.25),
    )
    with server:
        server.serve([_grid(nets[0], "ra", "q0"), _grid(nets[1], "ra", "q1")])
    t = server.tracker
    waits, latencies = t.samples("serve/queue_wait_s"), t.samples(
        "serve/latency_s")
    assert len(waits) == len(latencies) == 2
    assert max(waits) <= max(latencies)
    n = int(t.counter("serve/dispatches"))
    assert len(t.samples("serve/dispatch_s")) == n
    assert len(t.samples("grid/run_s")) == n


def test_partial_batch_bucket_padding_bit_identical(toy):
    """A 3-scenario dispatch padded to a 4-bucket with routing-neutral
    filler returns the unpadded rows bit-identically."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    grid = scenarios.ScenarioGrid.concat(
        _grid(nets[0], "ra", "a"), _grid(nets[1], "ra", "b"),
        _grid(nets[0], "aayg", "c"),
    )
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    want = runner.run(grid)                       # unpadded reference
    tr = tracker.StatsTracker()
    padded_runner = scenarios.GridRunner(init, apply_fn, data, cfg,
                                         tracker=tr)
    got = padded_runner.run(grid, pad_to=(4,))
    _assert_same(got, want)
    fills = tr.samples("grid/batch_fill")
    assert fills and all(f <= 1.0 for f in fills)
    assert min(fills) < 1.0                       # some group really padded


def test_serving_across_cache_eviction_rewarm_cycle(toy):
    """max_cached_programs=1 forces evict/re-compile between alternating
    shapes; results stay identical to an unbounded-cache runner."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    small = _grid(nets[0], "ra", "small")
    big = scenarios.ScenarioGrid.concat(_grid(nets[0], "ra", "x"),
                                        _grid(nets[1], "ra", "y"))
    ref = scenarios.GridRunner(init, apply_fn, data, cfg)
    want = [ref.run(small), ref.run(big), ref.run(small)]

    tr = tracker.StatsTracker()
    bounded = scenarios.GridRunner(init, apply_fn, data, cfg,
                                   tracker=tr, max_cached_programs=1)
    got = [bounded.run(small), bounded.run(big), bounded.run(small)]
    for g, w in zip(got, want):
        _assert_same(g, w)
    assert bounded.programs.evictions >= 2        # small->big->small
    assert tr.counter("cache/evict") == bounded.programs.evictions
    assert bounded.programs.stats["programs"] == 1


def test_warmup_precompiles_dispatch_shapes(toy):
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    reqs = [_grid(nets[0], "ra", "w0"), _grid(nets[1], "aayg", "w1")]
    server = serving.ScenarioServer(init, apply_fn, data, cfg)
    compiled = server.warmup(*reqs, scenarios.ScenarioGrid.concat(*reqs))
    assert compiled >= 1
    misses_before = server.runner.programs.misses
    with server:
        got = server.serve(reqs)
    assert server.runner.programs.misses == misses_before  # all warm
    assert [g.labels for g in got] == [r.labels for r in reqs]
    with pytest.raises(RuntimeError, match="start"):
        server.warmup(reqs[0])                    # post-start is an error
    with pytest.raises(RuntimeError, match="not accepting"):
        server.submit(reqs[0])                    # stopped server rejects


# ---------------------------------------------------------------------
# ScenarioGrid.take (the cancellation re-slice primitive)
# ---------------------------------------------------------------------

def test_take_selects_rows_and_labels(toy):
    data, nets, init, apply_fn = toy
    grid = scenarios.ScenarioGrid.concat(
        _grid(nets[0], "ra", "a"), _grid(nets[1], "aayg", "b"),
        _grid(nets[0], "ra", "c", seed=7),
    )
    sub = grid.take([2, 0])
    assert sub.labels == [grid.labels[2], grid.labels[0]]
    assert len(sub) == 2
    for name in grid.scenarios._fields:
        whole = getattr(grid.scenarios, name)
        part = getattr(sub.scenarios, name)
        if whole is None:
            assert part is None
            continue
        np.testing.assert_array_equal(
            np.asarray(part), np.asarray(whole)[[2, 0]]
        )
    with pytest.raises(ValueError, match="1-D"):
        grid.take(np.zeros((2, 2), np.intp))
    # A taken sub-grid is a first-class grid: it runs, bit-identically
    # to the matching rows of the full grid's result.
    cfg = _cfg(n_rounds=2, local_epochs=1)
    whole_res = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    part_res = scenarios.run_grid(init, apply_fn, data, sub, cfg)
    np.testing.assert_array_equal(np.asarray(part_res.acc),
                                  np.asarray(whole_res.acc)[[2, 0]])


# ---------------------------------------------------------------------
# Sharded serving: devices= end-to-end through the server
# ---------------------------------------------------------------------

def _serving_shard_check(devices) -> None:
    """Serving on a ('grid',) mesh == unsharded serving == direct
    run_grid, bitwise — including a coalesced mixed-protocol dispatch."""
    data, nets, init, apply_fn = _setup()
    cfg = _cfg(n_rounds=2, local_epochs=1)
    requests = [
        _grid(nets[0], "ra", "r0"),
        _grid(nets[1], "aayg", "r1"),
        _grid(nets[1], "ra", "r2", seed=3),
    ]
    refs = [scenarios.run_grid(init, apply_fn, data, g, cfg)
            for g in requests]
    serve_cfg = serving.ServeConfig(max_batch=8, max_delay_s=0.25)
    plain = serving.ScenarioServer(init, apply_fn, data, cfg,
                                   serve=serve_cfg)
    with plain:
        unsharded = plain.serve(requests)
    sharded_srv = serving.ScenarioServer(init, apply_fn, data, cfg,
                                         serve=serve_cfg, devices=devices)
    with sharded_srv:
        sharded = sharded_srv.serve(requests)
    for got, mid, want in zip(sharded, unsharded, refs):
        _assert_same(got, want)
        _assert_same(got, mid)
        assert got.labels == want.labels
    # The sharded server really dispatched through the shard_map path.
    snap = sharded_srv.tracker.snapshot()
    assert snap["serve/dispatches"] >= 1


def test_sharded_serving_one_device_mesh_bit_identical(toy):
    """A 1-device ('grid',) mesh through the server's devices= hook is
    bit-identical to unsharded serving and direct run_grid — the sharded
    code path (hoist -> shard_map -> per-mesh program cache) end-to-end,
    runnable on any machine."""
    _serving_shard_check(devices=1)


def test_sharded_serving_multi_device_matches_unsharded():
    """Forced 8-host-device serving == unsharded serving (bitwise)."""
    if jax.device_count() >= 8:
        _serving_shard_check(devices=jax.devices())
        return
    if os.environ.get("CI"):
        pytest.skip("covered by the forced-8-device CI serve-stress job")
    # jax is already initialized with fewer devices: rerun the check in a
    # subprocess with the forced host-device flag (same pattern as
    # tests/test_sharding.py).
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--shard-selfcheck"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, (
        f"forced-8-device serving selfcheck failed:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )
    assert "SERVING-SHARD-SELFCHECK-OK" in proc.stdout


# ---------------------------------------------------------------------
# Priority / SLA scheduling
# ---------------------------------------------------------------------

def test_priority_request_skips_delay_window(toy):
    """With a 2s coalescing window, a priority request dispatches
    immediately (well under the window); a best-effort request submitted
    alone would sit out the full window."""
    data, nets, init, apply_fn = toy
    cfg = _cfg(n_rounds=2, local_epochs=1)
    grid = _grid(nets[0], label="hot")
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=8, max_delay_s=2.0),
    )
    server.warmup(grid)                   # no compile in the timed region
    with server:
        t0 = time.monotonic()
        res = server.submit(grid, priority=1).result(timeout=120)
        elapsed = time.monotonic() - t0
    assert res.labels == grid.labels
    assert elapsed < 1.5, (
        f"priority request waited {elapsed:.2f}s — it sat out the "
        "coalescing window"
    )


def test_near_deadline_request_shrinks_window(toy):
    """A best-effort request whose SLA is far tighter than max_delay_s is
    dispatched within ~half its slack, not held for the full window."""
    data, nets, init, apply_fn = toy
    cfg = _cfg(n_rounds=2, local_epochs=1)
    grid = _grid(nets[0], label="sla")
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=8, max_delay_s=2.0),
    )
    server.warmup(grid)
    with server:
        t0 = time.monotonic()
        res = server.submit(grid, deadline_s=1.0).result(timeout=120)
        elapsed = time.monotonic() - t0
    assert res.labels == grid.labels
    assert elapsed < 1.5, f"near-deadline request waited {elapsed:.2f}s"


# ---------------------------------------------------------------------
# _FairQueue scheduling units (no jax dispatch)
# ---------------------------------------------------------------------

def _req(cost=1, priority=0, tenant="default", t=0.0):
    # cost == len(grid); a plain list stands in for a ScenarioGrid here.
    return serving._Request(grid=[None] * cost, future=Future(),
                            t_submit=t, priority=priority, tenant=tenant)


def test_fair_queue_priority_before_fifo():
    q = serving._FairQueue()
    lo = [_req(t=i) for i in range(3)]
    hi = _req(priority=2, t=10.0)
    for r in lo:
        q.put(r)
    q.put(hi)                            # submitted LAST, served FIRST
    assert q.pop(timeout=1) is hi
    assert [q.pop(timeout=1) for _ in range(3)] == lo   # FIFO after that
    assert q.depth == 0


def test_fair_queue_weighted_shares():
    """3:1 tenant weights -> ~3:1 dispatch shares while both are backlogged
    (stride scheduling), FIFO preserved within each tenant."""
    q = serving._FairQueue({"gold": 3.0, "bronze": 1.0})
    gold = [_req(tenant="gold", t=i) for i in range(30)]
    bronze = [_req(tenant="bronze", t=i) for i in range(30)]
    for g, b in zip(gold, bronze):
        q.put(g)
        q.put(b)
    first20 = [q.pop(timeout=1) for _ in range(20)]
    n_gold = sum(1 for r in first20 if r.tenant == "gold")
    assert 13 <= n_gold <= 17, f"gold got {n_gold}/20, expected ~15"
    for tenant in ("gold", "bronze"):
        served = [r for r in first20 if r.tenant == tenant]
        assert served == sorted(served, key=lambda r: r.t_submit)


def test_fair_queue_idle_tenant_banks_no_credit():
    """A tenant idle while another drains the queue re-joins at the busy
    minimum: it does NOT get a catch-up burst that starves the incumbent."""
    q = serving._FairQueue({"a": 1.0, "b": 1.0})
    for i in range(10):                  # only "a" is active
        q.put(_req(tenant="a", t=i))
    for _ in range(10):
        assert q.pop(timeout=1).tenant == "a"
    # "b" arrives late; both stay backlogged from here on.
    for i in range(10):
        q.put(_req(tenant="a", t=10 + i))
        q.put(_req(tenant="b", t=10 + i))
    first8 = [q.pop(timeout=1) for _ in range(8)]
    n_b = sum(1 for r in first8 if r.tenant == "b")
    assert 3 <= n_b <= 5, (
        f"idle tenant took {n_b}/8 after re-joining — banked credit"
    )


def test_fair_queue_close_drain_and_shutdown_sentinel():
    q = serving._FairQueue()
    reqs = [_req(t=i) for i in range(3)]
    for r in reqs:
        q.put(r)
    assert q.close(drain=True) == []
    assert [q.pop(timeout=1) for _ in range(3)] == reqs
    assert q.pop(timeout=1) is serving._SHUTDOWN    # drained + closed
    with pytest.raises(serving.ServerStopped):
        q.put(_req())


def test_fair_queue_close_no_drain_returns_dropped():
    q = serving._FairQueue()
    reqs = [_req(t=i) for i in range(3)]
    for r in reqs:
        q.put(r)
    dropped = q.close(drain=False)
    assert sorted(dropped, key=id) == sorted(reqs, key=id)
    assert q.pop(timeout=1) is serving._SHUTDOWN


if __name__ == "__main__":
    if "--shard-selfcheck" in sys.argv:
        assert jax.device_count() >= 8, (
            f"needs 8 forced devices, have {jax.device_count()}"
        )
        _serving_shard_check(devices=jax.devices())
        print("SERVING-SHARD-SELFCHECK-OK")
