"""Chaos tier for the multi-replica router (DESIGN.md §14).

Unit coverage for the routing primitives (hash ring, circuit breaker,
grid signature, config validation), then transport-level chaos via
tests/_serving_faults.ChaosReplica: a replica killed mid-run, a flapping
replica, stalled and slow transports.  The invariants under every fault:
each accepted future terminates (result, `DeadlineExceeded`, cancel-ack,
or a terminal error), no future resolves twice, and every DELIVERED
result is a direct `run_grid`'s, within float32 round-off (a replica's
coalesced batch is another program; DESIGN.md §16).
"""
import threading
import time
from concurrent.futures import wait

import pytest

from _same import assert_same_semantics
from _serving_faults import ChaosReplica
from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.launch import router, serving

_PACKET_BITS = 32 * 64


def _setup(n_clients=3):
    data = synthetic.fed_image_classification(
        n_clients=n_clients, samples_per_client=20, seed=0
    )
    coords = topology.TABLE_II_COORDS[:n_clients]
    nets = [
        topology.make_network(
            coords, edge_density=d, packet_len_bits=_PACKET_BITS,
            n_clients=n_clients, tx_power_dbm=tx,
        )
        # The third net's weaker radios give it genuinely different
        # link_eps values (at 3 clients the two density variants coincide).
        for d, tx in ((0.6, 17.0), (0.8, 17.0), (0.8, 11.0))
    ]
    from repro.models import smallnets
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=16)
    return data, nets, init, smallnets.apply_mlp_clf


@pytest.fixture(scope="module")
def toy():
    return _setup()


def _cfg(**kw):
    kw.setdefault("n_rounds", 2)
    kw.setdefault("local_epochs", 1)
    kw.setdefault("seg_len", 64)
    return simulator.SimConfig(**kw)


def _grid(net, proto="ra", label="g", seed=0):
    return scenarios.ScenarioGrid.product(
        networks=[(label, net)], protocols=[(proto, "ra_normalized")],
        seeds=[seed],
    )


def _mk_router(toy, n=3, *, serve_kw=None, route_kw=None):
    """n chaos-wrapped in-process replicas behind one router (not yet
    started; call rt.warmup(...) then use `with rt:`)."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    serve_kw = dict(serve_kw or {})
    serve_kw.setdefault("max_batch", 4)
    serve_kw.setdefault("max_delay_s", 0.02)
    chaos = [
        ChaosReplica(router.InProcessReplica(
            f"replica{i}",
            serving.ScenarioServer(init, apply_fn, data, cfg,
                                   serve=serving.ServeConfig(**serve_kw)),
        ))
        for i in range(n)
    ]
    rt = router.ScenarioRouter(
        chaos, route=router.RouterConfig(**dict(route_kw or {}))
    )
    return rt, chaos, cfg


def _primary(rt, grid) -> str:
    return rt._ring.preference(router.grid_signature(grid))[0]


# ----------------------------------------------------------------------
# Units: ring, breaker, signature, config.
# ----------------------------------------------------------------------

def test_hash_ring_covers_and_remaps_minimally():
    names = [f"r{i}" for i in range(5)]
    ring = router._HashRing(names, vnodes=64)
    keys = [f"key-{i}" for i in range(300)]
    prefs = {k: ring.preference(k) for k in keys}
    for k, order in prefs.items():
        assert sorted(order) == sorted(names)          # full failover order
        assert order == ring.preference(k)             # deterministic
    # Removing one replica remaps ONLY the keys it owned; everyone else
    # keeps their primary.
    smaller = router._HashRing([n for n in names if n != "r2"], vnodes=64)
    for k in keys:
        if prefs[k][0] != "r2":
            assert smaller.preference(k)[0] == prefs[k][0]
        else:
            # Its keys fall to the old SECOND choice.
            assert smaller.preference(k)[0] == prefs[k][1]
    with pytest.raises(ValueError):
        router._HashRing([])
    with pytest.raises(ValueError):
        router._HashRing(["a", "a"])


def test_circuit_breaker_state_machine():
    b = router.CircuitBreaker(failures=3, cooldown_s=1.0)
    assert b.state == b.CLOSED and b.allow(now=0.0)
    b.record_failure(now=0.0)
    b.record_failure(now=0.0)
    b.record_success()                     # success resets the streak
    b.record_failure(now=1.0)
    b.record_failure(now=1.0)
    assert b.state == b.CLOSED
    b.record_failure(now=1.0)              # third consecutive: trips
    assert b.state == b.OPEN
    assert not b.allow(now=1.5)            # cooling down
    assert b.allow(now=2.5)                # half-open: THE probe
    assert b.state == b.HALF_OPEN
    assert not b.allow(now=2.5)            # one probe at a time
    b.record_failure(now=2.5)              # probe failed: re-open
    assert b.state == b.OPEN
    assert not b.allow(now=3.0)
    assert b.allow(now=4.0)                # next probe window
    b.record_success()
    assert b.state == b.CLOSED and b.allow(now=4.0)


def test_circuit_breaker_heartbeat_semantics():
    b = router.CircuitBreaker(failures=2, cooldown_s=1.0)
    b.on_ping(False, now=0.0)
    b.on_ping(False, now=0.0)              # failed pings trip it
    assert b.state == b.OPEN
    b.on_ping(True, now=0.5)               # still cooling: no effect
    assert b.state == b.OPEN
    b.on_ping(True, now=1.5)               # past cooldown: ping re-closes
    assert b.state == b.CLOSED
    # A successful ping while CLOSED must NOT reset the failure streak
    # (pings can pass while dispatches fail).
    b.record_failure(now=2.0)
    b.on_ping(True, now=2.0)
    b.record_failure(now=2.0)
    assert b.state == b.OPEN


def test_router_config_validation():
    for bad in (
        dict(vnodes=0), dict(max_attempts=0), dict(jitter=1.5),
        dict(jitter=-0.1), dict(hedge_slack_frac=0.0),
        dict(hedge_slack_frac=1.0), dict(tenant_quotas={"t": 0}),
    ):
        with pytest.raises(ValueError):
            router.RouterConfig(**bad)


def test_grid_signature_families(toy):
    data, nets, init, apply_fn = toy
    a = router.grid_signature(_grid(nets[0], "ra", "a", seed=0))
    # Same program family: different seed, label, topology values.
    assert router.grid_signature(_grid(nets[0], "ra", "x", seed=7)) == a
    assert router.grid_signature(_grid(nets[1], "ra", "y", seed=0)) == a
    # Different protocol: different dispatch group, different family.
    assert router.grid_signature(_grid(nets[0], "aayg", "z")) != a
    # A batch that is merely WIDER (only seed mapped) stays in the same
    # family: batch size must not scatter a family across replicas.
    seeds = scenarios.ScenarioGrid.product(
        networks=[("w", nets[0])], protocols=[("ra", "ra_normalized")],
        seeds=[0, 1, 2],
    )
    assert router.grid_signature(seeds) == a
    # A coalesced batch over DIFFERENT topologies maps the link field a
    # 1-row grid hoists: different compiled program, different signature.
    two = scenarios.ScenarioGrid.concat(
        _grid(nets[0], "ra", "p", seed=0), _grid(nets[2], "ra", "q", seed=1)
    )
    assert router.grid_signature(two) != a


# ----------------------------------------------------------------------
# Integration: routing, failover, chaos.
# ----------------------------------------------------------------------

def test_router_bit_identical_with_cache_affinity(toy):
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=3)
    pool = [_grid(nets[i % 2], "ra", f"g{i}", seed=i) for i in range(4)]
    refs = [scenarios.run_grid(init, apply_fn, data, g, cfg) for g in pool]
    rt.warmup(pool, fanout=1)
    with rt:
        futs = [rt.submit(g) for g in pool]
        for f, ref in zip(futs, refs):
            assert_same_semantics(f.result(timeout=300), ref)
    # One program family -> one replica (cache affinity): all four
    # requests landed on the same replica, no faults so no retries.
    assert sorted(c.submits for c in chaos) == [0, 0, 4]
    snap = rt.tracker.snapshot()
    assert snap["router/requests"] == 4
    assert snap["router/attempts"] == 4
    assert snap.get("router/retries", 0) == 0


def test_replica_killed_mid_run_fails_over(toy):
    """The chaos headline: kill the loaded replica's server mid-run.
    In-flight requests fail over to survivors; everything delivers a
    direct run's result; the dead replica's breaker opens."""
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=3, route_kw=dict(
        max_attempts=4, backoff_base_s=0.01, breaker_cooldown_s=0.3,
        heartbeat_s=0.05, attempt_timeout_s=60.0,
    ))
    pool = [_grid(nets[i % 2], "ra", f"k{i}", seed=i) for i in range(6)]
    refs = [scenarios.run_grid(init, apply_fn, data, g, cfg) for g in pool]
    rt.warmup(pool, fanout=3)              # survivors are warm too
    victim = _primary(rt, pool[0])
    with rt:
        futs = [rt.submit(g) for g in pool[:3]]
        # Kill the primary MID-RUN: transport down AND its server hard-
        # stopped, so requests already inside it fail with ServerStopped
        # and must fail over.
        rep = next(c for c in chaos if c.name == victim)
        rep.kill()
        rep.inner.server.stop(drain=False)
        futs += [rt.submit(g) for g in pool[3:]]
        for f, ref in zip(futs, refs):
            assert_same_semantics(f.result(timeout=300), ref)
        # Heartbeats notice the corpse: breaker opens.
        deadline = time.monotonic() + 5.0
        while (rt.breaker(victim).state != router.CircuitBreaker.OPEN
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert rt.breaker(victim).state == router.CircuitBreaker.OPEN
    snap = rt.tracker.snapshot()
    assert snap["router/requests"] == 6
    assert snap["router/breaker_opens"] >= 1
    # The kill actually cost retries (some request met the dead replica).
    assert snap.get("router/retries", 0) >= 1


def test_flapping_replica_exactly_once_delivery(toy):
    """One replica flaps (kill/revive loop) while traffic flows: every
    future terminates, delivered results are direct runs', and exactly-
    once holds (late/duplicate results are discarded, never delivered)."""
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=3, route_kw=dict(
        max_attempts=5, backoff_base_s=0.01, breaker_cooldown_s=0.1,
        heartbeat_s=0.03, attempt_timeout_s=30.0,
    ))
    pool = [_grid(nets[i % 2], "ra", f"f{i}", seed=i) for i in range(8)]
    refs = [scenarios.run_grid(init, apply_fn, data, g, cfg) for g in pool]
    rt.warmup(pool, fanout=3)
    flapper = next(c for c in chaos if c.name == _primary(rt, pool[0]))
    stop_flap = threading.Event()

    def flap_loop():
        while not stop_flap.is_set():
            flapper.kill()
            time.sleep(0.08)
            flapper.revive()
            time.sleep(0.08)

    t = threading.Thread(target=flap_loop, daemon=True)
    with rt:
        t.start()
        futs = []
        for g in pool:
            futs.append(rt.submit(g))
            time.sleep(0.03)
        done, not_done = wait(futs, timeout=300)
        stop_flap.set()
        t.join(timeout=5)
        assert not not_done, f"{len(not_done)} futures never terminated"
        for f, ref in zip(futs, refs):
            assert_same_semantics(f.result(), ref)   # all delivered, all correct
    snap = rt.tracker.snapshot()
    assert snap["router/requests"] == 8


def test_stalled_transport_times_out_and_retries(toy):
    """A stalled transport (pings pass, submits hang) is caught by the
    attempt timeout, retried on a survivor, and the request delivers."""
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=2, route_kw=dict(
        max_attempts=3, attempt_timeout_s=0.3, backoff_base_s=0.01,
    ))
    g = _grid(nets[0], "ra", "s0")
    ref = scenarios.run_grid(init, apply_fn, data, g, cfg)
    rt.warmup([g], fanout=2)
    victim = next(c for c in chaos if c.name == _primary(rt, g))
    other = next(c for c in chaos if c.name != victim.name)
    with rt:
        victim.stall()
        f = rt.submit(g)
        assert_same_semantics(f.result(timeout=300), ref)
    assert victim.submits == 1 and other.submits == 1
    snap = rt.tracker.snapshot()
    assert snap["router/timeouts"] >= 1
    assert snap["router/retries"] >= 1


def test_slow_transport_hedges_near_deadline(toy):
    """A slow-but-alive replica: the hedge fires near the deadline, the
    fast secondary wins the resolution race, the slow result is
    discarded — delivered exactly once."""
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=2, route_kw=dict(
        max_attempts=2, attempt_timeout_s=None, hedge_slack_frac=0.5,
    ))
    g = _grid(nets[0], "ra", "h0")
    ref = scenarios.run_grid(init, apply_fn, data, g, cfg)
    rt.warmup([g], fanout=2)
    victim = next(c for c in chaos if c.name == _primary(rt, g))
    with rt:
        victim.slow(3.0)
        f = rt.submit(g, deadline_s=4.0)
        t0 = time.monotonic()
        assert_same_semantics(f.result(timeout=300), ref)
        # Delivered by the hedge well before the slow replica's 3s.
        assert time.monotonic() - t0 < 2.9
        time.sleep(1.2)                  # let the slow result lose the race
    snap = rt.tracker.snapshot()
    assert snap["router/hedges"] == 1
    # The slow loser never double-delivers: it was either cancelled when
    # the winner resolved the future, or its late result was discarded.
    assert (snap.get("router/results_discarded", 0)
            + snap.get("router/attempts_cancelled", 0)) >= 1


def test_router_deadline_fires_while_all_replicas_stalled(toy):
    """With every transport stalled, the ROUTER's own deadline timer
    fails the request with `DeadlineExceeded` — no dependence on any
    replica's reaper being alive."""
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=2, route_kw=dict(
        max_attempts=2, attempt_timeout_s=30.0,
    ))
    g = _grid(nets[0], "ra", "d0")
    with rt:
        for c in chaos:
            c.stall()
        t0 = time.monotonic()
        f = rt.submit(g, deadline_s=0.4)
        with pytest.raises(serving.DeadlineExceeded):
            f.result(timeout=5.0)
        assert time.monotonic() - t0 < 2.0
        for c in chaos:
            c.revive()
    snap = rt.tracker.snapshot()
    assert snap["router/deadline_exceeded"] == 1


def test_global_tenant_quota_spans_replicas(toy):
    """Quota counts OUTSTANDING scenarios across all replicas: reserved
    at submit, released when the client future terminates."""
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=2, route_kw=dict(
        max_attempts=2, attempt_timeout_s=30.0,
        tenant_quotas={"capped": 1},
    ))
    g = _grid(nets[0], "ra", "q0")
    ref = scenarios.run_grid(init, apply_fn, data, g, cfg)
    rt.warmup([g], fanout=2)
    with rt:
        for c in chaos:
            c.stall()                    # park the first request in flight
        f1 = rt.submit(g, tenant="capped")
        with pytest.raises(router.QuotaExceeded):
            rt.submit(_grid(nets[0], "ra", "q1"), tenant="capped")
        # Other tenants are not throttled by it.
        f_other = rt.submit(_grid(nets[0], "ra", "q2"))
        for c in chaos:
            c.revive()                   # stalled futures cancelled ->
        assert_same_semantics(f1.result(timeout=300), ref)   # retry delivers
        assert_same_semantics(f_other.result(timeout=300), ref)
        # Quota released on termination: submit admits again.
        f3 = rt.submit(_grid(nets[0], "ra", "q3"), tenant="capped")
        assert_same_semantics(f3.result(timeout=300), ref)
    snap = rt.tracker.snapshot()
    assert snap["router/quota_rejected"] == 1


def test_router_input_hardening(toy):
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(
        toy, n=2, serve_kw=dict(tenant_weights={"alice": 2.0}),
    )
    g = _grid(nets[0], "ra", "v0")
    with rt:
        with pytest.raises(serving.InvalidRequest):
            rt.submit(g, deadline_s=0.0)
        with pytest.raises(serving.InvalidRequest):
            rt.submit(g, deadline_s=float("nan"))
        with pytest.raises(serving.InvalidRequest):
            rt.submit(g, priority=float("nan"))
        with pytest.raises(serving.UnknownTenant):
            rt.submit(g, tenant="mallory")
        with pytest.raises(scenarios.AdmissionError):
            rt.submit(g.take([]))
    # None of the rejects leaked registry entries or quota.
    assert not rt._outstanding
    snap = rt.tracker.snapshot()
    assert snap.get("router/stopped_requests", 0) == 0


def test_stop_drain_serves_everything_then_hard_stop_rejects(toy):
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=2)
    pool = [_grid(nets[i % 2], "ra", f"t{i}", seed=i) for i in range(4)]
    refs = [scenarios.run_grid(init, apply_fn, data, g, cfg) for g in pool]
    rt.warmup(pool, fanout=2)
    rt.start()
    futs = [rt.submit(g) for g in pool]
    rt.stop()                            # drain default
    for f, ref in zip(futs, refs):
        assert f.done()
        assert_same_semantics(f.result(), ref)
    with pytest.raises(serving.ServerStopped):
        rt.submit(pool[0])
    rt.stop()                            # idempotent

    # Hard stop: parked requests fail with ServerStopped before stop
    # returns — none is left pending, and none is delivered late.
    rt2, chaos2, _ = _mk_router(toy, n=2, route_kw=dict(
        attempt_timeout_s=30.0,
    ))
    rt2.start()
    for c in chaos2:
        c.stall()
    parked = [rt2.submit(g) for g in pool[:2]]
    rt2.stop(drain=False)
    assert all(f.done() for f in parked)
    for f in parked:
        with pytest.raises(serving.ServerStopped):
            f.result(timeout=1)          # hang guard only: already done
    with pytest.raises(serving.ServerStopped):
        rt2.submit(pool[0])
    assert not rt2._outstanding
    snap = rt2.tracker.snapshot()
    assert snap["router/stopped_requests"] == 2
    assert snap["router/requests"] == 2


def test_drain_replica_planned_failover(toy):
    """drain_replica removes one replica from routing and stops it while
    the survivors keep serving its program families."""
    data, nets, init, apply_fn = toy
    rt, chaos, cfg = _mk_router(toy, n=3)
    g = _grid(nets[0], "ra", "p0")
    ref = scenarios.run_grid(init, apply_fn, data, g, cfg)
    rt.warmup([g], fanout=3)
    victim = _primary(rt, g)
    rep = next(c for c in chaos if c.name == victim)
    with rt:
        assert_same_semantics(rt.submit(g).result(timeout=300), ref)
        assert rep.submits == 1
        rt.drain_replica(victim)
        assert rep.inner.server._stopped
        # The family now lands on a survivor; the drained replica sees
        # no new traffic.
        assert_same_semantics(rt.submit(g).result(timeout=300), ref)
        assert rep.submits == 1
        with pytest.raises(KeyError):
            rt.drain_replica("no-such-replica")
    snap = rt.tracker.snapshot()
    assert snap["router/drains"] == 1
