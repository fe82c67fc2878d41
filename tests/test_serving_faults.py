"""Fault-injection tier for the serving engine (DESIGN.md §12).

Uses tests/_serving_faults.py to poison or stall specific dispatches and
asserts the server's survival guarantees: a poisoned dispatch fails only
its own batch, a stalled dispatch trips per-request deadlines via the
reaper (not the wedged dispatcher), a cancelled request is re-sliced out
of its coalesced batch before touching the device, and both stop flavors
leave no future forever-pending.
"""
import threading
import time
from concurrent.futures import CancelledError, wait

import pytest

from _same import assert_same_semantics
from _serving_faults import install
from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.launch import serving

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


def test_poisoned_dispatch_fails_only_the_poisoned_request(toy):
    """Coalesced dispatch 0 raises: each member is retried INDIVIDUALLY
    (blast-radius shrink, DESIGN.md §12) — the request whose solo retry
    also raises fails, its innocent neighbor is served with a direct
    run's result (within float32 round-off); the next submit is served
    normally."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    boom = RuntimeError("injected dispatch failure")
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=8, max_delay_s=0.25),
    )
    # Call 0 is the coalesced [a, b] batch; call 1 is a's solo retry
    # (poisoned again -> a truly fails); call 2 is b's solo retry
    # (clean -> b is served); call 3 is c.
    probe = install(server, raise_on={0: boom, 1: boom})
    ref_b = scenarios.run_grid(init, apply_fn, data,
                               _grid(nets[1], label="b"), cfg)
    ref_c = scenarios.run_grid(init, apply_fn, data,
                               _grid(nets[0], label="c"), cfg)
    with server:
        fa = server.submit(_grid(nets[0], "ra", "a"))
        fb = server.submit(_grid(nets[1], "ra", "b"))
        with pytest.raises(RuntimeError, match="injected"):
            fa.result(timeout=120)
        assert_same_semantics(fb.result(timeout=300), ref_b)
        fc = server.submit(_grid(nets[0], "ra", "c"))
        assert_same_semantics(fc.result(timeout=300), ref_c)
    assert probe.calls == 4
    assert probe.rows == [2, 1, 1, 1]
    snap = server.tracker.snapshot()
    assert snap["serve/dispatch_errors"] == 1
    assert snap["serve/dispatch_retries"] == 2
    assert snap["serve/requests"] == 3


def test_single_request_dispatch_failure_is_not_retried(toy):
    """A poisoned dispatch with ONE member has no innocent neighbors:
    the failure propagates without a retry dispatch."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=1, max_delay_s=0.01),
    )
    probe = install(server, raise_on={0: RuntimeError("injected solo")})
    with server:
        fa = server.submit(_grid(nets[0], "ra", "a"))
        with pytest.raises(RuntimeError, match="injected solo"):
            fa.result(timeout=120)
    assert probe.calls == 1
    snap = server.tracker.snapshot()
    assert snap["serve/dispatch_errors"] == 1
    assert snap.get("serve/dispatch_retries", 0) == 0


def test_deadline_race_between_dispatch_and_delivery_is_discarded(toy):
    """A request whose deadline expires AFTER the dispatcher's liveness
    re-slice but BEFORE its dispatch returns is failed by the reaper with
    `DeadlineExceeded`; the computed result is discarded
    (`serve/results_discarded`), never delivered twice."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=4, max_delay_s=0.01),
    )
    server.warmup(_grid(nets[0], label="a"))
    probe = install(server, stall_on={0: 1.0})
    with server:
        t0 = time.monotonic()
        fa = server.submit(_grid(nets[0], "ra", "a"), deadline_s=0.3)
        with pytest.raises(serving.DeadlineExceeded):
            fa.result(timeout=0.8)
        # The reaper fired mid-stall, not after the dispatch resolved.
        assert time.monotonic() - t0 < 0.9
        fb = server.submit(_grid(nets[0], "ra", "b"))
        assert fb.result(timeout=300) is not None
    # The expired request WAS dispatched (the race is post-re-slice) ...
    assert probe.calls == 2
    assert probe.rows[0] == 1
    snap = server.tracker.snapshot()
    assert snap["serve/deadline_exceeded"] == 1
    # ... and its late result was discarded, not delivered.
    assert snap["serve/results_discarded"] == 1


def test_stalled_dispatch_trips_deadlines_without_wedging(toy):
    """While dispatch 0 stalls, queued requests' deadlines still fire
    (reaper thread), their rows never reach the device, and the batcher
    keeps serving afterwards."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=8, max_delay_s=0.01),
    )
    server.warmup(_grid(nets[0], label="warm"))
    probe = install(server, stall_on={0: 1.5})
    with server:
        fa = server.submit(_grid(nets[0], "ra", "a"))
        time.sleep(0.2)               # let A reach the stalled dispatcher
        t0 = time.monotonic()
        fb = server.submit(_grid(nets[0], "ra", "b"), deadline_s=0.3)
        fc = server.submit(_grid(nets[0], "ra", "c"), deadline_s=0.3)
        with pytest.raises(serving.DeadlineExceeded):
            fb.result(timeout=1.0)
        with pytest.raises(serving.DeadlineExceeded):
            fc.result(timeout=1.0)
        # Deadlines fired DURING the stall, not after it resolved.
        assert time.monotonic() - t0 < 1.0
        assert fa.result(timeout=300) is not None
        fd = server.submit(_grid(nets[0], "ra", "d"))
        assert fd.result(timeout=300) is not None
    # Only A and D ever touched the runner: the expired batch was skipped
    # wholesale by the dispatcher's liveness check.
    assert probe.calls == 2
    snap = server.tracker.snapshot()
    assert snap["serve/deadline_exceeded"] == 2


def test_cancel_before_dispatch_reslices_coalesced_batch(toy):
    """Cancelling one request of a coalesced pending batch drops exactly
    its rows (ScenarioGrid.take re-slice); the surviving request's result
    is a direct run's (within float32 round-off)."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    ref = scenarios.run_grid(init, apply_fn, data,
                             _grid(nets[1], label="keep"), cfg)
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=8, max_delay_s=0.15),
    )
    probe = install(server, stall_on={0: 1.2})
    with server:
        fa = server.submit(_grid(nets[0], "ra", "a"))
        time.sleep(0.3)               # A is in the stalled dispatcher
        f_cancel = server.submit(_grid(nets[0], "ra", "cancel-me"))
        f_keep = server.submit(_grid(nets[1], "ra", "keep"))
        # Wait out the coalescing window so both requests are provably
        # inside one prepared _Dispatch (the dispatcher is still stalled),
        # THEN cancel: the drop must happen at dispatch time, by re-slice.
        time.sleep(0.35)
        assert f_cancel.cancel()      # still pending: cancel must win
        assert_same_semantics(f_keep.result(timeout=300), ref)
        assert fa.result(timeout=300) is not None
        with pytest.raises(CancelledError):
            f_cancel.result(timeout=1)
    # The coalesced 2-row batch was re-sliced to 1 surviving row.
    assert probe.calls == 2
    assert probe.rows[-1] == 1
    assert probe.labels[-1] == ["keep/ra+ra_normalized"]
    snap = server.tracker.snapshot()
    assert snap["serve/dropped_before_dispatch"] == 1


def test_submit_input_hardening(toy):
    """Malformed scheduling inputs fail at submit with NAMED errors —
    never undefined scheduler behavior (a NaN priority would poison every
    queue-ordering comparison; a zero deadline is born expired)."""
    data, nets, init, apply_fn = toy
    server = serving.ScenarioServer(
        init, apply_fn, data, _cfg(),
        serve=serving.ServeConfig(tenant_weights={"alice": 2.0}),
    )
    g = _grid(nets[0], "ra", "v")
    with server:
        for bad_deadline in (0.0, -1.0, float("nan"), float("inf")):
            with pytest.raises(serving.InvalidRequest):
                server.submit(g, deadline_s=bad_deadline)
        for bad_priority in (float("nan"), 1.5, "high"):
            with pytest.raises(serving.InvalidRequest):
                server.submit(g, priority=bad_priority)
        # With a declared roster, unknown tenants are rejected by name;
        # the default tenant is always admitted.
        with pytest.raises(serving.UnknownTenant):
            server.submit(g, tenant="mallory")
        assert server.submit(g, tenant="alice").result(timeout=300)
        assert server.submit(g).result(timeout=300)
    assert server.tracker.snapshot()["serve/requests"] == 2
    # NaN / non-positive fair-share weights are config errors, up front.
    for bad in ({"a": float("nan")}, {"a": 0.0}, {"a": -1.0}):
        with pytest.raises(ValueError):
            serving.ServeConfig(tenant_weights=bad)


def test_hard_stop_fails_all_pending_futures(toy):
    """stop(drain=False): queued, coalesced, and in-flight requests all
    fail with ServerStopped before stop returns — none is left pending,
    and the in-flight dispatch's late result is discarded; new submits
    are rejected."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=1, max_delay_s=0.01),
    )
    probe = install(server, stall_on={0: 1.0})
    server.start()
    f_inflight = server.submit(_grid(nets[0], "ra", "a"))
    time.sleep(0.2)                   # A is executing (stalled)
    f_queued = [server.submit(_grid(nets[0], "ra", f"q{i}"))
                for i in range(3)]
    pending = [f_inflight, *f_queued]
    server.stop(drain=False)
    # Every future was failed by stop itself, not by a later dispatch.
    assert all(f.done() for f in pending)
    for f in pending:
        with pytest.raises(serving.ServerStopped):
            f.result(timeout=1)       # hang guard only: already done
    # Only the stalled dispatch ever reached the runner; the queued
    # requests were failed without running.
    assert probe.calls == 1
    with pytest.raises(serving.ServerStopped):
        server.submit(_grid(nets[0], "ra", "late"))
    snap = server.tracker.snapshot()
    assert snap["serve/stopped_requests"] == 4
    assert snap["serve/requests"] == 4


def test_drain_stop_serves_everything_accepted(toy):
    """stop(drain=True): every accepted request resolves with a direct
    run's result (coalesced batches of 2 against batches of 1: within
    float32 round-off)."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    reqs = [_grid(nets[i % 2], "ra", f"r{i}", seed=i) for i in range(4)]
    refs = [scenarios.run_grid(init, apply_fn, data, g, cfg) for g in reqs]
    server = serving.ScenarioServer(
        init, apply_fn, data, cfg,
        serve=serving.ServeConfig(max_batch=2, max_delay_s=0.05),
    )
    server.start()
    futs = [server.submit(g) for g in reqs]
    server.stop()                     # drain default
    for f, ref in zip(futs, refs):
        assert f.done()
        assert_same_semantics(f.result(), ref)
    server.stop()                     # idempotent


def test_submit_stop_race_never_leaves_pending_futures(toy):
    """Threads racing submit against stop: every accepted future
    terminates (result or ServerStopped) — none is left pending."""
    data, nets, init, apply_fn = toy
    cfg = _cfg()
    grid = _grid(nets[0], label="race")
    for trial, drain in enumerate((True, False, True, False)):
        server = serving.ScenarioServer(
            init, apply_fn, data, cfg,
            serve=serving.ServeConfig(max_batch=4, max_delay_s=0.005),
        )
        server.warmup(grid)
        server.start()
        futures, rejected = [], []
        stop_now = threading.Event()

        def submitter():
            while not stop_now.is_set():
                try:
                    futures.append(server.submit(grid))
                except serving.ServerStopped:
                    rejected.append(1)
                    return

        threads = [threading.Thread(target=submitter) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05 * (trial + 1))
        server.stop(drain=drain)
        stop_now.set()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        done, not_done = wait(futures, timeout=120)
        assert not not_done, f"{len(not_done)} futures never terminated"
        for f in done:
            exc = f.exception(timeout=0)
            assert exc is None or isinstance(exc, serving.ServerStopped)
