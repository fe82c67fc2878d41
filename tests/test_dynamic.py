"""Dynamic-network scenario axes (DESIGN.md §8) + grid-engine regressions.

Four layers:

  * equivalence — a static scenario expressed through the dynamic
    machinery (T=1 schedule, all-ones participation mask, uniform
    local-epochs vector) reproduces the static path's semantics, per
    protocol, within float32 round-off (a different program); and the
    static grid is bit for bit the scalar `scenarios.simulate` (the same
    program);
  * sampling semantics — a sampled-out client's parameters are untouched
    by local training AND by every protocol's aggregation;
  * engine regressions — the four grid-engine bugs fixed alongside
    (stale/crashing rho through `concat`, NaN-blind uniformity hoisting,
    colliding labels, seg_len vs packet_len_bits inconsistency);
  * sharding — a dynamic grid dispatched through a device mesh keeps the
    single-device vmap path's semantics (the CI sharding job runs this
    module under 8 forced host devices).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _same import assert_same_program, assert_same_semantics
from repro.core import protocols, routing, topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.models import smallnets

N_CLIENTS = 3
N_ROUNDS = 3
EPOCHS = 2


def _toy_setup(n_clients=N_CLIENTS):
    data = synthetic.fed_image_classification(
        n_clients=n_clients, samples_per_client=20, seed=0
    )
    net = topology.make_network(
        topology.TABLE_II_COORDS[:n_clients], edge_density=0.8,
        packet_len_bits=25_000, n_clients=n_clients, tx_power_dbm=17.0,
    )
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=16)
    return data, net, init, smallnets.apply_mlp_clf


@pytest.fixture(scope="module")
def toy():
    return _toy_setup()


def _cfg(**kw):
    kw.setdefault("n_rounds", N_ROUNDS)
    kw.setdefault("local_epochs", EPOCHS)
    kw.setdefault("seg_len", 64)
    kw.setdefault("cfl_aggregator", 0)
    return simulator.SimConfig(**kw)


ALL_PROTOCOLS = [("ra", "ra_normalized"), ("ra", "substitution"),
                 ("aayg", "ra_normalized"), ("cfl", "ra_normalized"),
                 ("ideal_cfl", "ra_normalized"), ("none", "ra_normalized")]


# ---------------------------------------------------------------------------
# Equivalence: static == dynamic-with-neutral-axes, per protocol.
# ---------------------------------------------------------------------------
def test_static_grid_is_prerefactor_path_bitwise(toy):
    """The no-dynamic-axes grid traces the static program, and the scalar
    `scenarios.simulate` runs that same program: bitwise equal."""
    data, net, init, apply_fn = toy
    cfg = _cfg(protocol="ra", seed=3)
    want = scenarios.simulate(init, apply_fn, data, net, cfg)
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        seeds=[3], aggregator=0,
    )
    assert not grid.scenario(0).is_dynamic
    got = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    assert_same_program(got.result(0), want)


def test_neutral_dynamic_axes_bitwise_static(toy):
    """T=1 schedule + all-ones participation + uniform local_epochs vector
    == the static grid within float32 round-off, for every protocol
    branch."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    static = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=ALL_PROTOCOLS, seeds=[0, 1],
        aggregator=0,
    )
    dyn = scenarios.ScenarioGrid.product(
        schedules=[("toy", np.asarray(net.link_eps, np.float32)[None])],
        protocols=ALL_PROTOCOLS, seeds=[0, 1],
        participation=[("full", np.ones((1, N_CLIENTS), np.float32))],
        local_epochs=np.full((N_CLIENTS,), EPOCHS, np.int32),
        aggregator=0,
    )
    assert dyn.scenario(0).is_dynamic
    ref = scenarios.run_grid(init, apply_fn, data, static, cfg)
    got = scenarios.run_grid(init, apply_fn, data, dyn, cfg)
    assert_same_semantics(got, ref)


def test_allones_participation_alone_is_noop(toy):
    """participation mask = all-ones (and nothing else dynamic) leaves
    trajectories unchanged within float32 round-off."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    base = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        aggregator=0,
    )
    masked = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        participation=[("full", np.ones((N_CLIENTS,), np.float32))],
        aggregator=0,
    )
    assert_same_semantics(
        scenarios.run_grid(init, apply_fn, data, masked, cfg),
        scenarios.run_grid(init, apply_fn, data, base, cfg),
    )


def test_t1_schedule_equals_static(toy):
    """A length-1 topology schedule (round t reads entry t % 1) is the
    static scenario, within float32 round-off."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    static = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        aggregator=0,
    )
    sched = scenarios.ScenarioGrid.product(
        schedules=[("toy", net)], protocols=[("ra", "ra_normalized")],
        aggregator=0,
    )
    assert_same_semantics(
        scenarios.run_grid(init, apply_fn, data, sched, cfg),
        scenarios.run_grid(init, apply_fn, data, static, cfg),
    )


# ---------------------------------------------------------------------------
# Sampling semantics.
# ---------------------------------------------------------------------------
def _first_round_rows(sim, scen):
    """Every client's segment row before and after round 0."""
    state = jax.jit(sim.init_scan)(scen)
    after, _ = jax.jit(sim.advance_chunk)(state, scen, jnp.int32(0))
    return np.asarray(state["w"]), np.asarray(after["w"])


@pytest.mark.parametrize("protocol,mode", ALL_PROTOCOLS)
def test_sampled_out_client_untouched(toy, protocol, mode):
    """A sampled-out client neither trains nor receives: its segment row
    survives a whole round bitwise, under every protocol."""
    data, net, init, apply_fn = toy
    sim = simulator.build_sim(init, apply_fn, data, seg_len=64,
                              local_epochs=EPOCHS, n_rounds=N_ROUNDS)
    cfg = _cfg(protocol=protocol, mode=mode, cfl_aggregator=1, seed=7)
    mask = np.array([0.0, 1.0, 1.0], np.float32)     # client 0 sampled out
    scen = simulator.make_scenario(net, cfg, participation=mask)
    before, after = _first_round_rows(sim, scen)
    assert_same_program(after[0], before[0])
    # ...while sampled-in clients did move (training happened).
    assert not np.array_equal(before[1], after[1])


def test_heterogeneous_epochs_masked_scan(toy):
    """local_epochs=[0, 1, max]: epoch-0 client is frozen through training
    (bitwise), epoch-1 client matches a run with local_epochs=1 and the
    max-epoch client a run without the vector (protocol none; other
    programs, so within float32 round-off)."""
    data, net, init, apply_fn = toy
    sim = simulator.build_sim(init, apply_fn, data, seg_len=64,
                              local_epochs=EPOCHS, n_rounds=1)
    sim1 = simulator.build_sim(init, apply_fn, data, seg_len=64,
                               local_epochs=1, n_rounds=1)
    cfg = _cfg(protocol="none", seed=7)
    epochs = np.array([0, 1, EPOCHS], np.int32)
    scen = simulator.make_scenario(net, cfg, local_epochs=epochs)
    scen_plain = simulator.make_scenario(net, cfg)
    before, after = _first_round_rows(sim, scen)
    _, ref1 = _first_round_rows(sim1, scen_plain)
    _, reffull = _first_round_rows(sim, scen_plain)
    assert_same_program(after[0], before[0])
    assert_same_semantics(after[1], ref1[1])
    assert_same_semantics(after[2], reffull[2])
    assert not np.array_equal(after[1], after[2])   # the epochs count


def test_cfl_sampled_out_aggregator_never_zeroes_models():
    """C-FL's star center ignores its own mask entry (it is infrastructure:
    the round cannot run without it).  Regression: with the aggregator
    sampled out and every participating uplink failing, the old masking
    order collapsed the normalization denominator to ~0 and broadcast
    all-zero segments to participating receivers."""
    key = jax.random.PRNGKey(0)
    n, l, k = 3, 2, 4
    w = jax.numpy.asarray(
        jax.random.normal(key, (n, l, k)) + 3.0
    )                                       # bounded away from 0
    p = np.full((n,), 1.0 / n, np.float32)
    # Asymmetric routing: uplinks from clients 1, 2 to aggregator 0 ALWAYS
    # fail; downlinks from 0 always succeed.
    rho = np.eye(n, dtype=np.float32)
    rho[0, :] = 1.0
    mask = np.array([0.0, 1.0, 1.0], np.float32)    # aggregator sampled out
    for mode_id in (0, 1):                  # ra_normalized, substitution
        out = protocols.cfl_round_seg(
            w, jax.numpy.asarray(p), jax.numpy.asarray(rho),
            jax.random.PRNGKey(3), jax.numpy.asarray(mode_id),
            jax.numpy.asarray(0), participation=jax.numpy.asarray(mask),
        )
        out = np.asarray(out)
        assert np.isfinite(out).all()
        assert (np.abs(out) > 1e-3).all()   # no zeroed segments anywhere
        # With no participating uplink, the served global model is exactly
        # the server's held model: every receiver sees its own or w[0].
        for recv in range(n):
            for seg in range(l):
                assert (np.allclose(out[recv, seg], np.asarray(w)[0, seg])
                        or np.allclose(out[recv, seg],
                                       np.asarray(w)[recv, seg]))


def test_dynamic_grid_runs_and_differs(toy):
    """A real churn + sampling grid runs finite and actually changes the
    trajectory (the axes are live, not decorative)."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    churn = topology.markov_link_schedule(net, N_ROUNDS, p_drop=0.5,
                                          p_recover=0.5, seed=1)
    half = scenarios.sampling_schedule(N_CLIENTS, N_ROUNDS, 0.67, seed=2)
    static = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        aggregator=0,
    )
    dyn = scenarios.ScenarioGrid.product(
        schedules=[("churn", churn)], protocols=[("ra", "ra_normalized")],
        participation=[("half", half)], aggregator=0,
    )
    ref = scenarios.run_grid(init, apply_fn, data, static, cfg)
    got = scenarios.run_grid(init, apply_fn, data, dyn, cfg)
    assert np.isfinite(got.acc).all()
    assert not np.array_equal(got.acc, ref.acc)


# ---------------------------------------------------------------------------
# Schedule builders.
# ---------------------------------------------------------------------------
def test_markov_schedule_properties(toy):
    _, net, _, _ = toy
    base = np.asarray(net.link_eps, np.float32)
    zero = topology.markov_link_schedule(net, 4, p_drop=0.0, seed=3)
    np.testing.assert_array_equal(
        zero, np.broadcast_to(base[None], zero.shape)
    )
    churn = topology.markov_link_schedule(net, 6, p_drop=0.6, p_recover=0.4,
                                          seed=3)
    assert churn.shape == (6,) + base.shape
    np.testing.assert_array_equal(churn[0], base)      # starts all-on
    # Every entry is the base matrix with some links zeroed, symmetrically.
    gate = np.asarray(churn != 0.0)
    np.testing.assert_array_equal(gate, np.transpose(gate, (0, 2, 1)))
    assert ((churn == 0.0) | (churn == base[None])).all()
    assert (churn[1:] == 0.0).any()                    # some link dropped
    with pytest.raises(ValueError):
        topology.markov_link_schedule(net, 2, p_drop=1.5)


def test_fading_schedule_properties(toy):
    _, net, _, _ = toy
    base = np.asarray(net.link_eps)
    still = topology.fading_per_schedule(net, 2, shadow_sigma_db=0.0, seed=5)
    np.testing.assert_allclose(still[0], base, rtol=1e-5, atol=1e-7)
    faded = topology.fading_per_schedule(net, 3, shadow_sigma_db=6.0, seed=5)
    assert faded.shape == (3,) + base.shape
    assert (faded >= 0.0).all() and (faded <= 1.0).all()
    # Adjacency is fixed: no new links appear (a deep fade may underflow a
    # weak link's packet-success rate to exactly 0, so the reverse can
    # happen).
    assert (faded[:, base == 0.0] == 0.0).all()
    assert not np.array_equal(faded[0], faded[1])          # per-round draws


def test_sampling_schedule_properties():
    full = scenarios.sampling_schedule(5, 3, 1.0, seed=0)
    np.testing.assert_array_equal(full, np.ones((3, 5), np.float32))
    half = scenarios.sampling_schedule(10, 8, 0.5, seed=1)
    assert half.shape == (8, 10)
    np.testing.assert_array_equal(half.sum(axis=1), np.full(8, 5.0))
    with pytest.raises(ValueError):
        scenarios.sampling_schedule(10, 2, 0.0)


# ---------------------------------------------------------------------------
# Grid-engine regressions (the four bugs fixed alongside).
# ---------------------------------------------------------------------------
def test_concat_recomputes_rho_after_repad(toy):
    """Concatenating a prepare()d grid (or grids of differing V) must not
    carry a stale rho: concat drops it and prepare() rederives it from the
    re-padded link_eps."""
    _, net, _, _ = toy
    big = topology.make_network(
        np.concatenate([topology.TABLE_II_COORDS[:3],
                        topology.TABLE_II_COORDS[5:8]]),
        edge_density=0.6, packet_len_bits=25_000, n_clients=3,
        tx_power_dbm=17.0,
    )
    small = scenarios.ScenarioGrid.product(networks=[("small", net)])
    large = scenarios.ScenarioGrid.product(networks=[("big", big)])
    # Simulate a prepared grid: a batched rho of the UNPADDED small V.
    rho_small = jax.vmap(lambda le: routing.e2e_success(le)[0])(
        jax.numpy.asarray(small.scenarios.link_eps)
    )
    prepared = scenarios.ScenarioGrid(
        scenarios=small.scenarios._replace(rho=np.asarray(rho_small)),
        labels=list(small.labels),
    )
    joined = scenarios.ScenarioGrid.concat(prepared, large)   # was: crash
    assert joined.scenarios.rho is None
    assert joined.scenarios.link_eps.shape[-1] == big.n_nodes
    # The rederived rho matches the unpadded small-net routing (client block).
    rho_pad = joined.scenario(0).prepare().rho
    rho_raw, _ = routing.e2e_success(net.link_eps)
    np.testing.assert_allclose(np.asarray(rho_pad)[:3, :3],
                               np.asarray(rho_raw)[:3, :3], atol=1e-7)


def test_hoist_uniform_is_nan_tolerant(toy):
    """A grid-uniform float field containing NaN must still hoist (the old
    `(arr == arr[:1]).all()` test was NaN-blind and silently kept the leaf
    batched — forcing every lax.switch branch to execute)."""
    _, net, _, _ = toy
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        seeds=[0, 1],
    )
    le = np.asarray(grid.scenarios.link_eps).copy()
    le[:, 0, 1] = np.nan                    # same NaN in every row
    axes, args = scenarios._hoist_uniform(grid.scenarios._replace(link_eps=le))
    assert axes.link_eps is None            # hoisted despite the NaN
    assert axes.seed == 0                   # seed always stays mapped
    # Rows that GENUINELY differ (NaN in one row only) must stay mapped.
    le2 = np.asarray(grid.scenarios.link_eps).copy()
    le2[0, 0, 1] = np.nan
    axes2, _ = scenarios._hoist_uniform(grid.scenarios._replace(link_eps=le2))
    assert axes2.link_eps == 0


def test_duplicate_labels_rejected_and_deduped(toy):
    """product raises on colliding labels; concat disambiguates collisions
    (two single-seed grids previously collided silently); GridResult.result
    refuses ambiguous or missing labels."""
    _, net, _, _ = toy
    with pytest.raises(ValueError, match="duplicate"):
        scenarios.ScenarioGrid.product(
            networks=[("same", net), ("same", net)],
        )
    g0 = scenarios.ScenarioGrid.product(networks=[("toy", net)], seeds=[0])
    g1 = scenarios.ScenarioGrid.product(networks=[("toy", net)], seeds=[1])
    joined = scenarios.ScenarioGrid.concat(g0, g1)
    assert len(set(joined.labels)) == len(joined)
    assert joined.labels == ["toy/ra+ra_normalized#0",
                             "toy/ra+ra_normalized#1"]
    res = scenarios.GridResult(
        acc=np.zeros((2, 1, 3)), loss=np.zeros((2, 1, 3)),
        bias=np.zeros((2, 1)), labels=["a", "a"],
    )
    with pytest.raises(KeyError, match="ambiguous"):
        res.result("a")
    with pytest.raises(KeyError, match="no scenario"):
        res.result("b")


def test_concat_mixed_local_epochs_rejected(toy):
    _, net, _, _ = toy
    plain = scenarios.ScenarioGrid.product(networks=[("a", net)])
    hetero = scenarios.ScenarioGrid.product(
        networks=[("b", net)],
        local_epochs=np.array([1, 2, 1], np.int32),
    )
    with pytest.raises(ValueError, match="local_epochs"):
        scenarios.ScenarioGrid.concat(plain, hetero)


def test_packet_len_consistency_check(toy):
    """seg_len=1024 documents 32,768-bit segments while paper networks
    default to 25,000-bit PER packets: the mismatch must be surfaced (once)
    and a consistent pairing must pass silently."""
    _, net, _, _ = toy
    simulator._WARNED_PACKET_PAIRS.clear()
    cfg = simulator.SimConfig()             # seg_len=1024
    assert cfg.packet_len_bits == 32_768
    with pytest.warns(simulator.PacketLengthMismatchWarning):
        assert not simulator.check_packet_consistency(net, cfg.seg_len)
    # Warned pairs only warn once.
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        assert not simulator.check_packet_consistency(net, cfg.seg_len)
    consistent = topology.make_network(
        topology.TABLE_II_COORDS[:3], edge_density=0.8,
        packet_len_bits=cfg.packet_len_bits, n_clients=3,
    )
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        assert simulator.check_packet_consistency(consistent, cfg.seg_len)
    # Hand-built networks without a recorded packet length pass through.
    bare = topology.Network(coords=net.coords, adjacency=net.adjacency,
                            link_eps=net.link_eps, n_clients=3)
    assert simulator.check_packet_consistency(bare, cfg.seg_len)


def test_packet_len_checked_on_grid_path(toy):
    """Grids record their source networks' packet lengths and
    GridRunner.run surfaces the mismatch too (regression: only the scalar
    make_scenario path used to check)."""
    data, net, init, apply_fn = toy
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        aggregator=0,
    )
    assert grid.packet_len_bits == (25_000,)
    joined = scenarios.ScenarioGrid.concat(
        grid, scenarios.ScenarioGrid.product(
            networks=[("toy2", topology.make_network(
                topology.TABLE_II_COORDS[:3], edge_density=0.8,
                packet_len_bits=2_048, n_clients=3, tx_power_dbm=17.0))],
        )
    )
    assert joined.packet_len_bits == (2_048, 25_000)
    simulator._WARNED_PACKET_PAIRS.clear()
    runner = scenarios.GridRunner(init, apply_fn, data, _cfg())
    with pytest.warns(simulator.PacketLengthMismatchWarning):
        runner.run(grid)                    # seg_len=64 -> 2,048-bit segments


# ---------------------------------------------------------------------------
# Sharded dynamic grids (the CI sharding job runs this under 8 devices).
# ---------------------------------------------------------------------------
def test_dynamic_grid_sharded_bit_identical(toy):
    """A time-varying + sampled grid through a ('grid',) mesh (1 device
    always; 4 when available, covering real multi-device slicing of the
    time-leaved fields) == the plain vmap path, within float32
    round-off."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    churn = topology.markov_link_schedule(net, N_ROUNDS, p_drop=0.4,
                                          p_recover=0.5, seed=4)
    grid = scenarios.ScenarioGrid.product(
        schedules=[("churn", churn), ("static", net)],
        protocols=[("ra", "ra_normalized")], seeds=range(3),
        participation=[("full", None),
                       ("p67", scenarios.sampling_schedule(
                           N_CLIENTS, N_ROUNDS, 0.67, seed=5))],
        aggregator=0,
    )
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    plain = runner.run(grid)
    assert_same_semantics(runner.run(grid, devices=1), plain)
    if jax.device_count() >= 4:
        # 12 scenarios on 4 devices: 3-per-device slices, no padding; the
        # forced-8-device CI job also exercises the non-divisible pad.
        assert_same_semantics(runner.run(grid, devices=4), plain)
        assert_same_semantics(runner.run(grid, devices=8), plain)
