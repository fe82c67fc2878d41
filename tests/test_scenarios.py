"""Batched scenario engine: grid == scalar equivalence + engine properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _same import assert_same_program, assert_same_semantics
from repro.core import protocols, routing, topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.models import smallnets


def _toy_setup(n_clients=3):
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


@pytest.mark.parametrize("protocol,mode", [
    ("ra", "ra_normalized"),
    ("ra", "substitution"),
    ("aayg", "ra_normalized"),
    ("cfl", "ra_normalized"),
    ("ideal_cfl", "ra_normalized"),
])
def test_run_grid_one_point_matches_scalar_simulate(toy, protocol, mode):
    """The scalar `scenarios.simulate` IS the one-point grid: the same
    compiled program, so the trajectories agree bit for bit (same seed,
    same config) — 3-client toy net."""
    data, net, init, apply_fn = toy
    cfg = simulator.SimConfig(
        protocol=protocol, mode=mode, n_rounds=4, local_epochs=2,
        seg_len=64, seed=3, cfl_aggregator=1,
    )
    want = scenarios.simulate(init, apply_fn, data, net, cfg)
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[(protocol, mode)], seeds=[3],
        aggregator=1,
    )
    got = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    assert len(got) == 1
    assert_same_program(got.result(0), want)


def test_run_grid_matches_per_row_one_point_grids(toy):
    """A vmapped batch == each of its rows run as a one-point grid: batch
    sizes differ, so the programs do, and they agree within float32
    round-off."""
    data, net, init, apply_fn = toy
    cfg = simulator.SimConfig(n_rounds=3, local_epochs=2, seg_len=64,
                              cfl_aggregator=0)
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)],
        protocols=[("ra", "ra_normalized"), ("ra", "substitution"),
                   ("aayg", "ra_normalized"), ("cfl", "ra_normalized"),
                   ("ideal_cfl", "ra_normalized"), ("none", "ra_normalized")],
        seeds=[0, 1], aggregator=0,
    )
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    batched = runner.run(grid)
    for i in range(len(grid)):
        assert_same_semantics(batched.result(i),
                              runner.run(grid.take([i])).result(0))
    # The same runner and bucket called twice: one program, bit for bit.
    assert_same_program(runner.run(grid), batched)


def test_grid_mixed_node_counts_pad_is_routing_neutral(toy):
    """Scenarios with different node counts share one padded program, and
    padding with isolated nodes leaves the client-block rho unchanged."""
    data, net, init, apply_fn = toy
    big = topology.make_network(
        np.concatenate([topology.TABLE_II_COORDS[:3],
                        topology.TABLE_II_COORDS[5:8]]),
        edge_density=0.6, packet_len_bits=25_000, n_clients=3,
        tx_power_dbm=17.0,
    )
    # rho of the padded small net == rho of the unpadded small net (clients).
    v_max = big.link_eps.shape[0]
    padded = scenarios._pad_link_eps(net.link_eps, v_max)
    rho_pad, _ = routing.e2e_success(padded)
    rho_raw, _ = routing.e2e_success(net.link_eps)
    np.testing.assert_allclose(np.asarray(rho_pad[:3, :3]),
                               np.asarray(rho_raw[:3, :3]), atol=1e-7)

    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=64)
    grid = scenarios.ScenarioGrid.product(
        networks=[("small", net), ("big", big)],
        protocols=[("ra", "ra_normalized")],
    )
    res = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    assert res.acc.shape == (2, 2, 3)
    assert np.isfinite(res.acc).all()


def test_grid_labels_and_result_accessors(toy):
    data, net, init, apply_fn = toy
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=64)
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)],
        protocols=[("ra", "ra_normalized"), ("none", "ra_normalized")],
        seeds=[0, 7],
    )
    assert len(grid) == 4
    assert grid.labels[0] == "toy/ra+ra_normalized/s0"
    res = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    one = res.result("toy/none+ra_normalized/s7")
    assert one.acc_per_client.shape == (2, 3)
    assert res.mean_acc.shape == (4, 2)
    assert dict(res.items())["toy/ra+ra_normalized/s0"].bias_norms.shape == (2,)


def test_advance_chunk_is_pure(toy):
    """`advance_chunk` on the same (state, scenario, chunk) twice ->
    identical outputs; the input state is not mutated (the round loop is
    side-effect free)."""
    data, net, init, apply_fn = toy
    sim = simulator.build_sim(init, apply_fn, data, seg_len=64,
                              local_epochs=1, n_rounds=2)
    scen = simulator.make_scenario(net, simulator.SimConfig(lr=0.05,
                                                            seed=42))
    state = jax.jit(sim.init_scan)(scen)
    before = jax.tree.map(lambda x: np.asarray(x).copy(), state)
    chunk = jax.jit(sim.advance_chunk)
    out1 = chunk(state, scen, jnp.int32(0))
    out2 = chunk(state, scen, jnp.int32(0))
    assert_same_program(out1, out2)
    assert_same_program(state, before)
    # The round moved the models: the check above is not vacuous.
    assert not np.array_equal(np.asarray(out1[0]["w"]), before["w"])


def test_dispatch_round_matches_protocol_wrappers(toy):
    """Traced protocol_id switch == the static pytree-level wrappers."""
    data, net, init, apply_fn = toy
    key = jax.random.PRNGKey(5)
    n = 3
    params = {"w": jax.random.normal(key, (n, 4, 6)),
              "b": jax.random.normal(key, (n, 6))}
    p = jnp.asarray(data.weights())
    rho, _ = routing.e2e_success(net.link_eps)
    seg_len = 5
    w_seg, spec, m_params = protocols._to_segments(params, seg_len)

    want, _ = protocols.ra_round(params, p, rho, key, seg_len=seg_len)
    got_seg, _, _ = protocols.dispatch_round_seg(
        w_seg, p, rho, net.link_eps, key,
        jnp.asarray(protocols.PROTOCOL_IDS["ra"]), jnp.asarray(0),
        jnp.asarray(0),
    )
    got = protocols._from_segments(got_seg, spec, m_params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    want = protocols.cfl_round(params, p, rho, key, seg_len=seg_len,
                               aggregator=1)
    got_seg, _, _ = protocols.dispatch_round_seg(
        w_seg, p, rho, net.link_eps, key,
        jnp.asarray(protocols.PROTOCOL_IDS["cfl"]), jnp.asarray(0),
        jnp.asarray(1),
    )
    got = protocols._from_segments(got_seg, spec, m_params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
