"""Closed-loop selection + mobility (DESIGN.md §10) + routing/errors fixes.

Five layers:

  * equivalence — the ``uniform`` policy reproduces the open-loop
    participation path's masks exactly and its trajectories within
    float32 round-off (with and without a participation schedule), and a
    zero-velocity mobility schedule reproduces the static network's
    (DESIGN.md §16);
  * policy semantics — loss / grad_norm / bandwidth policies select the
    documented top-k sets, compose with the availability base mask,
    change the trajectory, and never starve a client;
  * grid engine — the ``sampling_policies`` axis batches/validates,
    mixes with every other axis, `concat` fills policy-free grids with
    the neutral uniform policy, and `GridResult.selected` records the
    realized masks (per-round even under eval thinning);
  * sharding — a (mobility schedule x policy) grid through a device mesh
    keeps the single-device vmap path's semantics (the CI sharding job
    runs this module under 8 forced host devices);
  * regressions — the routing/errors fixes landed alongside: dtype-aware
    clip floors, `sample_success`'s ``n_clients=0`` guard,
    `reconstruct_route` sentinel/cycle handling, `_greedy_slots` order
    invariance, and the `admitted_rho_mask` bandwidth wiring.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _same import assert_same_program, assert_same_semantics
from repro.core import errors, overhead, routing, selection, topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.models import smallnets

N_CLIENTS = 3
N_ROUNDS = 4
EPOCHS = 2


@pytest.fixture(scope="module")
def toy():
    data = synthetic.fed_image_classification(
        n_clients=N_CLIENTS, samples_per_client=20, seed=0
    )
    net = topology.make_network(
        topology.TABLE_II_COORDS[:N_CLIENTS], edge_density=0.8,
        packet_len_bits=25_000, n_clients=N_CLIENTS, tx_power_dbm=17.0,
    )
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=16)
    return data, net, init, smallnets.apply_mlp_clf


def _cfg(**kw):
    kw.setdefault("n_rounds", N_ROUNDS)
    kw.setdefault("local_epochs", EPOCHS)
    kw.setdefault("seg_len", 64)
    kw.setdefault("cfl_aggregator", 0)
    return simulator.SimConfig(**kw)


ALL_PROTOCOLS = [("ra", "ra_normalized"), ("ra", "substitution"),
                 ("aayg", "ra_normalized"), ("cfl", "ra_normalized"),
                 ("ideal_cfl", "ra_normalized"), ("none", "ra_normalized")]


def _trajectories(res: scenarios.GridResult) -> dict:
    """acc / loss / bias of a grid result, without its realized masks."""
    return {"acc": res.acc, "loss": res.loss, "bias": res.bias}


# ---------------------------------------------------------------------------
# Equivalence: uniform policy == open loop; frozen mobility == static.
# ---------------------------------------------------------------------------
def test_uniform_policy_bitwise_equals_open_loop_schedule(toy):
    """uniform closed loop over a participation schedule == the open-loop
    path within float32 round-off, for every protocol branch — and only
    the closed-loop result carries realized masks (== the schedule)."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    sched = scenarios.sampling_schedule(N_CLIENTS, N_ROUNDS, 0.67, seed=2)
    open_grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=ALL_PROTOCOLS,
        participation=[("p67", sched)], aggregator=0,
    )
    closed_grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=ALL_PROTOCOLS,
        participation=[("p67", sched)],
        sampling_policies=[("uni", "uniform", 1.0)], aggregator=0,
    )
    assert closed_grid.scenario(0).is_closed_loop
    assert not open_grid.scenario(0).is_closed_loop
    ref = scenarios.run_grid(init, apply_fn, data, open_grid, cfg)
    got = scenarios.run_grid(init, apply_fn, data, closed_grid, cfg)
    assert_same_semantics(_trajectories(got), _trajectories(ref))
    assert ref.selected is None and ref.selected_frac is None
    assert_same_program(
        got.selected,
        np.broadcast_to(sched[None], (len(closed_grid),) + sched.shape),
    )


def test_uniform_policy_bitwise_equals_static_grid(toy):
    """With no participation schedule at all, the uniform policy's base is
    all-ones: the fully static grid within float32 round-off."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    static = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        seeds=[0, 1], aggregator=0,
    )
    closed = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        seeds=[0, 1], sampling_policies=[("uni", "uniform", 0.5)],
        aggregator=0,
    )
    assert_same_semantics(
        _trajectories(scenarios.run_grid(init, apply_fn, data, closed, cfg)),
        _trajectories(scenarios.run_grid(init, apply_fn, data, static, cfg)),
    )


def test_mobility_zero_step_bitwise_static(toy):
    """A frozen random-waypoint walk IS the static network: every schedule
    entry bitwise equals its links, and the whole trajectory the static
    grid's within float32 round-off (a schedule is another program)."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    mob0 = topology.mobility_link_schedule(net, N_ROUNDS, step_m=0.0, seed=9)
    np.testing.assert_array_equal(
        mob0, np.broadcast_to(np.asarray(net.link_eps, np.float32)[None],
                              mob0.shape),
    )
    static = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        aggregator=0,
    )
    frozen = scenarios.ScenarioGrid.product(
        schedules=[("mob0", mob0)], protocols=[("ra", "ra_normalized")],
        aggregator=0,
    )
    assert_same_semantics(
        scenarios.run_grid(init, apply_fn, data, frozen, cfg),
        scenarios.run_grid(init, apply_fn, data, static, cfg),
    )


def test_mobility_schedule_properties(toy):
    _, net, _, _ = toy
    base = np.asarray(net.link_eps, np.float32)
    walk = topology.mobility_link_schedule(net, 6, step_m=500.0, seed=3)
    assert walk.shape == (6,) + base.shape
    np.testing.assert_array_equal(walk[0], base)       # round 0 = start
    assert not np.array_equal(walk[1], walk[5])        # nodes actually move
    assert (walk >= 0.0).all() and (walk <= 1.0).all()
    # range_m=None keeps the STATIC adjacency: no new links ever appear.
    assert (walk[:, base == 0.0] == 0.0).all()
    # Symmetric channel, no self links.
    gate = walk != 0.0
    np.testing.assert_array_equal(gate, np.transpose(gate, (0, 2, 1)))
    assert (walk[:, np.eye(base.shape[0], dtype=bool)] == 0.0).all()
    # Mobility is CORRELATED: one step moves link qualities less than the
    # whole walk does.
    step_delta = np.abs(walk[1] - walk[0]).mean()
    total_delta = np.abs(walk[5] - walk[0]).mean()
    assert step_delta <= total_delta + 1e-6
    # Range-based adjacency re-derives links per round (symmetric, no self).
    ranged = topology.mobility_link_schedule(net, 4, step_m=500.0, seed=3,
                                             range_m=4000.0)
    gate = ranged != 0.0
    np.testing.assert_array_equal(gate, np.transpose(gate, (0, 2, 1)))
    with pytest.raises(ValueError):
        topology.mobility_link_schedule(net, 2, step_m=-1.0)


# ---------------------------------------------------------------------------
# Policy semantics (unit level).
# ---------------------------------------------------------------------------
def _signals(loss, upd):
    return selection.SelectionSignals(
        loss=jnp.asarray(loss, jnp.float32),
        upd_norm=jnp.asarray(upd, jnp.float32),
    )


def _select(policy, base, sig, p=None, rho=None, frac=0.5):
    n = len(base)
    p = jnp.full((n,), 1.0 / n) if p is None else jnp.asarray(p)
    rho = jnp.ones((n, n)) if rho is None else jnp.asarray(rho)
    return np.asarray(selection.select_clients(
        jnp.asarray(selection.POLICY_IDS[policy], jnp.int32),
        jnp.asarray(base, jnp.float32), sig, p, rho,
        jnp.asarray(frac, jnp.float32),
    ))


def test_policy_topk_semantics():
    sig = _signals([3.0, 1.0, 2.0, 0.5], [0.1, 5.0, 1.0, 2.0])
    base = [1.0, 1.0, 1.0, 1.0]
    np.testing.assert_array_equal(_select("uniform", base, sig),
                                  [1, 1, 1, 1])
    np.testing.assert_array_equal(_select("loss", base, sig),
                                  [1, 0, 1, 0])          # top-2 losses: 0, 2
    np.testing.assert_array_equal(_select("grad_norm", base, sig),
                                  [0, 1, 0, 1])          # top-2 norms: 1, 3
    # frac=1.0 selects everyone under every policy.
    np.testing.assert_array_equal(_select("loss", base, sig, frac=1.0),
                                  [1, 1, 1, 1])


def test_policy_respects_base_mask():
    """The open-loop schedule is an availability base: ruled-out clients
    are never selected, even with the best score."""
    sig = _signals([10.0, 1.0, 2.0], [0.0, 0.0, 0.0])
    got = _select("loss", [0.0, 1.0, 1.0], sig, frac=0.3)   # k=1
    np.testing.assert_array_equal(got, [0, 0, 1])           # best AVAILABLE


def test_bandwidth_policy_matches_admission_order():
    p = np.array([0.1, 0.4, 0.2, 0.3], np.float32)
    rng = np.random.default_rng(0)
    rho = rng.uniform(0.3, 1.0, size=(4, 4)).astype(np.float32)
    np.fill_diagonal(rho, 1.0)
    order = routing.admit_homologous_routes(p, rho, n_clients=4,
                                            max_admitted=2)
    got = _select("bandwidth", [1.0] * 4,
                  _signals(np.zeros(4), np.zeros(4)), p=p, rho=rho)
    want = np.zeros(4)
    want[order] = 1.0
    np.testing.assert_array_equal(got, want)


def test_topk_mask_ties_and_select_count():
    # All-equal scores: stable sort → lowest indices first.
    mask = np.asarray(selection.topk_mask(jnp.zeros(5),
                                          jnp.asarray(2, jnp.int32)))
    np.testing.assert_array_equal(mask, [1, 1, 0, 0, 0])
    assert int(selection.select_count(jnp.asarray(1.0), 7)) == 7
    assert int(selection.select_count(jnp.asarray(1e-6), 7)) == 1
    assert int(selection.select_count(jnp.asarray(0.5), 3)) == 2
    # float32 cannot represent 0.3: a raw ceil(0.3 * 50) would give 16.
    assert int(selection.select_count(jnp.asarray(0.3), 50)) == 15
    assert int(selection.select_count(jnp.asarray(0.6), 25)) == 15


def test_update_norms_per_client():
    old = {"w": jnp.zeros((3, 2, 2)), "b": jnp.zeros((3, 2))}
    new = {"w": jnp.ones((3, 2, 2)).at[0].set(0.0),
           "b": jnp.zeros((3, 2)).at[2].set(3.0)}
    got = np.asarray(selection.update_norms(new, old))
    np.testing.assert_allclose(got, [0.0, 2.0, np.sqrt(4.0 + 18.0)],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Closed-loop trajectories.
# ---------------------------------------------------------------------------
def test_closed_loop_changes_trajectory_and_never_starves(toy):
    data, net, init, apply_fn = toy
    cfg = _cfg()
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        sampling_policies=[("uni", "uniform", 1.0), ("loss", "loss", 0.5),
                           ("grad", "grad_norm", 0.5),
                           ("bw", "bandwidth", 0.5)],
        aggregator=0,
    )
    res = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    assert np.isfinite(res.acc).all()
    assert res.selected.shape == (4, N_ROUNDS, N_CLIENTS)
    # The policies are live, not decorative.
    assert not np.array_equal(res.acc[0], res.acc[1])
    # k = ceil(0.5 * 3) = 2 every round for every top-k policy.
    np.testing.assert_array_equal(res.selected[1:].sum(axis=2),
                                  np.full((3, N_ROUNDS), 2.0))
    # Signal-driven policies never starve a client (optimistic init +
    # carried signals).  The bandwidth policy is EXPECTED to fixate on a
    # static network: its admission scores depend only on (p, rho).
    assert (res.selected[1:3].sum(axis=1) > 0).all()
    np.testing.assert_array_equal(
        res.selected[3], np.broadcast_to(res.selected[3][:1], (N_ROUNDS, N_CLIENTS))
    )


def test_closed_loop_equals_open_loop_replay_of_realized_masks(toy):
    """A loss-policy run == an open-loop run that replays the realized
    masks as a (T, N) participation schedule, within float32 round-off —
    the closed loop adds the policy, not new round semantics (the
    open-loop tests therefore cover sampled-out-client untouchedness
    here too)."""
    data, net, init, apply_fn = toy
    cfg = _cfg()
    closed = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        sampling_policies=[("loss", "loss", 0.5)], aggregator=0,
    )
    got = scenarios.run_grid(init, apply_fn, data, closed, cfg)
    realized = got.selected[0]                       # (T, N)
    assert 0.0 < realized.mean() < 1.0               # genuinely selective
    replay = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        participation=[("replay", realized)], aggregator=0,
    )
    ref = scenarios.run_grid(init, apply_fn, data, replay, cfg)
    assert_same_semantics(_trajectories(got), _trajectories(ref))


def test_closed_loop_eval_thinning_keeps_trajectory(toy):
    data, net, init, apply_fn = toy
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        sampling_policies=[("loss", "loss", 0.5)], aggregator=0,
    )
    full = scenarios.run_grid(init, apply_fn, data, grid, _cfg())
    thin = scenarios.run_grid(init, apply_fn, data, grid,
                              _cfg(eval_every=2))
    # Thinning is another program: the same trajectory within float32
    # round-off, and ``selected`` stays PER-ROUND (compared exactly).
    assert_same_semantics(
        {"acc": thin.acc, "bias": thin.bias, "selected": thin.selected},
        {"acc": full.acc[:, 1::2], "bias": full.bias,
         "selected": full.selected},
    )


def test_make_scenario_rejects_unknown_policy(toy):
    _, net, _, _ = toy
    with pytest.raises(ValueError, match="sampling_policy"):
        simulator.make_scenario(net, _cfg(), sampling_policy="nope")


# ---------------------------------------------------------------------------
# Grid engine: axis validation, concat, sequential equivalence.
# ---------------------------------------------------------------------------
def test_policy_axis_validation(toy):
    _, net, _, _ = toy
    with pytest.raises(ValueError, match="unknown sampling policy"):
        scenarios.ScenarioGrid.product(
            networks=[("toy", net)],
            sampling_policies=[("x", "nope", 0.5)],
        )
    with pytest.raises(ValueError, match="select_frac"):
        scenarios.ScenarioGrid.product(
            networks=[("toy", net)],
            sampling_policies=[("x", "loss", 0.0)],
        )
    with pytest.raises(ValueError, match="at least one"):
        scenarios.ScenarioGrid.product(
            networks=[("toy", net)], sampling_policies=[],
        )
    # Single-policy axes omit the label component (like participation).
    g1 = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], sampling_policies=[("solo", "loss", 0.5)],
    )
    assert g1.labels == ["toy/ra+ra_normalized"]
    g2 = scenarios.ScenarioGrid.product(
        networks=[("toy", net)],
        sampling_policies=[("a", "loss", 0.5), ("b", "uniform", 1.0)],
    )
    assert g2.labels == ["toy/ra+ra_normalized/a", "toy/ra+ra_normalized/b"]


def test_concat_fills_policy_free_grids_with_uniform(toy):
    data, net, init, apply_fn = toy
    cfg = _cfg()
    plain = scenarios.ScenarioGrid.product(
        networks=[("plain", net)], protocols=[("ra", "ra_normalized")],
        aggregator=0,
    )
    policy = scenarios.ScenarioGrid.product(
        networks=[("pol", net)], protocols=[("ra", "ra_normalized")],
        sampling_policies=[("loss", "loss", 0.5)], aggregator=0,
    )
    joined = scenarios.ScenarioGrid.concat(plain, policy)
    assert joined.scenarios.policy_id.shape == (2,)
    assert int(joined.scenarios.policy_id[0]) == selection.POLICY_IDS["uniform"]
    res = scenarios.run_grid(init, apply_fn, data, joined, cfg)
    # The filled-in uniform row still matches the standalone open-loop run
    # (closed loop against open loop: within float32 round-off).
    ref = scenarios.run_grid(init, apply_fn, data, plain, cfg)
    assert_same_semantics(res.result("plain/ra+ra_normalized"),
                          ref.result("plain/ra+ra_normalized"))
    # ...and the policy row matches ITS standalone run (batch of 2
    # against batch of 1).
    pol_ref = scenarios.run_grid(init, apply_fn, data, policy, cfg)
    assert_same_semantics(res.result("pol/ra+ra_normalized"),
                          pol_ref.result("pol/ra+ra_normalized"))


def test_closed_loop_grid_equals_sequential(toy):
    data, net, init, apply_fn = toy
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        sampling_policies=[("loss", "loss", 0.5), ("bw", "bandwidth", 0.5)],
        aggregator=0,
    )
    runner = scenarios.GridRunner(init, apply_fn, data, _cfg())
    batched = runner.run(grid)
    # Each row as its own one-point grid: batch sizes differ, so the
    # programs do; realized masks compare exactly.
    for i in range(len(grid)):
        row = runner.run(grid.take([i]))
        assert_same_semantics(batched.result(i), row.result(0))
        assert_same_semantics({"selected": batched.selected[i]},
                              {"selected": row.selected[0]})


# ---------------------------------------------------------------------------
# Sharding: (mobility x policy) grids keep their semantics through a mesh
# (the CI sharding job runs this under 8 forced host devices).
# ---------------------------------------------------------------------------
def test_policy_grid_sharded_bit_identical(toy):
    data, net, init, apply_fn = toy
    mob = topology.mobility_link_schedule(net, N_ROUNDS, step_m=600.0,
                                          seed=21)
    grid = scenarios.ScenarioGrid.product(
        schedules=[("mob", mob), ("static", net)],
        protocols=[("ra", "ra_normalized")],
        sampling_policies=[("uni", "uniform", 1.0), ("loss", "loss", 0.5),
                           ("bw", "bandwidth", 0.5)],
        aggregator=0,
    )
    runner = scenarios.GridRunner(init, apply_fn, data, _cfg())
    plain = runner.run(grid)
    assert_same_semantics(runner.run(grid, devices=1), plain)
    if jax.device_count() >= 4:
        for d in (4, 8):
            assert_same_semantics(runner.run(grid, devices=d), plain)


# ---------------------------------------------------------------------------
# Production dfl_step threading (mesh-axis closed loop).
# ---------------------------------------------------------------------------
def test_dfl_step_participation_and_selection():
    """ra_exchange with a participation mask == the segment-level protocol
    reference, and make_dfl_train_step's loss policy selects in-loop —
    run in a subprocess with 8 forced host devices (cf. test_system)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import dfl_step, protocols, selection

        n = 8
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("clients",))
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (n, 4, 6)),
                  "b": jax.random.normal(key, (n, 6))}
        p = jax.nn.softmax(jax.random.normal(key, (n,)))
        rho = jnp.full((n, n), 0.7)
        ekey = jax.random.PRNGKey(42)
        mask = jnp.asarray([1., 0., 1., 1., 0., 1., 1., 1.])
        seg_len = 6

        w_seg, spec, m_params = protocols._to_segments(params, seg_len)
        out, e = protocols.ra_round_seg(w_seg, p, rho, ekey,
                                        jnp.asarray(0), mask)
        want = protocols._from_segments(out, spec, m_params)

        @partial(shard_map, mesh=mesh,
                 in_specs=({"w": P("clients"), "b": P("clients")},
                           P(), P(), P(), P()),
                 out_specs={"w": P("clients"), "b": P("clients")})
        def exchange(stacked, p, rho, k, part):
            mine = jax.tree.map(lambda x: x[0], stacked)
            out = dfl_step.ra_exchange(mine, p, rho, k, axis="clients",
                                       seg_len=seg_len, participation=part)
            return jax.tree.map(lambda x: x[None], out)

        got = exchange(params, p, rho, ekey, mask)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        # masked-out clients keep their params bitwise
        for name in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(got[name])[1],
                                          np.asarray(params[name])[1])

        # Closed-loop rounds.  Local "training" moves client i's params
        # by ~i (update norm RISES with i) while the loss signal FALLS
        # with i — so the loss and grad_norm policies select OPPOSITE
        # halves and must produce different exchanges (regression: the
        # production grad_norm path used to alias the loss signal).
        def local_step(state, batch):
            moved = jax.tree.map(lambda x: x + 0.01 * state["loss"], state["params"])
            return dict(state, params=moved), {"loss": 7.0 - state["loss"]}

        outs = {}
        for policy in ("loss", "grad_norm"):
            round_fn = dfl_step.make_dfl_train_step(
                local_step, axis="clients", p=p, seg_len=seg_len,
                selection_policy=policy, select_frac=0.5)

            @partial(shard_map, mesh=mesh,
                     in_specs=({"params": {"w": P("clients"),
                                           "b": P("clients")},
                                "loss": P("clients")}, P(), P()),
                     out_specs={"params": {"w": P("clients"),
                                           "b": P("clients")},
                                "loss": P("clients")})
            def run_round(state, rho, k, _fn=round_fn):
                st = {"params": jax.tree.map(lambda x: x[0], state["params"]),
                      "loss": state["loss"][0]}
                st, _ = _fn(st, None, rho, k)
                return {"params": jax.tree.map(lambda x: x[None],
                                               st["params"]),
                        "loss": st["loss"][None]}

            sizes = jnp.arange(n, dtype=jnp.float32)    # client i moves ~i
            state = {"params": params, "loss": sizes}
            outs[policy] = run_round(state, rho, ekey)

        # The two policies selected different halves: exchanges differ.
        assert not np.allclose(np.asarray(outs["loss"]["params"]["w"]),
                               np.asarray(outs["grad_norm"]["params"]["w"]))
        print("DFL_SELECTION_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert "DFL_SELECTION_OK" in out.stdout, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# Regressions: the routing/errors fixes landed alongside.
# ---------------------------------------------------------------------------
def test_link_cost_dtype_aware_floor():
    """The clip floor must survive the float32 cast (a 1e-300 literal
    underflows to 0.0, disarming the clip): costs are never NaN, zero
    quality is inf (no link), and any positive normal quality is finite
    and bounded by -log(finfo.tiny)."""
    eps32 = jnp.asarray([[0.0, 1e-37], [1e-37, 0.0]], jnp.float32)
    cost = np.asarray(routing.link_cost(eps32))
    assert not np.isnan(cost).any()
    assert np.isinf(cost[0, 0]) and np.isinf(cost[1, 1])
    assert np.isfinite(cost[0, 1])
    bound = -np.log(np.finfo(np.float32).tiny) + 1.0
    assert cost[0, 1] <= bound
    # ...and such a link still routes: rho stays strictly positive.
    rho, _ = routing.e2e_success(eps32)
    assert np.asarray(rho)[0, 1] >= 0.0
    # packet_success_rate survives absurd distances without NaN.
    eps = np.asarray(topology.packet_success_rate(
        jnp.asarray([1e7], jnp.float32), 25_000))
    assert np.isfinite(eps).all() and (eps >= 0.0).all()
    # Integer 0/1 link matrices still work (finfo needs a float dtype).
    cost_int = np.asarray(routing.link_cost(jnp.asarray([[0, 1], [1, 0]])))
    np.testing.assert_array_equal(cost_int, [[np.inf, 0.0], [0.0, np.inf]])


def test_sample_success_explicit_n_clients_zero():
    """n_clients=0 must mean ZERO clients, not fall back to V (the old
    falsy `n_clients or shape[0]` guard)."""
    rho = jnp.full((4, 4), 0.5)
    e = errors.sample_success(jax.random.PRNGKey(0), rho, 3, n_clients=0)
    assert e.shape == (0, 0, 3)
    e_none = errors.sample_success(jax.random.PRNGKey(0), rho, 3)
    assert e_none.shape == (4, 4, 3)


def test_reconstruct_route_unreachable_intermediate():
    """An intermediate node whose next hop is itself (the unreachable
    sentinel) must fail FAST with [] — not spin for max_hops first."""
    # 0 -> 2 routes via 1, but 1 cannot reach 2 (sentinel next_hop[1,2]=1).
    nxt = np.array([[0, 1, 1],
                    [0, 1, 1],
                    [0, 1, 2]])
    assert routing.reconstruct_route(nxt, 0, 2) == []
    # Source-level sentinel still detected.
    nxt_src = np.array([[0, 0], [1, 1]])
    assert routing.reconstruct_route(nxt_src, 0, 1) == []
    # A corrupted matrix with a 2-cycle terminates with [].
    nxt_cyc = np.array([[0, 1, 1],
                        [0, 1, 0],
                        [0, 1, 2]])
    assert routing.reconstruct_route(nxt_cyc, 0, 2) == []
    # max_hops=0 is honored (the old `max_hops or ...` treated 0 as None).
    nxt_ok = np.array([[0, 1], [0, 1]])
    assert routing.reconstruct_route(nxt_ok, 0, 1, max_hops=0) == []
    assert routing.reconstruct_route(nxt_ok, 0, 1) == [0, 1]


def test_greedy_slots_order_invariant():
    rng = np.random.default_rng(0)
    txs = [(0, 1), (1, 2), (2, 3), (0, 3), (4, 5), (3, 4), (1, 5)]
    want = overhead._greedy_slots(txs)
    for _ in range(5):
        perm = [txs[i] for i in rng.permutation(len(txs))]
        assert overhead._greedy_slots(perm) == want


def test_admitted_rho_mask():
    p = np.array([0.1, 0.4, 0.2, 0.3], np.float32)
    rng = np.random.default_rng(1)
    rho = rng.uniform(0.3, 1.0, size=(5, 5))    # 4 clients + 1 relay row
    np.fill_diagonal(rho, 1.0)
    order = routing.admit_homologous_routes(p, rho, n_clients=4,
                                            max_admitted=2)
    masked = routing.admitted_rho_mask(p, rho, n_clients=4, max_admitted=2)
    for m in range(4):
        if m in order:
            np.testing.assert_array_equal(masked[m, :4], rho[m, :4])
        else:
            # Off-diagonal zeroed, own model kept.
            row = masked[m, :4].copy()
            assert row[m] == rho[m, m]
            row[m] = 0.0
            np.testing.assert_array_equal(row, np.zeros(4))
    # Relay rows + columns beyond the client block untouched.
    np.testing.assert_array_equal(masked[4], rho[4])
    np.testing.assert_array_equal(masked[:, 4], rho[:, 4])
    # No cap = everything admitted = unchanged.
    np.testing.assert_array_equal(
        routing.admitted_rho_mask(p, rho, n_clients=4), rho
    )
    # The score formula is shared with the traced policy path.
    np.testing.assert_allclose(
        np.asarray(routing.admission_scores(jnp.asarray(p),
                                            jnp.asarray(rho[:4, :4]))),
        routing.admission_scores(p, rho[:4, :4]), rtol=1e-6,
    )
