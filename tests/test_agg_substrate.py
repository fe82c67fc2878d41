"""Aggregation-substrate switch (DESIGN.md §9): Pallas kernel vs jnp path,
batched grids on the kernel, eval thinning, bias gating, packed masks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _same import assert_same_program, assert_same_semantics
from repro.core import aggregation, errors, topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.kernels import ops, ref
from repro.models import smallnets

MODES = ("ra_normalized", "substitution")
REFS = {"ra_normalized": ref.ra_aggregate_ref,
        "substitution": ref.ra_substitution_ref}


def _mask(key, n, l, density=0.7, dtype=jnp.bool_):
    e = jax.random.uniform(key, (n, n, l)) < density
    e = e | jnp.eye(n, dtype=jnp.bool_)[:, :, None]
    return e if dtype == jnp.bool_ else e.astype(dtype)


def _setup(seed, n, l, k, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = jax.random.normal(ks[0], (n, l, k)).astype(dtype)
    p = jax.nn.softmax(jax.random.normal(ks[1], (n,)))
    e = _mask(ks[2], n, l)
    return w, p, e


# ---------------------------------------------------------------------------
# Kernel vs reference: both modes, odd shapes, bf16, block-size padding.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("n,l,k", [
    (3, 5, 16), (7, 11, 100), (5, 13, 128),   # prime L: pad-up path
    (4, 8, 64), (6, 1, 36),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_matches_ref_both_modes(mode, n, l, k, dtype):
    w, p, e = _setup(n * 100 + l, n, l, k, dtype)
    got = ops.ra_aggregate(w, p, e, mode=mode)
    want = REFS[mode](w, p, e.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("mode", MODES)
def test_pallas_prime_l_keeps_block_size(mode):
    """Prime L (coprime with every block_l > 1) pads UP to a block multiple
    instead of degenerating to BL=1; results still match the oracle."""
    n, l, k = 4, 131, 32
    w, p, e = _setup(9, n, l, k)
    want = REFS[mode](w, p, e.astype(jnp.float32))
    for bl in (128, 256):   # two padded tiles; 256 > L: one full-L tile
        got = ops.ra_aggregate(w, p, e, mode=mode, block_l=bl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, err_msg=f"block_l={bl}")


# ---------------------------------------------------------------------------
# The batching rule: vmap over a grid axis lowers onto the batched kernel.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", MODES)
def test_pallas_vmap_over_grid_axis(mode):
    b, n, l, k = 5, 4, 7, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    w = jax.random.normal(ks[0], (b, n, l, k))
    p = jax.nn.softmax(jax.random.normal(ks[1], (n,)))   # shared (hoisted)
    e = jax.random.uniform(ks[2], (b, n, n, l)) < 0.6
    e = e | jnp.eye(n, dtype=jnp.bool_)[None, :, :, None]
    got = jax.vmap(
        lambda wi, ei: ops.ra_aggregate(wi, p, ei, mode=mode)
    )(w, e)
    want = jax.vmap(
        lambda wi, ei: REFS[mode](wi, p, ei.astype(jnp.float32))
    )(w, e)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # Direct rank-4 call == vmapped call.
    direct = ops.ra_aggregate(w, p, e, mode=mode)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(got), atol=1e-6)


def test_pallas_nested_vmap_folds_into_grid():
    b1, b2, n, l, k = 2, 3, 3, 5, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    w = jax.random.normal(ks[0], (b1, b2, n, l, k))
    p = jax.nn.softmax(jax.random.normal(ks[1], (n,)))
    e = jax.random.uniform(ks[2], (b1, b2, n, n, l)) < 0.5
    e = e | jnp.eye(n, dtype=jnp.bool_)[None, None, :, :, None]
    got = jax.vmap(jax.vmap(lambda wi, ei: ops.ra_aggregate(wi, p, ei)))(w, e)
    want = jax.vmap(jax.vmap(
        lambda wi, ei: ref.ra_aggregate_ref(wi, p, ei.astype(jnp.float32))
    ))(w, e)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# apply_mode substrate switch.
# ---------------------------------------------------------------------------
def test_apply_mode_impl_switch_equivalence():
    w, p, e = _setup(2, 5, 6, 24)
    for name, mode_id in aggregation.MODE_IDS.items():
        jnp_out = aggregation.apply_mode(jnp.asarray(mode_id), w, p, e,
                                         impl="jnp")
        pal_out = aggregation.apply_mode(jnp.asarray(mode_id), w, p, e,
                                         impl="pallas")
        np.testing.assert_allclose(np.asarray(pal_out), np.asarray(jnp_out),
                                   atol=1e-5, err_msg=name)


def test_resolve_impl():
    assert aggregation.resolve_impl("jnp") == "jnp"
    assert aggregation.resolve_impl("pallas") == "pallas"
    # auto on this (CPU) test host resolves to the jnp reference.
    if jax.default_backend() == "cpu":
        assert aggregation.resolve_impl("auto") == "jnp"
        assert aggregation.resolve_impl(None) in ("jnp", "pallas")
    with pytest.raises(ValueError):
        aggregation.resolve_impl("cuda")


# ---------------------------------------------------------------------------
# End-to-end: run_grid on the pallas substrate == jnp substrate.
# ---------------------------------------------------------------------------
def _toy():
    data = synthetic.fed_image_classification(
        n_clients=3, samples_per_client=20, seed=0
    )
    net = topology.make_network(
        topology.TABLE_II_COORDS[:3], edge_density=0.8,
        packet_len_bits=2048, n_clients=3, tx_power_dbm=17.0,
    )
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=16)
    return data, net, init, smallnets.apply_mlp_clf


@pytest.fixture(scope="module")
def toy():
    return _toy()


def _toy_grid(net):
    return scenarios.ScenarioGrid.product(
        networks=[("toy", net)],
        protocols=[("ra", "ra_normalized"), ("ra", "substitution")],
        seeds=[0, 1],
    )


def test_run_grid_pallas_substrate_matches_jnp(toy):
    """The substrate is selectable END TO END through run_grid: the whole
    grid (both aggregation modes) on the Pallas kernel (interpret on CPU)
    matches the jnp-substrate grid to 1e-5."""
    data, net, init, apply_fn = toy
    cfg = simulator.SimConfig(n_rounds=3, local_epochs=1, seg_len=64)
    grid = _toy_grid(net)
    res_jnp = scenarios.run_grid(init, apply_fn, data, grid,
                                 dataclasses.replace(cfg, agg_impl="jnp"))
    res_pal = scenarios.run_grid(init, apply_fn, data, grid,
                                 dataclasses.replace(cfg, agg_impl="pallas"))
    np.testing.assert_allclose(res_pal.acc, res_jnp.acc, atol=1e-5)
    np.testing.assert_allclose(res_pal.loss, res_jnp.loss, atol=1e-5)
    np.testing.assert_allclose(res_pal.bias, res_jnp.bias, atol=1e-5)


def test_default_impl_is_bit_identical_to_explicit_jnp(toy):
    """auto (CPU) == explicit jnp, bitwise — the default resolves to the
    same program, so the grid path never changes under the switch."""
    data, net, init, apply_fn = toy
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=64)
    grid = _toy_grid(net)
    res_auto = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    res_jnp = scenarios.run_grid(init, apply_fn, data, grid,
                                 dataclasses.replace(cfg, agg_impl="jnp"))
    assert_same_program(res_auto, res_jnp)


# ---------------------------------------------------------------------------
# Round-loop compute diet: eval thinning + bias gating.
# ---------------------------------------------------------------------------
def test_eval_every_thins_metrics_exactly(toy):
    """eval_every=k: acc/loss rows are the k-th rounds of the full run
    (the trained trajectory is untouched; a thinned run is another
    program, so within float32 round-off); bias stays per-round."""
    data, net, init, apply_fn = toy
    cfg = simulator.SimConfig(n_rounds=6, local_epochs=1, seg_len=64)
    grid = _toy_grid(net)
    full = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    thin = scenarios.run_grid(init, apply_fn, data, grid,
                              dataclasses.replace(cfg, eval_every=3))
    assert thin.acc.shape == (len(grid), 2, 3)
    assert thin.bias.shape == full.bias.shape
    assert_same_semantics(
        {"acc": thin.acc, "loss": thin.loss, "bias": thin.bias},
        {"acc": full.acc[:, 2::3], "loss": full.loss[:, 2::3],
         "bias": full.bias},
    )


def test_eval_every_dynamic_scenario(toy):
    """Thinning composes with dynamic axes (participation schedule)."""
    data, net, init, apply_fn = toy
    cfg = simulator.SimConfig(n_rounds=4, local_epochs=1, seg_len=64)
    part = scenarios.sampling_schedule(3, 4, 0.67, seed=1)
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        participation=[("p67", part), ("full", None)],
    )
    full = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    thin = scenarios.run_grid(init, apply_fn, data, grid,
                              dataclasses.replace(cfg, eval_every=2))
    assert_same_semantics({"acc": thin.acc, "bias": thin.bias},
                          {"acc": full.acc[:, 1::2], "bias": full.bias})


def test_eval_every_must_divide_n_rounds(toy):
    data, _, init, apply_fn = toy
    with pytest.raises(ValueError):
        simulator.build_sim(init, apply_fn, data, seg_len=64,
                            local_epochs=1, n_rounds=5, eval_every=2)
    with pytest.raises(ValueError):
        simulator.build_sim(init, apply_fn, data, seg_len=64,
                            local_epochs=1, n_rounds=4, eval_every=0)


def test_track_bias_off_keeps_trajectory(toy):
    """track_bias=False: bias is NaN everywhere, acc/loss stay the same
    (within float32 round-off: the program without bias is another)."""
    data, net, init, apply_fn = toy
    cfg = simulator.SimConfig(n_rounds=3, local_epochs=1, seg_len=64)
    grid = _toy_grid(net)
    on = scenarios.run_grid(init, apply_fn, data, grid, cfg)
    off = scenarios.run_grid(init, apply_fn, data, grid,
                             dataclasses.replace(cfg, track_bias=False))
    assert_same_semantics({"acc": off.acc, "loss": off.loss},
                          {"acc": on.acc, "loss": on.loss})
    assert np.isnan(off.bias).all()
    assert np.isfinite(on.bias).all()


def test_bias_fused_matches_reference():
    """The (N, L)-reduction bias (`bias_sq_norm_fused`) == the (L, N, N)
    materialization (`bias_sq_norm`) to float32 roundoff."""
    key = jax.random.PRNGKey(3)
    n, l = 6, 5
    p = jax.nn.softmax(jax.random.normal(key, (n,)))
    for i in range(10):
        e = _mask(jax.random.fold_in(key, i), n, l, density=0.3 + 0.07 * i)
        fused = aggregation.bias_sq_norm_fused(p, e)
        full = aggregation.bias_sq_norm(p, e)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(full),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Packed success masks.
# ---------------------------------------------------------------------------
def test_sample_success_is_packed_bool():
    rho = jnp.full((4, 4), 0.6)
    e = errors.sample_success(jax.random.PRNGKey(0), rho, 7)
    assert e.dtype == jnp.bool_
    assert np.asarray(e)[np.eye(4, dtype=bool)].all()
    e8 = errors.sample_success(jax.random.PRNGKey(0), rho, 7,
                               dtype=jnp.uint8)
    assert e8.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(e8), np.asarray(e).astype(np.uint8))


def test_bool_mask_bit_identical_to_float_on_jnp_path():
    """The packed mask is cast exactly once at the aggregation boundary:
    every jnp mechanism is BITWISE identical under bool vs float32 masks."""
    w, p, e = _setup(7, 5, 6, 16)
    ef = e.astype(jnp.float32)
    for fn in (aggregation.ra_normalized, aggregation.substitution):
        np.testing.assert_array_equal(np.asarray(fn(w, p, e)),
                                      np.asarray(fn(w, p, ef)))
    np.testing.assert_array_equal(
        np.asarray(aggregation.bias_sq_norm(p, e)),
        np.asarray(aggregation.bias_sq_norm(p, ef)),
    )


def test_mask_senders_bool_matches_float():
    _, _, e = _setup(8, 5, 4, 8)
    part = jnp.asarray([1.0, 0.0, 1.0, 0.0, 1.0])
    got = aggregation.mask_senders(e, part)
    assert got.dtype == jnp.bool_
    want = aggregation.mask_senders(e.astype(jnp.float32), part)
    np.testing.assert_array_equal(np.asarray(got).astype(np.float32),
                                  np.asarray(want))
