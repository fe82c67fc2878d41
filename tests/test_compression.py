"""Compression-aware model exchange (DESIGN.md §15).

Covers the traced exchange-codec layer end to end:

  * codec primitives — top-k keep counts, stochastic-quantization error
    bounds/unbiasedness, the `none` codec as an exact passthrough, and the
    host-side bits-on-air mirror (`compression.host_factor`) against its
    traced twin (`compression.bits_fraction`);
  * transmit-mask composition — `aggregation.apply_transmit_mask`
    semantics, the sparsity-aware Pallas kernel vs the jnp reference, and
    the all-ones mask as a bitwise no-op;
  * the simulator path — codec=none (and topk at ratio 1) equal to the
    codec-free program within float32 round-off for EVERY protocol,
    quantization actually perturbing the exchange, and non-participants
    never receiving encoded state;
  * the grid path — a `codecs=` axis sweeping ratio x protocol x PER in
    one dispatch with a neutral reference point, concat's neutral fill,
    admission validation, and the single-device semantics on
    forced-8-device ``('grid',)`` and 4x2 ``('grid', 'model')`` meshes
    (subprocess selfcheck, mirrored by the CI sharding job);
  * satellites — dtype-derived packet bits, the optimizer-zoo wiring
    (momentum-0 SGD == plain GD in semantics), the joint budgeted
    selection+compression policy, and `Overhead.compressed`.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _same import assert_same_semantics
from repro.core import aggregation, compression, errors, overhead, routing, \
    selection, topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.kernels import ops
from repro.launch import mesh as launch_mesh
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


# ---------------------------------------------------------------------------
# Codec primitives
# ---------------------------------------------------------------------------
def test_keep_count_and_quant_bits():
    # ceil with the epsilon nudge: 0.3 * 10 keeps exactly 3, not 4.
    assert int(compression.keep_count(0.3, 10)) == 3
    assert int(compression.keep_count(1.0, 10)) == 10
    # Clips to at least one segment / one bit.
    assert int(compression.keep_count(1e-6, 10)) == 1
    assert int(compression.quant_bits(0.25)) == 8
    assert int(compression.quant_bits(1e-6)) == 1
    # Vector ratios broadcast per client.
    ks = compression.keep_count(jnp.asarray([0.5, 1.0]), 8)
    np.testing.assert_array_equal(np.asarray(ks), [4, 8])


def test_topk_transmit_mask_ranks_by_segment_norm():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (4, 6, 8))
    mask = compression.topk_transmit_mask(w, 0.5)
    assert mask.shape == (4, 6) and mask.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(mask.sum(axis=1)), [3] * 4)
    norms = np.asarray(jnp.sum(jnp.square(w), axis=2))
    for c in range(4):
        kept = set(np.nonzero(np.asarray(mask[c]))[0])
        assert kept == set(np.argsort(-norms[c])[:3])
    # ratio=1 keeps everything; zero shard-padding rows rank last, so the
    # padded tail is never kept even at full ratio.
    np.testing.assert_array_equal(
        np.asarray(compression.topk_transmit_mask(w, 1.0)), True
    )
    w_pad = w.at[:, 4:].set(0.0)
    m = compression.topk_transmit_mask(w_pad, 1.0, n_real=4)
    np.testing.assert_array_equal(np.asarray(m[:, 4:]), False)
    np.testing.assert_array_equal(np.asarray(m[:, :4]), True)


def test_stochastic_quantize_bounds_and_unbiasedness():
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (3, 5, 16))
    # ratio 0.25 -> 8-bit: error bounded by one quantization step.
    q = compression.stochastic_quantize(w, 0.25, jax.random.PRNGKey(2))
    assert q.shape == w.shape and q.dtype == w.dtype
    step = np.asarray(jnp.max(jnp.abs(w), axis=2)) / (2.0**8 - 1.0)
    err = np.abs(np.asarray(q - w))
    assert (err <= step[:, :, None] + 1e-6).all()
    # Stochastic rounding is unbiased: averaging many independent draws
    # converges on the input.
    draws = jnp.stack([
        compression.stochastic_quantize(w, 0.25, jax.random.PRNGKey(i))
        for i in range(64)
    ])
    np.testing.assert_allclose(np.asarray(draws.mean(0)), np.asarray(w),
                               atol=float(step.max()) / 4)
    # All-zero segments quantize to exactly zero (no 0/0 poisoning).
    z = jnp.zeros((2, 3, 4))
    np.testing.assert_array_equal(
        np.asarray(compression.stochastic_quantize(z, 0.5,
                                                   jax.random.PRNGKey(3))), 0.0
    )


def test_encode_none_is_exact_passthrough():
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 6, 8))
    for codec in ("none", "topk"):
        out, tx = compression.encode(
            jnp.asarray(compression.CODEC_IDS[codec], jnp.int32),
            w, 1.0, jax.random.PRNGKey(5),
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(tx), True)
    out, tx = compression.encode(
        jnp.asarray(compression.CODEC_IDS["quant"], jnp.int32),
        w, 0.25, jax.random.PRNGKey(5),
    )
    assert not np.array_equal(np.asarray(out), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(tx), True)


def test_bits_fraction_matches_host_factor():
    for codec, ratio in (("none", 1.0), ("topk", 0.4), ("topk", 1.0),
                         ("quant", 0.25), ("quant", 0.1)):
        traced = compression.bits_fraction(
            jnp.asarray(compression.CODEC_IDS[codec], jnp.int32),
            ratio, 10,
        )
        host = compression.host_factor(codec, ratio, n_segments=10)
        np.testing.assert_allclose(float(traced), host, rtol=1e-6)
    assert compression.host_factor("none", 1.0) == 1.0
    assert compression.host_factor("topk", 0.4, n_segments=10) == 0.4
    assert compression.host_factor("quant", 0.25) == 0.25
    with pytest.raises(ValueError):
        compression.host_factor("gzip", 0.5)
    with pytest.raises(ValueError):
        compression.host_factor("topk", 0.0, n_segments=10)
    with pytest.raises(ValueError):
        compression.host_factor("topk", 0.5)   # needs n_segments


def test_packet_bits_follow_dtype():
    assert errors.dtype_bits(jnp.float32) == 32
    assert errors.dtype_bits(jnp.bfloat16) == 16
    assert errors.dtype_bits(jnp.float16) == 16
    assert errors.packet_len_bits(8) == 256
    assert errors.packet_len_bits(8, bits_per_value=16) == 128
    # The simulator warns against the dtype-derived width, not a
    # hard-coded 32: a 16-bit state halves the implied packet length.
    assert simulator.check_packet_len(128, 8, bits_per_value=16)
    assert not simulator.check_packet_len(256, 8, bits_per_value=16)
    with pytest.raises(ValueError):
        simulator.check_packet_len(256, 8, bits_per_value=16, strict=True)


# ---------------------------------------------------------------------------
# Transmit-mask composition + sparsity-aware kernel
# ---------------------------------------------------------------------------
def test_apply_transmit_mask_semantics():
    n, l = 4, 6
    key = jax.random.PRNGKey(6)
    e = jax.random.bernoulli(key, 0.6, (n, n, l))
    tx = jax.random.bernoulli(jax.random.PRNGKey(7), 0.5, (n, l))
    out = aggregation.apply_transmit_mask(e, tx)
    # Pruned sender segments are dropped for every receiver...
    ref = np.asarray(e) & np.asarray(tx)[:, None, :]
    # ...but each client always keeps its own segment.
    ref |= np.eye(n, dtype=bool)[:, :, None]
    np.testing.assert_array_equal(np.asarray(out), ref)
    # Float masks compose the same way.
    out_f = aggregation.apply_transmit_mask(
        e.astype(jnp.float32), tx.astype(jnp.float32)
    )
    np.testing.assert_array_equal(np.asarray(out_f), ref.astype(np.float32))
    # All-ones tx is the identity (modulo the diagonal the aggregation
    # modes re-add anyway).
    ones = aggregation.apply_transmit_mask(e, jnp.ones((n, l), jnp.bool_))
    np.testing.assert_array_equal(
        np.asarray(ones),
        np.asarray(e) | np.eye(n, dtype=bool)[:, :, None],
    )


@pytest.mark.parametrize("mode", ["ra_normalized", "substitution"])
def test_pallas_tx_kernel_matches_jnp(mode):
    n, l, k = 5, 12, 8
    key = jax.random.PRNGKey(8)
    w = jax.random.normal(key, (n, l, k))
    p = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(9), (n,)))
    e = jax.random.bernoulli(jax.random.PRNGKey(10), 0.7, (n, n, l))
    tx = jax.random.bernoulli(jax.random.PRNGKey(11), 0.5, (n, l))
    mode_id = jnp.asarray(aggregation.MODE_IDS[mode], jnp.int32)
    ref = aggregation.apply_mode(mode_id, w, p, e, tx=tx, impl="jnp")
    out = ops.ra_aggregate(w, p, e, tx=tx, mode=mode, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # All-ones tx == the tx-free kernel, bitwise.  The tx variant restores
    # the receiver's own row like `apply_transmit_mask`, so compare on a
    # mask that already carries the diagonal (as the simulator's always
    # does — `aggregation.mask_senders` ors in the eye).
    e_diag = e | jnp.eye(n, dtype=jnp.bool_)[:, :, None]
    base = ops.ra_aggregate(w, p, e_diag, mode=mode, interpret=True)
    full = ops.ra_aggregate(w, p, e_diag, tx=jnp.ones((n, l), jnp.bool_),
                            mode=mode, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(full))
    # Batched (vmapped grid axis) path.
    b = 3
    wb = jax.random.normal(jax.random.PRNGKey(12), (b, n, l, k))
    pb = jnp.broadcast_to(p, (b, n))
    eb = jax.random.bernoulli(jax.random.PRNGKey(13), 0.7, (b, n, n, l))
    txb = jax.random.bernoulli(jax.random.PRNGKey(14), 0.5, (b, n, l))
    refb = jax.vmap(
        lambda w_, p_, e_, t_: aggregation.apply_mode(
            mode_id, w_, p_, e_, tx=t_, impl="jnp"
        )
    )(wb, pb, eb, txb)
    outb = ops.ra_aggregate(wb, pb, eb, tx=txb, mode=mode, interpret=True)
    np.testing.assert_allclose(np.asarray(outb), np.asarray(refb), atol=1e-5)


# ---------------------------------------------------------------------------
# Simulator path: neutrality + codec effects
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("protocol,mode", [
    ("ra", "ra_normalized"), ("ra", "substitution"),
    ("aayg", "ra_normalized"), ("cfl", "ra_normalized"),
    ("ideal_cfl", "ra_normalized"), ("none", "ra_normalized"),
])
def test_codec_none_bitwise_neutral(protocol, mode):
    """codec='none' (and topk at ratio 1) == the codec-free program within
    float32 round-off, for every protocol."""
    data, net, init, apply_fn = _toy_setup()
    cfg = simulator.SimConfig(seg_len=8, local_epochs=1, n_rounds=2,
                              protocol=protocol, mode=mode, seed=0)
    sim = simulator.build_sim(init, apply_fn, data, seg_len=8,
                              local_epochs=1, n_rounds=2)
    base = jax.jit(sim.run_scenario)(simulator.make_scenario(net, cfg))
    run = jax.jit(sim.run_scenario)
    assert_same_semantics(run(simulator.make_scenario(
        net, cfg, codec="none", compress_ratio=1.0)), base)
    assert_same_semantics(run(simulator.make_scenario(
        net, cfg, codec="topk", compress_ratio=1.0)), base)


def test_quant_codec_perturbs_exchange_but_not_locals():
    data, net, init, apply_fn = _toy_setup()
    cfg = simulator.SimConfig(seg_len=8, local_epochs=1, n_rounds=2,
                              protocol="ra", seed=0)
    sim = simulator.build_sim(init, apply_fn, data, seg_len=8,
                              local_epochs=1, n_rounds=2)
    run = jax.jit(sim.run_scenario)
    base = run(simulator.make_scenario(net, cfg))
    quant = run(simulator.make_scenario(net, cfg, codec="quant",
                                        compress_ratio=0.25))
    assert not np.array_equal(np.asarray(base["loss"]),
                              np.asarray(quant["loss"]))
    # But an isolated-protocol run ("none" exchanges nothing) never sees
    # the codec: local training operates on unencoded state.
    cfg_iso = simulator.SimConfig(seg_len=8, local_epochs=1, n_rounds=2,
                                  protocol="none", seed=0)
    assert_same_semantics(
        run(simulator.make_scenario(net, cfg_iso, codec="quant",
                                    compress_ratio=0.25)),
        run(simulator.make_scenario(net, cfg_iso)),
    )


def test_nonparticipants_keep_unencoded_state():
    """A sampled-out client's parameters must not drift under a lossy
    codec: its next-round loss equals the codec-free run's."""
    data, net, init, apply_fn = _toy_setup()
    mask = np.array([[1, 1, 0]], np.float32)     # client 2 never trains
    cfg = simulator.SimConfig(seg_len=8, local_epochs=1, n_rounds=3,
                              protocol="none", seed=0)
    sim = simulator.build_sim(init, apply_fn, data, seg_len=8,
                              local_epochs=1, n_rounds=3)
    run = jax.jit(sim.run_scenario)
    base = run(simulator.make_scenario(net, cfg, participation=mask))
    quant = run(simulator.make_scenario(net, cfg, participation=mask,
                                        codec="quant", compress_ratio=0.25))
    assert_same_semantics(np.asarray(quant["loss"])[:, 2],
                          np.asarray(base["loss"])[:, 2])


# ---------------------------------------------------------------------------
# Grid path: one-dispatch codec axis
# ---------------------------------------------------------------------------
def _codec_grids(net):
    base = scenarios.ScenarioGrid.product(
        networks=[("toy", net)],
        protocols=[("ra", "ra_normalized"), ("aayg", "ra_normalized")],
        seeds=[0, 1],
    )
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)],
        protocols=[("ra", "ra_normalized"), ("aayg", "ra_normalized")],
        seeds=[0, 1],
        codecs=[("id", "none", 1.0), ("topk50", "topk", 0.5),
                ("q8", "quant", 0.25)],
    )
    return base, grid


def test_grid_codec_axis_neutral_point():
    """A ratio x protocol grid runs as one dispatch per (protocol, mode)
    group and its neutral point == the codec-free grid, within float32
    round-off."""
    data, net, init, apply_fn = _toy_setup()
    base, grid = _codec_grids(net)
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=8)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    runner.validate(grid)
    res_base = runner.run(base)
    res = runner.run(grid)
    assert len(res) == len(base) * 3
    for lbl in base.labels:
        assert_same_semantics(res.result(lbl + "/id"), res_base.result(lbl))
    # concat's neutral fill keeps codec-free rows' semantics.
    res_cat = runner.run(scenarios.ScenarioGrid.concat(base, grid))
    for lbl in base.labels:
        assert_same_semantics(res_cat.result(lbl), res_base.result(lbl))


def test_grid_codec_validation():
    _, net, _, _ = _toy_setup()
    with pytest.raises(ValueError, match="unknown codec"):
        scenarios.ScenarioGrid.product(
            networks=[("toy", net)], codecs=[("x", "gzip", 0.5)]
        )
    with pytest.raises(ValueError, match="ratio"):
        scenarios.ScenarioGrid.product(
            networks=[("toy", net)], codecs=[("x", "topk", 0.0)]
        )
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], codecs=[("x", "topk", 0.5)]
    )
    bad = scenarios.ScenarioGrid(
        scenarios=grid.scenarios._replace(
            compress_ratio=np.full((len(grid),), 2.0, np.float32)
        ),
        labels=grid.labels,
    )
    with pytest.raises(scenarios.AdmissionError, match="compress_ratio"):
        scenarios.validate_grid(bad)
    bad = scenarios.ScenarioGrid(
        scenarios=grid.scenarios._replace(
            codec_id=np.full((len(grid),), 99, np.int32)
        ),
        labels=grid.labels,
    )
    with pytest.raises(scenarios.AdmissionError, match="codec_id"):
        scenarios.validate_grid(bad)


def _multi_device_check():
    """Codec grid on ('grid',) and 4x2 ('grid', 'model') meshes ==
    single-device within float32 round-off (needs >= 8 devices)."""
    assert jax.device_count() >= 8, (
        f"needs 8 devices, have {jax.device_count()}"
    )
    data, net, init, apply_fn = _toy_setup()
    _, grid = _codec_grids(net)
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=8)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    ref = runner.run(grid)
    assert_same_semantics(runner.run(grid, devices=jax.devices()[:8]), ref)
    mesh42 = launch_mesh.grid_model_mesh(8, model_shards=2)
    assert_same_semantics(runner.run(grid, sharding=mesh42), ref)


def test_codec_grid_sharded_matches_single_device():
    """Forced 8-device sharded codec grids == single-device (within
    float32 round-off)."""
    if jax.device_count() >= 8:
        _multi_device_check()
        return
    if os.environ.get("CI"):
        # The dedicated CI sharding job runs this in-process under forced
        # 8 host devices; don't duplicate the compile in the tier-1 job.
        pytest.skip("covered by the forced-8-device CI sharding job")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--selfcheck"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, (
        f"forced-8-device selfcheck failed:\n{proc.stdout}\n{proc.stderr}"
    )
    assert "COMPRESSION-SELFCHECK-OK" in proc.stdout


# ---------------------------------------------------------------------------
# Optimizer-zoo wiring
# ---------------------------------------------------------------------------
def test_local_optimizer_sgd_is_bitwise_plain_gd():
    data, net, init, apply_fn = _toy_setup()
    cfg = simulator.SimConfig(seg_len=8, local_epochs=2, n_rounds=2,
                              protocol="ra", seed=0)
    sim = simulator.build_sim(init, apply_fn, data, seg_len=8,
                              local_epochs=2, n_rounds=2)
    sim_sgd = simulator.build_sim(init, apply_fn, data, seg_len=8,
                                  local_epochs=2, n_rounds=2,
                                  local_optimizer="sgd")
    sc = simulator.make_scenario(net, cfg)
    # The same update expression in another program: equal within
    # float32 round-off.
    assert_same_semantics(jax.jit(sim_sgd.run_scenario)(sc),
                          jax.jit(sim.run_scenario)(sc))


def test_local_optimizer_adamw_changes_training():
    data, net, init, apply_fn = _toy_setup()
    cfg = simulator.SimConfig(seg_len=8, local_epochs=2, n_rounds=2,
                              protocol="ra", seed=0)
    sim = simulator.build_sim(init, apply_fn, data, seg_len=8,
                              local_epochs=2, n_rounds=2)
    sim_adam = simulator.build_sim(init, apply_fn, data, seg_len=8,
                                   local_epochs=2, n_rounds=2,
                                   local_optimizer="adamw")
    sc = simulator.make_scenario(net, cfg)
    base = jax.jit(sim.run_scenario)(sc)
    adam = jax.jit(sim_adam.run_scenario)(sc)
    assert np.isfinite(np.asarray(adam["loss"])).all()
    assert not np.array_equal(np.asarray(base["loss"]),
                              np.asarray(adam["loss"]))
    with pytest.raises(ValueError):
        simulator.build_sim(init, apply_fn, data, seg_len=8,
                            local_epochs=2, n_rounds=2,
                            local_optimizer="lbfgs")


def test_local_optimizer_respects_participation_mask():
    """Optimizer-driven training still freezes sampled-out clients."""
    data, net, init, apply_fn = _toy_setup()
    mask = np.array([[1, 0, 1]], np.float32)
    cfg = simulator.SimConfig(seg_len=8, local_epochs=2, n_rounds=2,
                              protocol="none", seed=0)
    sim = simulator.build_sim(init, apply_fn, data, seg_len=8,
                              local_epochs=2, n_rounds=2,
                              local_optimizer="adamw")
    res = jax.jit(sim.run_scenario)(
        simulator.make_scenario(net, cfg, participation=mask)
    )
    loss = np.asarray(res["loss"])
    # Client 1 never trains: its loss trajectory is flat.
    np.testing.assert_array_equal(loss[0, 1], loss[1, 1])


# ---------------------------------------------------------------------------
# Joint budgeted selection + compression
# ---------------------------------------------------------------------------
def test_budget_allocation_respects_slot_budget():
    n = 8
    p = jnp.full((n,), 1.0 / n)
    rho = jnp.asarray(np.random.default_rng(0).uniform(0.2, 1.0, (n, n)),
                      jnp.float32)
    base = jnp.ones((n,), jnp.float32)
    for frac in (0.25, 0.5, 0.7, 1.0):
        alloc = selection.budget_allocation(base, p, rho, frac)
        a = np.asarray(alloc)
        assert (a >= 0).all() and (a <= 1).all()
        # The waterfill never exceeds the round's slot budget.
        assert a.sum() <= frac * n + 1e-5
        # Full models while budget remains: the allocation is sorted in
        # admission order with at most ONE fractional client.
        assert ((a == 0) | (a == 1)).sum() >= n - 1
    # Unavailable clients never receive leftover budget.
    base2 = base.at[:4].set(0.0)
    alloc = np.asarray(selection.budget_allocation(base2, p, rho, 1.0))
    assert (alloc[:4] == 0).all()
    assert alloc.sum() <= 4 + 1e-5


def test_budget_ratio_gates_on_policy():
    n = 5
    p = jnp.full((n,), 0.2)
    rho = jnp.ones((n, n), jnp.float32)
    base = jnp.ones((n,), jnp.float32)
    # Non-budget policies broadcast the scalar ratio unchanged.
    r = selection.budget_ratio(
        jnp.asarray(selection.POLICY_IDS["uniform"], jnp.int32),
        base, p, rho, 0.5, 0.75,
    )
    np.testing.assert_allclose(np.asarray(r), 0.75)
    # The budget policy scales the waterfill by the scenario ratio.
    rb = selection.budget_ratio(
        jnp.asarray(selection.POLICY_IDS["budget"], jnp.int32),
        base, p, rho, 0.5, 0.75,
    )
    alloc = np.asarray(selection.budget_allocation(base, p, rho, 0.5))
    np.testing.assert_allclose(np.asarray(rb), alloc * 0.75, rtol=1e-6)


def test_budget_policy_closed_loop():
    """The budget policy's realized participation stays within the slot
    budget every round, and the run stays finite under a lossy codec."""
    data, net, init, apply_fn = _toy_setup()
    cfg = simulator.SimConfig(seg_len=8, local_epochs=1, n_rounds=3,
                              protocol="ra", seed=0)
    sim = simulator.build_sim(init, apply_fn, data, seg_len=8,
                              local_epochs=1, n_rounds=3)
    sc = simulator.make_scenario(net, cfg, sampling_policy="budget",
                                 select_frac=0.5, codec="topk",
                                 compress_ratio=0.5)
    res = jax.jit(sim.run_scenario)(sc)
    sel = np.asarray(res["selected"])
    n = sel.shape[-1]
    # Participants per round <= ceil(budget): B = 0.5 * 3 = 1.5 -> <= 2.
    assert (sel.sum(axis=-1) <= np.ceil(0.5 * n)).all()
    assert np.isfinite(np.asarray(res["loss"])).all()


def test_budget_policy_in_selection_switch():
    n = 6
    p = jnp.full((n,), 1.0 / n)
    rho = jnp.asarray(np.random.default_rng(1).uniform(0.2, 1.0, (n, n)),
                      jnp.float32)
    base = jnp.ones((n,), jnp.float32)
    sig = selection.init_signals(jnp.zeros((n,)))
    mask = selection.select_clients(
        jnp.asarray(selection.POLICY_IDS["budget"], jnp.int32),
        base, sig, p, rho, jnp.asarray(0.5, jnp.float32),
    )
    alloc = np.asarray(selection.budget_allocation(base, p, rho, 0.5))
    np.testing.assert_array_equal(np.asarray(mask), (alloc > 0))


# ---------------------------------------------------------------------------
# Overhead accounting
# ---------------------------------------------------------------------------
def test_overhead_compressed():
    net = topology.paper_network(edge_density=0.5)
    _, nxt = routing.e2e_success(net.link_eps)
    ra = overhead.ra_overhead(np.asarray(nxt), 10, 38.72)
    half = ra.compressed(0.5)
    assert half.n_transmissions == ra.n_transmissions
    assert half.n_slots == int(np.ceil(ra.n_slots * 0.5))
    np.testing.assert_allclose(half.traffic_mbits, ra.traffic_mbits * 0.5)
    # Identity factor is a no-op; out-of-range factors are rejected.
    assert ra.compressed(1.0) == ra
    with pytest.raises(ValueError):
        ra.compressed(0.0)
    with pytest.raises(ValueError):
        ra.compressed(1.5)


if __name__ == "__main__":
    if "--selfcheck" in sys.argv:
        _multi_device_check()
        print("COMPRESSION-SELFCHECK-OK")
