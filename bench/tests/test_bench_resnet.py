"""The `resnet18gn_cifar10` configuration at a CPU test's size: the
program's ResNet-GN against the configuration's plain reference, the
reference's GroupNorm and FLOP counter against hand computations, and a
whole run of `resnet18gn_cifar10.grid` that is correct when sound and not
correct with its timed path broken (`_tiny.FAULTS`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.tests import _tiny

CELL = "resnet18gn_cifar10.grid"
SEED = 3_000_000_019
# Four stages of two blocks at an eighth of the published widths, 8x8
# inputs.  The step is 25x the cell's: at 0.002 the small model barely
# moves in two rounds, and leaving training out would not show.
TINY = {"stage_widths": [8, 16, 32, 64], "in_hw": [8, 8],
        "samples_per_client": 8, "test_samples": 20, "lr": 0.05,
        "n_rounds": 2, "local_epochs": 1, "seg_len": 128,
        "packet_len_bits": 4096}


def _cell():
    return harness.resolve(CELL, overrides={"config": TINY})


def _widths(cell):
    return {k: cell.config[k] for k in cell.model.WIDTHS}


def _overrides():
    cell = _cell()
    shapes = jax.eval_shape(lambda k: cell.model.init(k, _widths(cell)),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    return {"config": {**TINY, "n_params": n}}


def _program_and_reference():
    from repro.models import smallnets

    cell = _cell()
    w = _widths(cell)
    params = smallnets.init_resnet(jax.random.PRNGKey(7), **w)
    x = jax.random.normal(jax.random.PRNGKey(8), (6, 8, 8, 3))
    y = jnp.arange(6) % 10
    return cell, w, params, x, y


def test_program_logits_match_the_reference():
    from repro.models import smallnets

    cell, w, params, x, _ = _program_and_reference()
    with jax.default_matmul_precision("highest"):
        got = smallnets.apply_resnet(params, x)
    want = cell.model.apply(params, x, reference.Matmuls("highest"), w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_one_gd_step_matches_the_reference():
    """One full-batch GD step through the program's custom convolution
    VJP (strided, 1x1 and cin-3 weight gradients) and GroupNorm lands on
    the reference's parameters."""
    from repro.models import smallnets

    cell, w, params, x, y = _program_and_reference()
    mm = reference.Matmuls("highest")

    def step(loss):
        g = jax.grad(loss)(params)
        return jax.tree.map(lambda p, d: p - 0.05 * d, params, g)

    with jax.default_matmul_precision("highest"):
        got = step(lambda p: smallnets.ce_loss(smallnets.apply_resnet(p, x), y))
    want = step(lambda p: reference.ce_loss(cell.model.apply(p, x, mm, w), y))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_group_norm_is_the_written_formula(groups):
    """y = (x - mean) / sqrt(var + 1e-5) * scale + bias, with mean and
    biased variance over each sample's H x W x (C / G) block."""
    from repro.models import smallnets

    rng = np.random.default_rng(0)
    x = rng.normal(2.0, 3.0, size=(3, 5, 4, 8)).astype(np.float32)
    scale = rng.normal(size=(groups, 8 // groups)).astype(np.float32)
    bias = rng.normal(size=(groups, 8 // groups)).astype(np.float32)
    want = np.empty_like(x, dtype=np.float64)
    cg = 8 // groups
    for b in range(3):
        for g in range(groups):
            blk = x[b, :, :, g * cg:(g + 1) * cg].astype(np.float64)
            mu = blk.sum() / blk.size
            var = ((blk - mu) ** 2).sum() / blk.size
            want[b, :, :, g * cg:(g + 1) * cg] = (
                (blk - mu) / np.sqrt(var + 1e-5) * scale[g] + bias[g])
    p = {"scale": jnp.asarray(scale), "bias": jnp.asarray(bias)}
    for fn in (smallnets.group_norm, _cell().model.group_norm):
        np.testing.assert_allclose(np.asarray(fn(jnp.asarray(x), p)), want,
                                   rtol=2e-5, atol=2e-5)
    init = smallnets.init_group_norm(groups, 8)
    assert init["scale"].shape == (groups, cg)
    assert (np.asarray(init["scale"]) == 1).all()
    assert (np.asarray(init["bias"]) == 0).all()


def test_forward_flops_is_the_hand_count():
    """2 * Ho * Wo * kh * kw * cin * cout per convolution plus the output
    layer, counted by hand at the published widths on 32x32x3."""
    mod = _cell().model
    w = {"in_ch": 3, "n_classes": 10, "stage_blocks": [2, 2, 2, 2],
         "stage_widths": [64, 128, 256, 512], "gn_groups": 2}
    stem = 2 * 32 * 32 * 9 * 3 * 64                          # 3,538,944
    stage0 = 4 * (2 * 32 * 32 * 9 * 64 * 64)                 # 301,989,888
    # Each later stage: conv1 (cin = c / 2) at half the size, its 1x1
    # projection, and three c x c convolutions; the same count for all.
    later = 2 * 16 * 16 * (9 * 64 * 128 + 64 * 128 + 3 * 9 * 128 * 128)
    fc = 2 * 512 * 10
    assert later == 268_435_456
    assert mod.forward_flops(w, (32, 32, 3)) == (
        stem + stage0 + 3 * later + fc) == 1_110_845_440


def test_sound_run_is_correct():
    line = harness.run_cell(CELL, SEED, 0.5, False, require_tpu=False,
                            overrides=_overrides())
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(_tiny.FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    _tiny.FAULTS[fault](monkeypatch)
    line = harness.run_cell(CELL, SEED, 0.5, False, require_tpu=False,
                            overrides=_overrides())
    assert line["correct"] is False, line["checks"]


def test_control_departs_from_the_reference():
    _, rows, c, system = harness.measure(CELL, SEED, 0.5, False,
                                         require_tpu=False,
                                         overrides=_overrides())
    sound = harness.check(rows, c, system)
    control = harness.check(rows, c, system, control="bf16_3x")
    assert control["loss_gap"] > 10 * sound["loss_gap"]
