"""2-D ('grid', 'model') mesh execution + resumable checkpointing
(DESIGN.md §13).

Equivalence layers, mirroring tests/test_sharding.py's structure:

  * `grid_model_mesh` construction/validation and fingerprint identity;
  * model-axis size 1 has the semantics of the plain vmap path
    (degenerate (g, 1) mesh) — runs on however many devices exist;
  * `checkpoint.run_resumable` == fused `run_scenario` on one device
    within float32 round-off (two programs), and an interrupted +
    resumed run == an uninterrupted one bit for bit (one program), open
    and closed loop;
  * forced-8-device checks: a 4×2 ('grid', 'model') mesh (and the
    devices=(spec, Dm) tuple), a transformer NWP scenario under 2×2, and
    a model-sharded resumable run — all equal to single-device within
    float32 round-off (DESIGN.md §16).
    In-process when the interpreter already has >= 8 devices (the CI
    sharding job forces XLA_FLAGS=--xla_force_host_platform_device_count=8),
    else in a subprocess with the forced flag.

Run the multi-device check directly:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python tests/test_mesh2d.py --selfcheck
"""
import os
import subprocess
import sys
import tempfile

import jax
import pytest

from _same import assert_same_program, assert_same_semantics
from repro.checkpoint import checkpoint
from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.launch import mesh as launch_mesh
from repro.models import registry, smallnets


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


def _toy_grid(net, n_seeds=4):
    return scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        seeds=range(n_seeds),
    )


# ---------------------------------------------------------------------------
# Mesh builder
# ---------------------------------------------------------------------------
def test_grid_model_mesh_builder():
    mesh = launch_mesh.grid_model_mesh(1, model_shards=1)
    assert mesh.axis_names == ("grid", "model")
    assert dict(mesh.shape) == {"grid": 1, "model": 1}
    with pytest.raises(ValueError):
        launch_mesh.grid_model_mesh(1, model_shards=0)
    with pytest.raises(ValueError):
        launch_mesh.grid_model_mesh(1, model_shards=2)   # 1 % 2 != 0
    # The fingerprint distinguishes axis layouts on the same devices.
    f1 = launch_mesh.mesh_fingerprint(launch_mesh.grid_mesh(1))
    f2 = launch_mesh.mesh_fingerprint(mesh)
    assert f1 != f2
    assert f2 == launch_mesh.mesh_fingerprint(
        launch_mesh.grid_model_mesh(1, model_shards=1)
    )


def test_model_axis_size1_bit_identical():
    """A (g, 1) ('grid', 'model') mesh == the plain vmap path within
    float32 round-off, through both the sharding= mesh and the
    devices=(spec, Dm) tuple."""
    data, net, init, apply_fn = _toy_setup()
    grid = _toy_grid(net, n_seeds=3)
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=64)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    plain = runner.run(grid)
    mesh = launch_mesh.grid_model_mesh(1, model_shards=1)
    assert_same_semantics(runner.run(grid, sharding=mesh), plain)
    assert_same_semantics(runner.run(grid, devices=(1, 1)), plain)


# ---------------------------------------------------------------------------
# Resumable checkpointing (single device)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("eval_every,policy", [(1, None), (2, "loss")])
def test_resumable_matches_run_scenario(eval_every, policy):
    """Host-loop chunk runner == fused run_scenario within float32
    round-off (two programs); an interrupted run resumed from its
    checkpoint, and a completed one resumed again, == the uninterrupted
    host-loop run bit for bit (one program)."""
    data, net, init, apply_fn = _toy_setup()
    sim = simulator.build_sim(init, apply_fn, data, seg_len=64,
                              local_epochs=1, n_rounds=4,
                              eval_every=eval_every)
    cfg = simulator.SimConfig(n_rounds=4, seg_len=64, local_epochs=1,
                              eval_every=eval_every, seed=3)
    kw = dict(sampling_policy=policy, select_frac=0.67) if policy else {}
    sc = simulator.make_scenario(net, cfg, **kw)
    ref = jax.jit(sim.run_scenario)(sc)
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        full = checkpoint.run_resumable(sim, sc, ckpt_dir=d1)
        # Interrupt after 1 chunk, then resume; resuming a COMPLETE run
        # must replay nothing and return the stored metrics.
        assert checkpoint.run_resumable(
            sim, sc, ckpt_dir=d2, stop_after=1
        ) is None
        assert checkpoint.latest_step(d2) == 0
        resumed = checkpoint.run_resumable(sim, sc, ckpt_dir=d2)
        again = checkpoint.run_resumable(sim, sc, ckpt_dir=d2)
    assert_same_semantics(full, ref)
    assert_same_program(resumed, full)
    assert_same_program(again, full)


def test_resumable_validates_mesh():
    data, net, init, apply_fn = _toy_setup()
    sim = simulator.build_sim(init, apply_fn, data, seg_len=64,
                              local_epochs=1, n_rounds=2, model_shards=2)
    sc = simulator.make_scenario(
        net, simulator.SimConfig(n_rounds=2, seg_len=64, local_epochs=1)
    )
    with pytest.raises(ValueError, match="model"):
        checkpoint.run_resumable(sim, sc, ckpt_dir="/tmp/unused-mesh2d")


# ---------------------------------------------------------------------------
# Forced-8-device checks
# ---------------------------------------------------------------------------
def _multi_device_check():
    assert jax.device_count() >= 8, (
        f"needs 8 devices, have {jax.device_count()}"
    )
    data, net, init, apply_fn = _toy_setup()
    grid = _toy_grid(net, n_seeds=4)
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=64)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    ref = runner.run(grid)
    # 4×2 ('grid', 'model'): 4 scenarios across the grid axis, each
    # scenario's segment rows split over 2 model shards.
    mesh42 = launch_mesh.grid_model_mesh(8, model_shards=2)
    assert_same_semantics(runner.run(grid, sharding=mesh42), ref)
    # The devices=(spec, Dm) tuple builds the same mesh internally.
    assert_same_semantics(runner.run(grid, devices=(8, 2)), ref)
    # Degenerate 8×1 matches too (per-device programs == 1-D grid mesh).
    assert_same_semantics(
        runner.run(
            grid, sharding=launch_mesh.grid_model_mesh(8, model_shards=1)
        ), ref
    )

    # Transformer NWP scenario: 2×2 ('grid', 'model') == single-device.
    m = registry.sim_model("transformer_nwp", vocab=90)
    nwp_data = synthetic.fed_char_stream(
        n_clients=3, vocab=90, seq_len=16, sequences_per_client=8,
        test_sequences=16, seed=0,
    )
    nwp_runner = scenarios.GridRunner(m.init_fn, m.apply_fn, nwp_data, cfg)
    nwp_grid = _toy_grid(net, n_seeds=2)
    assert_same_semantics(
        nwp_runner.run(
            nwp_grid,
            sharding=launch_mesh.grid_model_mesh(4, model_shards=2),
        ),
        nwp_runner.run(nwp_grid),
    )

    # Model-sharded resumable run == fused single-device run_scenario.
    sim1 = simulator.build_sim(init, apply_fn, data, seg_len=64,
                               local_epochs=1, n_rounds=4, eval_every=2)
    sim2 = simulator.build_sim(init, apply_fn, data, seg_len=64,
                               local_epochs=1, n_rounds=4, eval_every=2,
                               model_shards=2)
    sc = simulator.make_scenario(
        net, simulator.SimConfig(n_rounds=4, seg_len=64, local_epochs=1,
                                 eval_every=2, seed=3),
        sampling_policy="loss", select_frac=0.67,
    )
    fused = jax.jit(sim1.run_scenario)(sc)
    mesh = launch_mesh.grid_model_mesh(4, model_shards=2)
    with tempfile.TemporaryDirectory() as d:
        assert checkpoint.run_resumable(
            sim2, sc, ckpt_dir=d, mesh=mesh, stop_after=1
        ) is None
        resumed = checkpoint.run_resumable(sim2, sc, ckpt_dir=d, mesh=mesh)
    assert_same_semantics(resumed, fused)


def test_2d_mesh_matches_single_device():
    """Forced 4×2 ('grid', 'model') mesh == single-device (within float32
    round-off)."""
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
    assert "MESH2D-SELFCHECK-OK" in proc.stdout


if __name__ == "__main__":
    if "--selfcheck" in sys.argv:
        _multi_device_check()
        print("MESH2D-SELFCHECK-OK")
