"""Sharded grid execution (scenarios.run_grid devices=): equivalence tests.

Three layers (DESIGN.md §7, §16):

  * batch padding is pure bookkeeping — real rows untouched, filler rows
    routing-neutral (all nodes isolated), unpad drops them;
  * a 1-device ('grid',) mesh through shard_map has the semantics of the
    plain jit(vmap) path (a different program: equal within float32
    round-off);
  * a multi-device mesh (8 forced host devices) has the single-device
    path's semantics, covering the non-divisible pad (5 scenarios on 4
    devices -> pad to 8) and the wider-than-batch mesh shrink (5
    scenarios, 8 devices -> 5-device mesh).  Runs
    in-process when the interpreter already has >= 8 devices (CI forces
    XLA_FLAGS=--xla_force_host_platform_device_count=8), else in a
    subprocess with the forced flag (jax locks device count at first init).

Run this module standalone to execute the multi-device check directly:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python tests/test_sharding.py --selfcheck
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from _same import assert_same_semantics
from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
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


def _toy_grid(net, n_seeds=5):
    # One (protocol, mode) group of n_seeds scenarios: 5 on 4 devices
    # exercises pad + unpad; 5 on 8 exercises the mesh shrink.
    return scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=[("ra", "ra_normalized")],
        seeds=range(n_seeds),
    )


def test_pad_scenario_batch_nondivisible():
    """5 scenarios padded to 8: real rows bit-equal, filler isolated."""
    _, net, _, _ = _toy_setup()
    batch = _toy_grid(net, n_seeds=5).scenarios
    padded = scenarios._pad_scenario_batch(batch, 8)
    assert padded.link_eps.shape[0] == 8
    for name in ("link_eps", "seed", "protocol_id", "mode_id",
                 "aggregator", "lr"):
        np.testing.assert_array_equal(
            np.asarray(getattr(padded, name))[:5],
            np.asarray(getattr(batch, name)),
        )
    # Filler: every node isolated (routing-neutral), scalars copy row 0 so
    # a (protocol, mode)-homogeneous group stays homogeneous.
    assert not np.asarray(padded.link_eps)[5:].any()
    np.testing.assert_array_equal(
        np.asarray(padded.protocol_id)[5:],
        np.broadcast_to(np.asarray(batch.protocol_id)[0], (3,)),
    )
    # Already-divisible and down-padding edge cases.
    assert scenarios._pad_scenario_batch(batch, 5) is batch
    with pytest.raises(ValueError):
        scenarios._pad_scenario_batch(batch, 4)


def test_one_device_mesh_bit_identical():
    """shard_map over a 1-device ('grid',) mesh == the plain vmap path
    within float32 round-off, through both the devices= and sharding=
    knobs."""
    data, net, init, apply_fn = _toy_setup()
    grid = _toy_grid(net, n_seeds=3)
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=64)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    plain = runner.run(grid)
    assert_same_semantics(runner.run(grid, devices=1), plain)
    assert_same_semantics(
        runner.run(grid, sharding=launch_mesh.grid_mesh(1)), plain
    )


def _multi_device_check():
    """The forced-8-device equivalence check (in-process or subprocess)."""
    assert jax.device_count() >= 8, (
        f"needs 8 devices, have {jax.device_count()}"
    )
    data, net, init, apply_fn = _toy_setup()
    grid = _toy_grid(net, n_seeds=5)
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=64)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    ref = runner.run(grid)
    # 5 scenarios on 4 devices: pads to 8.  On 8 devices: the mesh is
    # wider than the batch and shrinks to 5 (no padding).
    for d in (4, 8):
        assert_same_semantics(runner.run(grid, devices=d), ref)
    # Mixed-protocol grid: per-(protocol, mode) groups each pad their own
    # sub-batch (2 rows on 4 devices -> pad to 4).
    mixed = scenarios.ScenarioGrid.product(
        networks=[("toy", net)],
        protocols=[("ra", "ra_normalized"), ("aayg", "ra_normalized"),
                   ("cfl", "ra_normalized")],
        seeds=range(2),
    )
    assert_same_semantics(runner.run(mixed, devices=4), runner.run(mixed))


def test_multi_device_grid_matches_single_device():
    """Forced 8-way host-device grid == single-device results (within
    float32 round-off)."""
    if jax.device_count() >= 8:
        _multi_device_check()
        return
    if os.environ.get("CI"):
        # The dedicated CI sharding job runs this in-process under forced
        # 8 host devices; don't duplicate the compile in the tier-1 job.
        pytest.skip("covered by the forced-8-device CI sharding job")
    # jax already initialized with fewer devices: rerun this module's
    # selfcheck in a subprocess with the forced host-device flag.
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
    assert "SHARDING-SELFCHECK-OK" in proc.stdout


if __name__ == "__main__":
    if "--selfcheck" in sys.argv:
        _multi_device_check()
        print("SHARDING-SELFCHECK-OK")
