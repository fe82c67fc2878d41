"""End-to-end system behaviour: the paper's headline claims on the simulator
+ the production shard_map integration (subprocess with 8 host devices)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.models import smallnets


@pytest.fixture(scope="module")
def fl_setup():
    data = synthetic.fed_image_classification(
        n_clients=10, samples_per_client=80, seed=0
    )
    net = topology.paper_network(packet_len_bits=25_000)
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=48)
    return data, net, init, smallnets.apply_mlp_clf


def _run(fl_setup, protocol, mode="ra_normalized", rounds=12, **kw):
    data, net, init, apply_fn = fl_setup
    cfg = simulator.SimConfig(
        protocol=protocol, mode=mode, n_rounds=rounds, local_epochs=3,
        seg_len=256, **kw,
    )
    return scenarios.simulate(init, apply_fn, data, net, cfg)


def test_ra_beats_aayg(fl_setup):
    """Paper Fig. 2: R&A D-FL outperforms flooding AaYG (J=1)."""
    ra = _run(fl_setup, "ra")
    aayg = _run(fl_setup, "aayg", aayg_mixes=1)
    assert ra.mean_acc[-1] > aayg.mean_acc[-1] + 0.05


def test_ra_approaches_ideal_cfl(fl_setup):
    """Paper Fig. 9 limit: with good routes R&A ~ ideal error-free C-FL."""
    ra = _run(fl_setup, "ra")
    ideal = _run(fl_setup, "ideal_cfl")
    assert abs(ra.mean_acc[-1] - ideal.mean_acc[-1]) < 0.03


def test_ra_clients_consistent(fl_setup):
    """R&A clients converge to consistent accuracy (small spread)."""
    ra = _run(fl_setup, "ra")
    aayg = _run(fl_setup, "aayg", aayg_mixes=1)
    assert ra.acc_per_client[-1].std() < aayg.acc_per_client[-1].std() + 1e-9


def test_training_progresses(fl_setup):
    res = _run(fl_setup, "ra", rounds=10)
    assert res.mean_acc[-1] > res.mean_acc[0]
    assert res.loss_per_client[-1].mean() < res.loss_per_client[0].mean()


def test_shard_map_ra_exchange_matches_protocol():
    """Production dfl_step (masked collectives over a mesh axis) must equal
    the simulator's ra_round — run in a subprocess with 8 host devices."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core import dfl_step, protocols

        n = 8
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("clients",))
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (n, 4, 6)),
                  "b": jax.random.normal(key, (n, 6))}
        p = jax.nn.softmax(jax.random.normal(key, (n,)))
        rho = jnp.full((n, n), 0.7)
        ekey = jax.random.PRNGKey(42)

        # reference: host-side protocol round with the same key
        seg_len = 6
        want, e = protocols.ra_round(params, p, rho, ekey, seg_len=seg_len)

        for comm in ("all_to_all", "reduce_scatter", "psum"):
            @partial(shard_map, mesh=mesh,
                     in_specs=({"w": P("clients"), "b": P("clients")},
                               P(), P(), P()),
                     out_specs={"w": P("clients"), "b": P("clients")})
            def exchange(stacked, p, rho, k, _comm=comm):
                mine = jax.tree.map(lambda x: x[0], stacked)
                out = dfl_step.ra_exchange(mine, p, rho, k, axis="clients",
                                           seg_len=seg_len, comm=_comm)
                return jax.tree.map(lambda x: x[None], out)

            got = exchange(params, p, rho, ekey)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, err_msg=comm)
        print("SHARD_MAP_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert "SHARD_MAP_OK" in out.stdout, out.stdout + out.stderr
