"""Networking deep-dive: how routing quality shapes D-FL convergence.

Sweeps relay-node count and packet length on the paper's network, prints the
Theorem-1 routing objective next to achieved accuracy — the analytical bound
tracks the empirical ordering (paper Sec. IV validation).

  PYTHONPATH=src python examples/routing_scenario.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import convergence, routing, topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.models import smallnets


def main() -> None:
    data = synthetic.fed_image_classification(n_clients=10, samples_per_client=80)
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=48)
    p = jnp.asarray(data.weights())

    print(f"{'scenario':34s} {'routing objective':>18s} {'final acc':>10s}")
    for n_relays in (0, 14, 28):
        net = topology.paper_network_with_relays(
            n_relays, edge_density=0.15, packet_len_bits=400_000,
            tx_power_dbm=17.0,
        )
        rho, _ = routing.e2e_success(net.link_eps)
        obj = float(convergence.routing_objective(p, rho))
        cfg = simulator.SimConfig(protocol="ra", n_rounds=12, local_epochs=3,
                                  seg_len=256)
        res = scenarios.simulate(init, smallnets.apply_mlp_clf, data, net,
                                 cfg)
        print(f"relays={n_relays:<3d} (V={net.n_nodes:<3d})            "
              f"{obj:18.5f} {res.mean_acc[-1]:10.3f}")

    # Bandwidth-constrained admission order (Sec. IV final paragraphs).
    net = topology.paper_network(packet_len_bits=400_000)
    rho, _ = routing.e2e_success(net.link_eps)
    order = routing.admit_homologous_routes(np.asarray(data.weights()),
                                            np.asarray(rho), n_clients=10)
    print("\nbandwidth-constrained admission order (largest-p_m first):",
          [c + 1 for c in order])


if __name__ == "__main__":
    main()
