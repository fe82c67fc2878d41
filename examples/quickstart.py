"""Quickstart: R&A D-FL on the paper's 10-client Table-II network.

Runs the full paper pipeline on CPU in ~1 minute:
  topology -> min-E2E-PER routing -> 10 clients x local training ->
  segmented lossy delivery -> adaptive-normalized aggregation,
and compares against the AaYG flooding baseline and ideal C-FL.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import routing, topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.models import smallnets

N_ROUNDS = 15


def main() -> None:
    # 1. The paper's network (Table II coordinates), harsh channel so
    #    communication errors are visible.
    net = topology.make_network(
        topology.TABLE_II_COORDS, edge_density=0.5, packet_len_bits=100_000,
        n_clients=10, tx_power_dbm=17.0,
    )
    rho, next_hop = routing.e2e_success(net.link_eps)
    print(f"network: 10 clients, {int(np.asarray(net.adjacency).sum()) // 2} links, "
          f"mean E2E packet success {np.asarray(rho)[~np.eye(10, dtype=bool)].mean():.3f}")
    route = routing.reconstruct_route(np.asarray(next_hop), 4, 9)
    print(f"min-PER route 5 -> 10 (paper numbering): {[r + 1 for r in route]}")

    # 2. Non-iid federated data (one class per client, synthetic stand-in).
    data = synthetic.fed_image_classification(n_clients=10, samples_per_client=80)

    # 3. Train under each protocol.
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=48)
    for proto, mode, label in [
        ("ra", "ra_normalized", "R&A D-FL + adaptive normalization (paper)"),
        ("ra", "substitution", "R&A D-FL + model substitution [12]"),
        ("aayg", "ra_normalized", "AaYG flooding D-FL [13,14]"),
        ("ideal_cfl", "ra_normalized", "ideal error-free C-FL"),
    ]:
        cfg = simulator.SimConfig(protocol=proto, mode=mode, n_rounds=N_ROUNDS,
                                  local_epochs=3, seg_len=256)
        res = scenarios.simulate(init, smallnets.apply_mlp_clf, data, net,
                                 cfg)
        print(f"{label:48s} acc={res.mean_acc[-1]:.3f} "
              f"spread={res.acc_per_client[-1].std():.3f}")


if __name__ == "__main__":
    main()
