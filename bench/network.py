"""The paper's wireless network (Sec. III-A, V-A; Fig. 9), in NumPy.

A copy of the channel model and network builder of `repro.core.topology`,
kept with the benchmark so its inputs stay fixed: 10 clients at the
Table II coordinates plus routing-only relays dropped uniformly over twice
the area, the closest ``edge_density`` share of node pairs connected,
free-space path loss at 2.5 GHz over 30 MHz, BPSK bit errors, and packet
success ``(1 - BER) ** packet_len_bits``.  Computed in float64 (with
``log1p`` for the packet exponent) and returned as float32.
"""
from __future__ import annotations

import math

import numpy as np

FC_HZ = 2.5e9
BANDWIDTH_HZ = 30e6
NOISE_PSD_DBM_HZ = -174.0

TABLE_II_COORDS = np.array(
    [[2196, 1351], [3637, 3127], [2642, 284], [2884, 848], [5254, 596],
     [1730, 1923], [3572, 2668], [4546, 5326], [4328, 4001], [2534, 5171]],
    dtype=np.float64,
)

_erfc = np.frompyfunc(math.erfc, 1, 1)


def packet_success(dist_m: np.ndarray, packet_len_bits: int,
                   tx_power_dbm: float) -> np.ndarray:
    d_km = np.maximum(dist_m, 1.0) / 1000.0
    loss_db = 20.0 * np.log10(FC_HZ / 1e6) + 20.0 * np.log10(d_km) + 32.4
    noise_dbm = NOISE_PSD_DBM_HZ + 10.0 * np.log10(BANDWIDTH_HZ)
    snr = 10.0 ** ((tx_power_dbm - loss_db - noise_dbm) / 10.0)
    ber = 0.5 * _erfc(np.sqrt(2.0 * snr) / math.sqrt(2.0)).astype(np.float64)
    return np.exp(packet_len_bits * np.log1p(-np.minimum(ber, 1.0 - 1e-300)))


def _connect(adj: np.ndarray, dist: np.ndarray) -> None:
    """Join components through their closest cross pair until connected."""
    v = adj.shape[0]

    def components():
        seen = np.zeros(v, bool)
        comps = []
        for s in range(v):
            if seen[s]:
                continue
            stack, comp = [s], []
            seen[s] = True
            while stack:
                u = stack.pop()
                comp.append(u)
                for w in np.nonzero(adj[u])[0]:
                    if not seen[w]:
                        seen[w] = True
                        stack.append(w)
            comps.append(comp)
        return comps

    comps = components()
    while len(comps) > 1:
        best, pair = np.inf, None
        for other in comps[1:]:
            sub = dist[np.ix_(comps[0], other)]
            i, j = np.unravel_index(np.argmin(sub), sub.shape)
            if sub[i, j] < best:
                best, pair = sub[i, j], (comps[0][i], other[j])
        adj[pair[0], pair[1]] = adj[pair[1], pair[0]] = True
        comps = components()


def fig9_network(*, n_relays: int, relay_seed: int, edge_density: float,
                 packet_len_bits: int, tx_power_dbm: float):
    """(coords (V, 2), adjacency (V, V) bool, link success (V, V) float32)."""
    rng = np.random.default_rng(relay_seed)
    area = TABLE_II_COORDS.max(axis=0) * 2.0
    relays = rng.uniform(low=0.0, high=area, size=(n_relays, 2))
    coords = np.concatenate([TABLE_II_COORDS, relays], axis=0)
    v = coords.shape[0]
    diff = coords[:, None, :] - coords[None, :, :]
    dist = np.sqrt((diff ** 2).sum(-1))
    iu = np.triu_indices(v, k=1)
    n_edges = max(v - 1, int(round(edge_density * len(iu[0]))))
    sel = np.argsort(dist[iu], kind="stable")[:n_edges]
    adj = np.zeros((v, v), bool)
    adj[iu[0][sel], iu[1][sel]] = True
    adj |= adj.T
    _connect(adj, dist)
    eps = packet_success(dist, packet_len_bits, tx_power_dbm)
    eps = np.where(adj, eps, 0.0) * (1.0 - np.eye(v))
    return coords, adj, eps.astype(np.float32)
