"""Control for the suite's comparison contract (tests/_same.py).

`assert_same_semantics` must accept round-off of a few ulp and reject
what a real fault looks like; `assert_same_program` must reject even the
round-off.  The result compared is a real closed-loop grid run.
"""
import dataclasses

import numpy as np
import pytest

from _same import assert_same_program, assert_same_semantics
from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.models import smallnets


@pytest.fixture(scope="module")
def result() -> scenarios.GridResult:
    data = synthetic.fed_image_classification(
        n_clients=3, samples_per_client=20, seed=0
    )
    net = topology.make_network(
        topology.TABLE_II_COORDS[:3], edge_density=0.8,
        packet_len_bits=2048, n_clients=3, tx_power_dbm=17.0,
    )
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=16)
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], seeds=[0, 1],
        sampling_policies=[("loss", "loss", 0.5)],
    )
    cfg = simulator.SimConfig(n_rounds=3, local_epochs=1, seg_len=64)
    return scenarios.run_grid(init, smallnets.apply_mlp_clf, data, grid,
                              cfg)


def _swap_rows(r):
    return dataclasses.replace(
        r, acc=r.acc[::-1], loss=r.loss[::-1], bias=r.bias[::-1],
        selected=r.selected[::-1],
    )


def _scale_loss(r):
    return dataclasses.replace(r, loss=r.loss * np.float32(1 + 1e-4))


def _flip_participation(r):
    sel = r.selected.copy()
    sel[0, 1, 0] = 1.0 - sel[0, 1, 0]        # one client, one round
    return dataclasses.replace(r, selected=sel)


def _ulps(x, n):
    for _ in range(n):
        x = np.nextafter(x, np.float32(np.inf), dtype=np.float32)
    return x


@pytest.mark.parametrize("corrupt", [_swap_rows, _scale_loss,
                                     _flip_participation],
                         ids=["swapped_row", "loss_1e-4", "participation"])
def test_same_semantics_rejects_a_real_difference(result, corrupt):
    with pytest.raises(AssertionError):
        assert_same_semantics(corrupt(result), result)


def test_round_off_is_the_same_semantics_not_the_same_program(result):
    """Four ulp on every float field, as two programs of the same float32
    math may show: the same semantics, not the same program."""
    noisy = dataclasses.replace(result, acc=_ulps(result.acc, 4),
                                loss=_ulps(result.loss, 4),
                                bias=_ulps(result.bias, 4))
    assert_same_semantics(noisy, result)
    with pytest.raises(AssertionError):
        assert_same_program(noisy, result)
    assert_same_program(result, result)
