"""Small sizes at which a cell's whole run fits a CPU test, and the faults
planted under its timed path.

The widths are cut here only; the cells on the chip run the published ones.
Each fault breaks the program underneath the harness, never the reference,
so a run that goes through it has to come out not correct.
"""
import dataclasses

import jax
import numpy as np

from bench import harness

SIZES = {
    # At the simulator's step of 0.05 the small CNN barely moves in two
    # rounds, and three bfloat16 passes then depart from the reference by
    # too little to tell from rounding; a step of 1 makes them show.
    "cnn": {"c1": 4, "c2": 8, "fc": 16, "samples_per_client": 8,
            "test_samples": 20, "lr": 1.0, "local_epochs": 2,
            "n_rounds": 3},
    # A step this large moves the small LSTM off its near-uniform start
    # within three rounds, so that precision shows in its loss.
    "charrnn": {"hidden": 16, "sequences_per_client": 6,
                "test_sequences": 8, "seq_len": 12, "lr": 75.0,
                "local_epochs": 3, "n_rounds": 3},
}
COMMON = {"n_rounds": 2, "local_epochs": 1, "seg_len": 128,
          "packet_len_bits": 4096}


def overrides(cell_name: str) -> dict:
    """Configuration keys that shrink ``cell_name`` to a CPU test's size;
    the cell's limits are kept."""
    cell = harness.resolve(cell_name)
    cfg = {**COMMON, **SIZES[cell.config["model"]]}
    widths = {k: cfg.get(k, cell.config[k]) for k in cell.model.WIDTHS}
    shapes = jax.eval_shape(lambda k: cell.model.init(k, widths),
                            jax.random.PRNGKey(0))
    cfg["n_params"] = sum(int(np.prod(l.shape))
                          for l in jax.tree_util.tree_leaves(shapes))
    return {"config": cfg}


def _after_system_init(monkeypatch, change):
    init = harness.System.__init__

    def patched(self, cell):
        init(self, cell)
        change(self)

    monkeypatch.setattr(harness.System, "__init__", patched)


def state_unchanged(monkeypatch):
    """Local training returns each client's model as it came in."""
    _after_system_init(monkeypatch, lambda s: setattr(
        s, "sim_cfg", dataclasses.replace(s.sim_cfg, local_epochs=0)))


def half_batch(monkeypatch):
    """Each client trains and is scored on the first half of its shard."""
    from repro.data.synthetic import FederatedDataset

    def halve(s):
        f = s.fed
        s.fed = FederatedDataset([x[:len(x) // 2] for x in f.train_x],
                                 [y[:len(y) // 2] for y in f.train_y],
                                 f.test_x, f.test_y)

    _after_system_init(monkeypatch, halve)


def answer_altered(monkeypatch):
    """Every scenario's last-round train loss of client 0 comes back 10%
    high, as if altered where the program produces it."""
    from repro.fl import scenarios

    run = scenarios.GridRunner.run

    def patched(self, grid, **kw):
        res = run(self, grid, **kw)
        loss = res.loss.copy()
        loss[:, -1, 0] *= np.float32(1.1)
        return dataclasses.replace(res, loss=loss)

    monkeypatch.setattr(scenarios.GridRunner, "run", patched)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}
