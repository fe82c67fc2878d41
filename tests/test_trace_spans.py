"""The round's phases and the grid runner's host work on the profiler's clock.

`simulator.ROUND_SCOPES` name every op of local training, of the exchange
and of evaluation in the compiled programs' ``op_name`` metadata;
`GridRunner.run` opens ``repro.grid/*`` spans (`Tracker.span`) that a
profiler trace records with their stats.
"""
import glob
import os
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.launch import tracker
from repro.models import smallnets

PAIRS = [("ra", "ra_normalized"), ("aayg", "substitution")]


@pytest.fixture(scope="module")
def toy():
    data = synthetic.fed_image_classification(
        n_clients=3, samples_per_client=20, seed=0
    )
    net = topology.make_network(
        topology.TABLE_II_COORDS[:3], edge_density=0.8,
        packet_len_bits=32 * 64, n_clients=3, tx_power_dbm=17.0,
    )
    init = lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=16)
    cfg = simulator.SimConfig(n_rounds=2, local_epochs=1, seg_len=64)
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", net)], protocols=PAIRS, seeds=[1, 2])
    return data, init, smallnets.apply_mlp_clf, cfg, grid


def _in_scope(op_name: str, scope: str) -> bool:
    """``scope`` is a component of the path, bare or wrapped by a
    transformation (``vmap(evaluate)``, ``jvp(local_train)``)."""
    return any(re.fullmatch(rf"(\w+\()*{scope}\)*", part)
               for part in op_name.split("/"))


@pytest.mark.parametrize("path", ["vmap", "sharded"])
def test_compiled_grid_program_names_each_round_scope(toy, path):
    data, init, apply_fn, cfg, grid = toy
    runner = scenarios.GridRunner(init, apply_fn, data, cfg)
    devices = None if path == "vmap" else jax.devices()[:1]
    runner.warmup(grid, devices=devices)
    text = "\n".join(c.as_text() for c in runner.programs.compiled())
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in simulator.ROUND_SCOPES:
        assert any(_in_scope(n, scope) for n in names), scope


def _host_spans(logdir: str) -> list[tuple[str, float, float, dict]]:
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    return sorted(
        ((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
         for plane in ProfileData.from_file(path).planes
         for line in plane.lines for e in line.events
         if e.name.startswith(tracker.SPAN_PREFIX)),
        key=lambda s: s[1])


def traced_grid_run(toy, logdir: str, stats: tracker.StatsTracker):
    """One warm `GridRunner.run` (its second call) under the profiler;
    returns the ``repro.*`` host spans the trace recorded."""
    data, init, apply_fn, cfg, grid = toy
    runner = scenarios.GridRunner(init, apply_fn, data, cfg, tracker=stats)
    runner.run(grid)                              # compile outside the trace
    jax.profiler.start_trace(logdir)
    try:
        runner.run(grid)
    finally:
        jax.profiler.stop_trace()
    return _host_spans(logdir)


def test_grid_run_spans_nest_and_share_the_run_id(toy, tmp_path):
    stats = tracker.StatsTracker()
    spans = traced_grid_run(toy, str(tmp_path), stats)
    (outer,) = [s for s in spans if s[0] == "repro.grid/run"]
    _, t0, t1, meta = outer
    assert meta == {"run": 2}
    inner = {}
    for name, s, e, m in spans:
        if name != "repro.grid/run":
            assert t0 <= s <= e <= t1, name
            assert m["run"] == 2, name
            inner.setdefault(name, []).append(m)
    assert set(inner) == {"repro.grid/validate", "repro.grid/prepare",
                          "repro.grid/dispatch", "repro.grid/collect"}
    groups = {f"{p}+{m}" for p, m in PAIRS}
    for name in ("repro.grid/prepare", "repro.grid/dispatch"):
        assert {m["group"] for m in inner[name]} == groups
        assert all(m["batch"] == 2 for m in inner[name])
    assert len(inner["repro.grid/collect"]) == 1
    # The same spans, timed into the tracker's series, once per call.
    assert len(stats.samples("grid/run_s")) == 2
    assert len(stats.samples("grid/dispatch_s")) == 2 * len(PAIRS)
