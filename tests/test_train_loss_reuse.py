"""The chunk-end train loss is the next chunk's first local-epoch forward.

`advance_chunk` takes chunk c's train ``loss`` from chunk c+1's first
local epoch (`local_train`'s `jax.value_and_grad` on the same parameters
and shard) and runs a train-loss pass of its own only on the last chunk.
These tests pin what the reported number means (a standalone forward on
the chunk's end state), the program's structure (one standalone pass a
scenario, under the last-chunk cond, also under the grid vmap and the
model-axis `shard_map`), bitwise resume at every chunk boundary, and the
``grid/train_loss_reused`` counter.
"""
import tempfile

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from _same import assert_same_program, assert_same_semantics
from repro.checkpoint import checkpoint
from repro.core import protocols, topology
from repro.data import synthetic
from repro.fl import scenarios, simulator
from repro.launch import tracker as launch_tracker
from repro.models import smallnets

N_CLIENTS = 3
TEST_SIZE = 40      # differs from every shard and width, to tell passes apart


def _data(model: str) -> synthetic.FederatedDataset:
    d = 64 if model == "cnn" else 32
    f = synthetic.fed_image_classification(
        n_clients=N_CLIENTS, samples_per_client=20, d=d,
        test_size=TEST_SIZE, seed=0,
    )
    if model == "mlp":
        return f
    img = lambda x: x.reshape(-1, 8, 8, 1)
    return synthetic.FederatedDataset(
        [img(x) for x in f.train_x], list(f.train_y), img(f.test_x), f.test_y
    )


def _model(model: str):
    if model == "cnn":
        return (lambda k: smallnets.init_cnn(k, in_hw=(8, 8), c1=4, c2=8,
                                             fc=16),
                smallnets.apply_cnn)
    return (lambda k: smallnets.init_mlp_clf(k, d_in=32, d_hidden=16),
            smallnets.apply_mlp_clf)


def _net():
    return topology.make_network(
        topology.TABLE_II_COORDS[:N_CLIENTS], edge_density=0.8,
        packet_len_bits=25_000, n_clients=N_CLIENTS, tx_power_dbm=17.0,
    )


# (build_sim kwargs, make_scenario kwargs) per scenario class.
CASES = {
    "plain": ({}, {}),
    "eval_every3": ({"n_rounds": 6, "eval_every": 3}, {}),
    "participation": ({}, {"participation": np.array(
        [[1, 1, 0], [0, 1, 1], [1, 0, 1]], np.float32)}),
    "hetero_epochs": ({}, {"local_epochs": np.array([0, 1, 2], np.int32)}),
    "codec": ({}, {"codec": "quant", "compress_ratio": 0.5}),
    "local_optimizer": ({"local_optimizer": "adamw"}, {}),
    "closed_uniform": ({}, {"sampling_policy": "uniform",
                            "select_frac": 0.67}),
}


def _sim_and_scenario(model: str, case: str, **over):
    sim_kw, sc_kw = CASES[case]
    sim_kw = {"n_rounds": 3, **sim_kw, **over}
    data = _data(model)
    init, apply_fn = _model(model)
    sim = simulator.build_sim(init, apply_fn, data, seg_len=64,
                              local_epochs=2, **sim_kw)
    cfg = simulator.SimConfig(seg_len=64, local_epochs=2, seed=5,
                              cfl_aggregator=1, lr=0.1,
                              n_rounds=sim_kw["n_rounds"],
                              eval_every=sim.eval_every)
    sc = simulator.make_scenario(_net(), cfg, **sc_kw)
    return data, init, apply_fn, sim, sc


def _standalone_loss(rows, data, init, apply_fn):
    """``vmap(loss)`` over the clients' shards, from the segment rows."""
    stacked = jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf, (N_CLIENTS,) + leaf.shape),
        init(jax.random.PRNGKey(0)),
    )
    _, spec, m_params = protocols._to_segments(stacked, rows.shape[-1])
    params = protocols._from_segments(rows, spec, m_params)
    xs, ys = simulator._pad_shards(data)
    with jax.default_matmul_precision("float32"):
        return jax.jit(jax.vmap(
            lambda p, x, y: smallnets.ce_loss(apply_fn(p, x), y)
        ))(params, xs, ys)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("model", ["mlp", "cnn"])
def test_reported_loss_is_a_forward_on_the_chunk_end_state(model, case):
    """Every chunk's ``loss`` row equals a plain forward of every client's
    model over its whole shard at that chunk's end state."""
    data, init, apply_fn, sim, sc = _sim_and_scenario(model, case)
    got = jax.jit(sim.run_scenario)(sc)
    state = jax.jit(sim.init_scan)(sc)
    chunk = jax.jit(sim.advance_chunk)
    assert got["loss"].shape == (sim.n_chunks, N_CLIENTS)
    for c in range(sim.n_chunks):
        state, _ = chunk(state, sc, jnp.int32(c))
        want = _standalone_loss(state["w"], data, init, apply_fn)
        np.testing.assert_allclose(np.asarray(got["loss"][c]),
                                   np.asarray(want), rtol=1e-6,
                                   err_msg=f"chunk {c}")


# ---------------------------------------------------------------------------
# Structure: one standalone train-loss pass a scenario, under a cond.
# ---------------------------------------------------------------------------
def _subjaxprs(eqn):
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for i, v in enumerate(vals):
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                v = v.jaxpr
            if isinstance(v, jax.extend.core.Jaxpr):
                tag = (f"cond#{id(eqn)}.{i}" if eqn.primitive.name == "cond"
                       else eqn.primitive.name)
                yield tag, v


def _shard_matmul_paths(jaxpr, shard: int, path=()):
    """The enclosing higher-order primitives of every matmul that reads a
    train shard (an operand with a ``shard``-long axis)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                shard in getattr(v.aval, "shape", ()) for v in eqn.invars):
            yield path
        for tag, sub in _subjaxprs(eqn):
            yield from _shard_matmul_paths(sub, shard, path + (tag,))


def _stack2(tree):
    return jax.tree.map(lambda x: jnp.stack([x, x]), tree)


def _traced_programs(sim, sc):
    """name -> (jaxpr, scans around the chunk body) of each program form."""
    c = jnp.int32(0)
    state = jax.jit(sim.init_scan)(sc)
    out = {"chunk": (jax.make_jaxpr(sim.advance_chunk)(state, sc, c), 0)}
    out["chunk_vmap"] = (jax.make_jaxpr(
        jax.vmap(sim.advance_chunk, in_axes=(0, 0, None))
    )(_stack2(state), _stack2(sc), c), 0)
    out["grid_vmap"] = (jax.make_jaxpr(jax.vmap(sim.run_scenario))(
        _stack2(sc)), 1)
    return out


@pytest.mark.parametrize("eval_every", [1, 3])
def test_train_loss_pass_runs_only_under_the_last_chunk_cond(eval_every):
    """The chunk program holds no standalone train-loss forward of its own:
    the only one sits in one branch of one cond (the last chunk's), in the
    chunk program, under the grid vmap (whose chunk index is unbatched, so
    the cond stays a branch) and under the model-axis shard_map."""
    data, init, apply_fn, sim, sc = _sim_and_scenario(
        "mlp", "plain", n_rounds=6, eval_every=eval_every)
    shard = int(simulator._pad_shards(data)[0].shape[1])
    assert shard not in (TEST_SIZE, 32, 16, 10, N_CLIENTS, 64)
    programs = _traced_programs(sim, sc)

    sim2 = simulator.build_sim(init, apply_fn, data, seg_len=64,
                               local_epochs=2, n_rounds=6,
                               eval_every=eval_every, model_shards=2)
    mesh = jax.sharding.AbstractMesh((1, 2), ("grid", "model"))
    sharded = shard_map(jax.vmap(sim2.run_scenario), mesh=mesh,
                        in_specs=(P("grid"),), out_specs=P("grid"),
                        check_vma=False)
    programs["model_shard_map"] = (jax.make_jaxpr(sharded)(_stack2(sc)), 1)

    for name, (jaxpr, outer_scans) in programs.items():
        paths = list(_shard_matmul_paths(jaxpr.jaxpr, shard))
        in_cond = [p for p in paths if any(t.startswith("cond#") for t in p)]
        standalone = [p for p in paths if p not in in_cond
                      and p.count("scan") == outer_scans]
        assert not standalone, (name, standalone)
        assert in_cond, name
        branches = {t for p in in_cond for t in p if t.startswith("cond#")}
        assert len(branches) == 1, (name, branches)
    assert sim.train_loss_passes == 1


def test_grid_program_lowers_the_last_chunk_pass_as_a_branch():
    """In the lowered grid program the last-chunk cond is a real branch
    (``stablehlo.case``), not a select that runs the pass every chunk."""
    data, init, apply_fn, sim, sc = _sim_and_scenario("mlp", "plain")
    text = jax.jit(jax.vmap(sim.run_scenario)).lower(_stack2(sc)).as_text()
    assert text.count("stablehlo.case") == 1


# ---------------------------------------------------------------------------
# Resume and the counter.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("eval_every", [1, 3])
def test_resume_at_every_chunk_boundary_is_bitwise(eval_every):
    """`run_resumable` stopped at each chunk boundary and resumed returns
    the uninterrupted run's metrics bit for bit (one program), and
    `run_scenario`'s within float32 round-off (the host loop and the
    fused scan are two programs; DESIGN.md §16)."""
    data, init, apply_fn, sim, sc = _sim_and_scenario(
        "mlp", "plain", n_rounds=3 * eval_every, eval_every=eval_every)
    ref = jax.jit(sim.run_scenario)(sc)
    with tempfile.TemporaryDirectory() as d:
        whole = checkpoint.run_resumable(sim, sc, ckpt_dir=d)
    assert sorted(whole) == sorted(ref)
    for stop in range(1, sim.n_chunks):
        with tempfile.TemporaryDirectory() as d:
            assert checkpoint.run_resumable(sim, sc, ckpt_dir=d,
                                            stop_after=stop) is None
            assert checkpoint.latest_step(d) == stop - 1
            got = checkpoint.run_resumable(sim, sc, ckpt_dir=d)
        assert_same_program(got, whole)
        assert_same_semantics(got, ref)


@pytest.mark.parametrize("n_rounds,eval_every", [(4, 1), (6, 2), (3, 3)])
def test_grid_counts_reused_train_losses(n_rounds, eval_every):
    """``grid/train_loss_reused`` adds (n_chunks - 1) per real scenario,
    filler rows excluded; 0 when one chunk covers every round."""
    data = _data("mlp")
    init, apply_fn = _model("mlp")
    stats = launch_tracker.StatsTracker()
    cfg = simulator.SimConfig(seg_len=64, local_epochs=1, n_rounds=n_rounds,
                              eval_every=eval_every)
    runner = scenarios.GridRunner(init, apply_fn, data, cfg, tracker=stats)
    grid = scenarios.ScenarioGrid.product(
        networks=[("toy", _net())],
        protocols=[("ra", "ra_normalized"), ("none", "ra_normalized")],
        seeds=[0, 1, 2],
    )
    n_chunks = n_rounds // eval_every
    runner.run(grid, pad_to=4)           # 2 groups of 3, padded to 4
    assert stats.counter("grid/train_loss_reused") == (n_chunks - 1) * 6
    runner.run(grid)
    assert stats.counter("grid/train_loss_reused") == (n_chunks - 1) * 12
