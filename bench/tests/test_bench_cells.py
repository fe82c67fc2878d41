"""Every cell of BENCHMARK.json resolves to its own files, and the
configurations' reference models start from the program's weights."""
import json
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(cell)
    assert c.chips in (1, 4)
    assert c.traffic["kind"] in harness.WINDOWS
    assert {"setup_s"} <= {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    moved = {m["name"] for m in c.end_to_end}
    assert all(m["moves"] in moved for m in c.per_layer)
    for m in c.end_to_end + c.per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    assert set(c.limits) == {"loss_gap"}
    assert all(v > 0 for v in c.limits.values())


def test_names_units_and_paths_keep_to_the_contract():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                  "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
               for k in ("end_to_end", "per_layer") for m in SPEC[k])
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith("bench/") for f in files)
    for path in (ROOT / "bench").rglob("*"):
        if "__pycache__" not in path.parts:
            assert re.match(r"^[A-Za-z0-9_./-]+$",
                            str(path.relative_to(ROOT))), path


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.resolve("no_such_cell")


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_reduced_keys_agree_and_name_their_source_values(config):
    entry = {c["name"]: c for c in SPEC["configs"]}[config]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["reduced"]) <= set(cfg["source_values"])
    assert set(cfg["reduced"]) <= set(cfg)


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_reference_init_is_the_programs(config):
    """Same key, same widths: the reference's initial model is bitwise the
    program's, leaf for leaf, in the same segment order."""
    from repro.models import smallnets

    entry = {c["name"]: c for c in SPEC["configs"]}[config]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    mod = harness.load_module((ROOT / entry["file"]).with_suffix(".py"))
    widths = {k: cfg[k] for k in mod.WIDTHS}
    key = jax.random.PRNGKey(np.int32(123456789))
    mine = jax.tree_util.tree_flatten_with_path(mod.init(key, widths))[0]
    theirs = jax.tree_util.tree_flatten_with_path(
        smallnets.MODELS[cfg["model"]][0](key, **widths))[0]
    assert [p for p, _ in mine] == [p for p, _ in theirs]
    for (_, a), (_, b) in zip(mine, theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sum(int(np.prod(a.shape)) for _, a in mine) == cfg["n_params"]
