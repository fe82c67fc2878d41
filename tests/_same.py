"""The suite's two ways of comparing simulator results (DESIGN.md §16).

* `assert_same_program` — bitwise (NaN equals NaN).  For comparisons whose
  two sides run the same compiled executable on the same inputs: the
  scalar `scenarios.simulate` against the one-point grid, an interrupted
  `run_resumable` against an uninterrupted one, determinism and purity
  checks, the same runner and batch bucket called twice.
* `assert_same_semantics` — a stated float32 tolerance on the float
  fields (``acc``, ``loss``, ``bias``, model rows), exact on the rest
  (``selected`` masks, integer fields).  For comparisons of
  programs that differ by design: a batch of G against batches of 1, the
  ``('grid',)`` and ``('grid', 'model')`` meshes against one device,
  bucket-padded serving and the router against a direct run,
  `run_resumable`'s host loop against the fused `run_scenario` scan, the
  closed loop's uniform policy against the open loop, and neutral codec
  or dynamic axes against the plain grid.

Two XLA programs of the same float32 math may fuse and reassociate sums
differently, so they agree to a few ulp, not bit for bit.  Across the
suite, run in one process and under ``-n 6 --dist loadfile``, the widest
gap these comparisons showed on the CPU was 4.8e-7 absolute and 7.5e-7
relative (1-4 ulp, the same in both runs): every element sits at least 13
times inside ``ATOL + RTOL * |want|``, and a real difference falls far
outside it (`tests/test_same.py` holds the control).

Both accept a `GridResult`, a `SimResult`, a metrics dict, or any pytree
of arrays, and compare field by field.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

RTOL = 1e-5
ATOL = 1e-6


def _fields(result) -> dict[str, np.ndarray]:
    """``result`` as field path -> numpy array (None fields and a
    `GridResult`'s labels left out: they name rows, they are not results)."""
    if dataclasses.is_dataclass(result):
        result = {f.name: getattr(result, f.name)
                  for f in dataclasses.fields(result) if f.name != "labels"}
    leaves, _ = jax.tree_util.tree_flatten_with_path(result)
    return {jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in leaves}


def _pairs(got, want):
    g, w = _fields(got), _fields(want)
    assert g.keys() == w.keys(), (sorted(g), sorted(w))
    return [(k, g[k], w[k]) for k in g]


def assert_same_program(got, want) -> None:
    """Bitwise equality, NaN equal to NaN: both sides ran one executable."""
    for name, a, b in _pairs(got, want):
        np.testing.assert_array_equal(a, b, err_msg=name, strict=True)


def assert_same_semantics(got, want) -> None:
    """Equality within float32 round-off on float fields (`RTOL`, `ATOL`,
    NaN equal to NaN); ``selected`` and non-float fields compare exactly."""
    for name, a, b in _pairs(got, want):
        if "selected" in name or a.dtype.kind not in "fc":
            np.testing.assert_array_equal(a, b, err_msg=name, strict=True)
        else:
            assert a.shape == b.shape, (name, a.shape, b.shape)
            np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL,
                                       equal_nan=True, err_msg=name)
