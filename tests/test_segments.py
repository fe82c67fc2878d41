"""Segment codec round-trip: golden + property tests (DESIGN.md §13).

The segment-native refactor moved `protocols._to_segments` /
`_from_segments` out of the per-round hot loop to the run_scenario()
boundary; these tests pin the codec contract that move relies on:

  * golden layout — flatten order is tree-flatten order, the final
    segment zero-pads, and values land exactly where the spec says;
  * bitwise round-trip over realistic (transformer-shaped) pytrees —
    odd leaf sizes, prime total parameter counts, bf16 leaves, and
    zero-size leaves all survive `_from_segments(_to_segments(x))`
    unchanged;
  * boundary segmentation == per-round segmentation — re-encoding
    between exchange rounds (the old hot-loop behaviour) is bitwise
    equivalent to staying in segment space (the new behaviour), so the
    refactor cannot have changed any trajectory.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import errors, protocols
from repro.models import registry


def _stack(tree, n):
    return jax.tree.map(lambda l: jnp.broadcast_to(l, (n,) + l.shape), tree)


def _roundtrip(stacked, seg_len):
    seg, spec, m = protocols._to_segments(stacked, seg_len)
    return seg, protocols._from_segments(seg, spec, m)


# ---------------------------------------------------------------------------
# Golden layout
# ---------------------------------------------------------------------------
def test_to_segments_golden_layout():
    """Hand-checked layout: 2 clients, leaves of 3 + 4 params, seg_len=4."""
    a = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)          # 3 params
    b = 10.0 + jnp.arange(8, dtype=jnp.float32).reshape(2, 2, 2)  # 4 params
    seg, spec, m = protocols._to_segments({"a": a, "b": b}, seg_len=4)
    assert m == 7
    assert seg.shape == (2, 2, 4)            # ceil(7/4)=2 segments
    # Client 0 flat vector: a-row then b-row, one zero of padding.
    np.testing.assert_array_equal(
        np.asarray(seg[0]).reshape(-1),
        [0.0, 1.0, 2.0, 10.0, 11.0, 12.0, 13.0, 0.0],
    )
    np.testing.assert_array_equal(
        np.asarray(seg[1]).reshape(-1),
        [3.0, 4.0, 5.0, 14.0, 15.0, 16.0, 17.0, 0.0],
    )
    back = protocols._from_segments(seg, spec, m)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(back["b"]), np.asarray(b))


# ---------------------------------------------------------------------------
# Property: bitwise round-trip on awkward shapes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sizes,seg_len", [
    ([7, 11, 13], 8),        # odd leaf sizes, prime total M=31
    ([1, 1, 1], 4),          # tiny leaves, heavy padding
    ([97], 16),              # single prime leaf
    ([5, 0, 9], 4),          # zero-size leaf in the middle
    ([0, 3], 2),             # zero-size leaf first
])
def test_roundtrip_bitwise_odd_shapes(sizes, seg_len):
    key = jax.random.PRNGKey(0)
    leaves = {}
    for i, s in enumerate(sizes):
        key, k = jax.random.split(key)
        leaves[f"l{i}"] = jax.random.normal(k, (3, s), jnp.float32)
    seg, back = _roundtrip(leaves, seg_len)
    assert seg.shape[2] == seg_len
    assert seg.shape[1] == errors.num_segments(sum(sizes), seg_len)
    for k_, v in leaves.items():
        np.testing.assert_array_equal(np.asarray(back[k_]), np.asarray(v))


def test_roundtrip_bitwise_bf16():
    """All-bf16 pytree: the codec keeps the dtype and every bit."""
    key = jax.random.PRNGKey(1)
    tree = {
        "w": jax.random.normal(key, (2, 5, 7), jnp.float32).astype(jnp.bfloat16),
        "b": jnp.asarray([[1.5, -2.25, 3.0]] * 2, jnp.bfloat16),
    }
    seg, back = _roundtrip(tree, seg_len=4)
    assert seg.dtype == jnp.bfloat16
    for k, v in tree.items():
        assert back[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(back[k]).view(np.uint16), np.asarray(v).view(np.uint16)
        )


def test_roundtrip_transformer_pytree():
    """The real thing: a tiny transformer's params, batched over clients."""
    m = registry.sim_model("transformer_nwp", vocab=53)   # prime vocab
    params = m.init_fn(jax.random.PRNGKey(2))
    stacked = _stack(params, 3)
    sizes = [int(np.prod(l.shape)) for l in jax.tree.leaves(params)]
    total = sum(sizes)
    for seg_len in (64, 127):                 # incl. prime seg_len
        seg, back = _roundtrip(stacked, seg_len)
        assert seg.shape == (3, errors.num_segments(total, seg_len), seg_len)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            back, stacked,
        )


def test_mixed_dtype_promotes_documented():
    """Mixed-dtype trees promote through the (single-dtype) row matrix;
    values survive exactly under the promotion (f32 holds every bf16)."""
    tree = {
        "lo": jnp.asarray([[1.5, 2.5]], jnp.bfloat16),
        "hi": jnp.asarray([[3.25, -4.75, 5.0]], jnp.float32),
    }
    seg, back = _roundtrip(tree, seg_len=4)
    assert seg.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(back["lo"]), np.asarray(tree["lo"], np.float32)
    )
    np.testing.assert_array_equal(np.asarray(back["hi"]), np.asarray(tree["hi"]))


# ---------------------------------------------------------------------------
# Boundary segmentation == per-round segmentation
# ---------------------------------------------------------------------------
def test_boundary_vs_per_round_segmentation():
    """k exchange rounds staying in segment space (new boundary
    segmentation) are bitwise identical to re-encoding the pytree every
    round (the old hot-loop behaviour)."""
    n, seg_len, rounds = 4, 8, 3
    key = jax.random.PRNGKey(3)
    k_tree, k_p, key = jax.random.split(key, 3)
    tree = {
        "a": jax.random.normal(k_tree, (n, 3, 7), jnp.float32),
        "b": jax.random.normal(k_tree, (n, 11), jnp.float32),
    }
    p = jax.nn.softmax(jax.random.normal(k_p, (n,)))
    rho = jnp.full((n, n), 0.8, jnp.float32)
    mode = jnp.int32(0)

    def one_round(seg, k):
        out, _e = protocols.ra_round_seg(seg, p, rho, k, mode)
        return out

    keys = jax.random.split(key, rounds)

    # New: encode once, exchange in segment space, decode once.
    seg, spec, m = protocols._to_segments(tree, seg_len)
    for k in keys:
        seg = one_round(seg, k)
    boundary = protocols._from_segments(seg, spec, m)

    # Old: encode/decode around every round.
    cur = tree
    for k in keys:
        s, sp, mm = protocols._to_segments(cur, seg_len)
        cur = protocols._from_segments(one_round(s, k), sp, mm)

    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        boundary, cur,
    )
