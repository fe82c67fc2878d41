"""Compile-only checks of the main path's Pallas kernel for a TPU v5e.

The TPU compiler is installed alongside jaxlib and compiles for a chip that
is described rather than attached, so these tests run on a CPU-only host and
refuse what the chip's compiler would refuse (block tiling, VMEM limits,
mask dtypes) — which interpret mode cannot see.  Nothing runs: each case
lowers the public `ops.ra_aggregate(..., interpret=False)` under `jax.vmap`,
as `scenarios.run_grid` reaches it, and checks that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so a module that
touched it while being collected would break a multi-worker test run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# (B, N, L, K): the FashionMNIST CNN of chip_smoke.py (421,546 params in
# 1024-value segments) and the transformer row `ra_transformer_dm1` of
# BENCH_kernels.json.
CNN_SHAPE = (8, 10, 412, 1024)
TRANSFORMER_SHAPE = (4, 10, 2225, 512)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(sharding, shape, mode, with_tx):
    b, n, l, k = shape

    def spec(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=sharding)

    args = [spec((b, n, l, k), jnp.float32), spec((b, n), jnp.float32),
            spec((b, n, n, l), jnp.bool_)]
    if with_tx:
        args.append(spec((b, n, l), jnp.bool_))

    def grid(w, p, e, *tx):
        return jax.vmap(lambda w_, p_, e_, *t: ops.ra_aggregate(
            w_, p_, e_, tx=t[0] if t else None, mode=mode, interpret=False,
        ))(w, p, e, *tx)

    return jax.jit(grid).lower(*args).compile()


@pytest.mark.parametrize("shape,mode,with_tx", [
    (CNN_SHAPE, "ra_normalized", False),
    (CNN_SHAPE, "ra_normalized", True),
    (CNN_SHAPE, "substitution", False),
    (CNN_SHAPE, "substitution", True),
    (TRANSFORMER_SHAPE, "ra_normalized", False),
])
def test_ra_aggregate_compiles_for_v5e(one_chip, shape, mode, with_tx):
    compiled = _compile(one_chip, shape, mode, with_tx)
    assert "tpu_custom_call" in compiled.as_text()
