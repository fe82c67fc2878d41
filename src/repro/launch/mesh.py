"""Mesh construction: production SPMD meshes + the scenario-grid mesh.

Every mesh is built by a FUNCTION (not a module-level constant) so importing
this module never touches jax device state.

Two mesh families live here:

  * `make_production_mesh` — the multi-pod dry-run meshes (DESIGN.md §5).
    TPU v5e targets:
      single pod : (16, 16)    = 256 chips, axes ('data', 'model')
      multi-pod  : (2, 16, 16) = 512 chips, axes ('pod', 'data', 'model')
  * `grid_mesh` — a 1-D ('grid',) mesh over whole devices, used by
    `repro.fl.scenarios` to shard a batched scenario sweep so each device
    runs its slice of the grid with no cross-device collectives in the hot
    loop (DESIGN.md §7).
  * `grid_model_mesh` — the 2-D ('grid', 'model') extension (DESIGN.md
    §13): the model axis additionally shards each scenario's segment
    dimension, so transformer-scale models split their (N, S, K) exchange
    state across the devices of one model-sharding group.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

# Published per-chip peaks, keyed by `jax.Device.device_kind`.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s interconnect = 4 links x 50 GB/s).
CHIP_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,        # bytes/s
        "ici_bw": 50e9,         # bytes/s per link
    },
}

# The chip the production dry-run meshes describe.
PRODUCTION_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}"
        ) from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def data_axes(*, multi_pod: bool = False):
    return ("pod", "data") if multi_pod else ("data",)


GRID_AXIS = "grid"

# Axis name for model-axis (segment) sharding inside each scenario —
# DESIGN.md §13.  Must match `repro.fl.simulator.MODEL_AXIS` (kept as an
# independent literal so this module stays import-light; a mesh built here
# and a sim built with the default `model_axis` always agree).
MODEL_AXIS = "model"


def _resolve_devices(
    devices: Sequence[jax.Device] | int | None, *, what: str
) -> list[jax.Device]:
    """Normalize a device spec (None = all, int = first k, or a sequence)."""
    if devices is None:
        return list(jax.devices())
    if isinstance(devices, int):
        avail = jax.devices()
        if not 1 <= devices <= len(avail):
            raise ValueError(
                f"{what}: asked for {devices} devices, have {len(avail)}"
            )
        return avail[:devices]
    return list(devices)


def grid_mesh(devices: Sequence[jax.Device] | int | None = None) -> jax.sharding.Mesh:
    """1-D ``(GRID_AXIS,)`` mesh for sharding a scenario batch over devices.

    Args:
      devices: the devices to shard over — a sequence of `jax.Device`, an
        int (the first k of `jax.devices()`), or None for all devices.

    Returns:
      A `jax.sharding.Mesh` with one axis named ``'grid'``.  Scenarios are
      independent, so the grid axis needs no collectives; any device subset
      (including a single device) is a valid mesh.
    """
    devices = _resolve_devices(devices, what="grid_mesh")
    return jax.sharding.Mesh(np.asarray(devices), (GRID_AXIS,))


def grid_model_mesh(
    devices: Sequence[jax.Device] | int | None = None,
    *,
    model_shards: int = 1,
) -> jax.sharding.Mesh:
    """2-D ``(GRID_AXIS, MODEL_AXIS)`` mesh: scenario-parallel × model-shard.

    The mesh of DESIGN.md §13: the grid axis shards a scenario batch
    (independent rows, no collectives) while the model axis shards each
    scenario's SEGMENT dimension — every group of ``model_shards``
    consecutive devices forms one model-sharding group whose collectives
    (`all_gather` of the full segment rows before local training) stay
    inside the group.

    Args:
      devices: a device sequence, an int (first k of `jax.devices()`), or
        None for all devices.  The count must be a multiple of
        ``model_shards``.
      model_shards: Dm, the model-axis size.  ``model_shards=1`` is a
        degenerate (g, 1) mesh — per-device programs identical to
        `grid_mesh`'s.

    Returns:
      A mesh of shape ``(len(devices) // model_shards, model_shards)``
      with axes ``('grid', 'model')``.
    """
    devs = _resolve_devices(devices, what="grid_model_mesh")
    if model_shards < 1:
        raise ValueError(f"model_shards={model_shards} must be >= 1")
    if len(devs) % model_shards:
        raise ValueError(
            f"grid_model_mesh: {len(devs)} devices do not factor into "
            f"model_shards={model_shards} groups"
        )
    arr = np.asarray(devs).reshape(len(devs) // model_shards, model_shards)
    return jax.sharding.Mesh(arr, (GRID_AXIS, MODEL_AXIS))


def mesh_fingerprint(mesh: jax.sharding.Mesh) -> tuple:
    """A hashable host-side identity for a mesh: axis names + platform +
    device ids.

    The compiled-program cache key component (`repro.fl.scenarios.
    ProgramCache`): two dispatches may share an executable only when they
    target the SAME devices under the same axis layout, so a serving tier
    that switches device subsets (1-device vs full mesh, or a shrunk mesh
    for a small batch) keeps one warm program per subset instead of
    silently reusing a program compiled for different hardware.  Reads
    only device metadata — no device sync.
    """
    return (
        tuple(mesh.axis_names),
        tuple((d.platform, d.id) for d in mesh.devices.flat),
    )
