"""Pytree + scan-state checkpointing: npz payload + JSON manifest.

Two layers (DESIGN.md §13):

  * Generic pytree save/restore — `save` gathers every leaf to host
    (sharding-aware: a sharded `jax.Array` is materialized via
    `jax.device_get`), `restore` scatters back into the structure of a
    ``like`` pytree, checking leaf count, shapes AND dtypes (the manifest
    records dtypes; a mismatch raises unless ``cast=True``) and placing
    each leaf onto ``like``'s sharding when it has one.
  * `run_resumable` — a host loop over `SimPrograms.advance_chunk` that
    checkpoints the round-scan state ``(state, rng, round_idx)`` every
    ``save_every`` chunks and resumes bitwise-identically: it jits the
    SAME `advance_chunk` the fused `run_scenario` scans over, so an
    interrupted+resumed run replays the exact per-round program.  With
    ``model_shards > 1`` the chunk programs are wrapped in a `shard_map`
    binding the sim's model axis, sharding the ``"w"`` rows' segment
    dimension; save/restore still sees global arrays (gather/scatter at
    the jit boundary).

No orbax dependency — the container is offline.
"""
from __future__ import annotations

import json
import os
import tempfile
import uuid
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map

from repro.fl import simulator

Pytree = Any


class CorruptCheckpoint(RuntimeError):
    """The checkpoint at a path is internally inconsistent — a torn
    write (manifest and arrays from different `save` calls), a missing
    payload file, or an array count that disagrees with the manifest.
    `run_resumable` treats such a checkpoint as absent and restarts from
    round 0 rather than resuming from torn state."""


def _paths(tree: Pytree) -> list[tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(jax.tree_util.keystr(kp), leaf) for kp, leaf in flat]


def save(path: str, tree: Pytree, *, step: int | None = None) -> None:
    """Write ``tree`` to ``path`` (a directory), overwriting any previous
    checkpoint there.

    Sharding-aware gather: each leaf goes through `jax.device_get`, so a
    `jax.Array` sharded over a mesh (e.g. the model-axis-sharded ``"w"``
    rows of a ``model_shards > 1`` sim) is materialized as its full
    global value before hitting disk.

    Crash-safe: both files are staged in a temp dir on the same
    filesystem, then atomically `os.replace`d into place — arrays first,
    manifest last, so the manifest is the commit point (a crash leaves
    either the previous checkpoint or the new one, never a half-written
    file).  A per-save ``save_id`` is stamped into BOTH files; `restore`
    rejects the one torn window the ordering leaves open (new arrays
    with the old manifest) as `CorruptCheckpoint`.
    """
    os.makedirs(path, exist_ok=True)
    leaves = _paths(tree)
    save_id = uuid.uuid4().hex
    arrays = {
        f"leaf_{i}": np.asarray(jax.device_get(l))
        for i, (_, l) in enumerate(leaves)
    }
    treedef = jax.tree_util.tree_structure(tree)
    manifest = {
        "keys": [k for k, _ in leaves],
        "treedef": str(treedef),
        "step": step,
        "dtypes": [str(a.dtype) for a in arrays.values()],
        "shapes": [list(a.shape) for a in arrays.values()],
        "save_id": save_id,
    }
    tmp = tempfile.mkdtemp(prefix=".ckpt-tmp-", dir=path)
    try:
        np.savez(os.path.join(tmp, "arrays.npz"), __save_id__=save_id,
                 **arrays)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(os.path.join(tmp, "arrays.npz"),
                   os.path.join(path, "arrays.npz"))
        os.replace(os.path.join(tmp, "manifest.json"),
                   os.path.join(path, "manifest.json"))
    finally:
        for name in ("arrays.npz", "manifest.json"):
            try:
                os.unlink(os.path.join(tmp, name))
            except FileNotFoundError:
                pass
        os.rmdir(tmp)


def restore(path: str, like: Pytree, *, cast: bool = False) -> Pytree:
    """Restore into the structure of ``like`` (leaf count, shapes and
    dtypes checked).

    Args:
      path: checkpoint directory written by `save`.
      like: a pytree of arrays (or shape/dtype structs) giving the target
        structure.  Leaves that carry a ``.sharding`` (committed
        `jax.Array`s) get the restored value `jax.device_put` onto that
        sharding; other leaves come back on the default device.
      cast: a stored dtype that differs from ``like``'s raises
        ValueError unless ``cast=True``, in which case the leaf is cast
        to the target dtype (the manifest records the stored dtypes, so
        the mismatch message names both sides).

    Returns:
      ``like``'s structure filled with the stored values.

    Raises:
      FileNotFoundError: no manifest at ``path`` (no checkpoint).
      CorruptCheckpoint: the manifest exists but the payload is missing,
        from a different `save` call (torn write), or holds the wrong
        number of arrays.
    """
    manifest, data = _load_consistent(path)
    leaves_like, treedef = jax.tree_util.tree_flatten(like)
    stored = [data[f"leaf_{i}"] for i in range(len(manifest["keys"]))]
    if len(stored) != len(leaves_like):
        raise ValueError(
            f"checkpoint has {len(stored)} leaves, target has {len(leaves_like)}"
        )
    out = []
    for i, (got, want) in enumerate(zip(stored, leaves_like)):
        if tuple(got.shape) != tuple(np.shape(want)):
            raise ValueError(
                f"shape mismatch at {manifest['keys'][i]}: "
                f"{tuple(got.shape)} vs {tuple(np.shape(want))}"
            )
        want_dtype = np.dtype(want.dtype)
        if str(want_dtype) != manifest["dtypes"][i]:
            if not cast:
                raise ValueError(
                    f"dtype mismatch at {manifest['keys'][i]}: checkpoint "
                    f"holds {manifest['dtypes'][i]}, target wants "
                    f"{want_dtype}; pass cast=True to convert explicitly"
                )
            got = got.astype(want_dtype)
        sharding = getattr(want, "sharding", None)
        if sharding is not None:
            out.append(jax.device_put(got, sharding))
        else:
            out.append(jnp.asarray(got))
    return jax.tree_util.tree_unflatten(treedef, out)


def _load_consistent(path: str) -> tuple[dict, Any]:
    """Load ``(manifest, npz)`` from ``path``, proving they belong to
    the SAME `save` call.

    FileNotFoundError when there is no manifest (no checkpoint at all);
    `CorruptCheckpoint` when the manifest exists but the payload is
    missing, carries a different ``save_id`` (torn write), or its leaf
    keys disagree with the manifest's count.  Checkpoints written before
    ``save_id`` existed (no id in either file) pass the pairing check.
    """
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    try:
        data = np.load(os.path.join(path, "arrays.npz"))
    except FileNotFoundError:
        raise CorruptCheckpoint(
            f"checkpoint at {path!r} has a manifest but no arrays.npz "
            f"(torn write — treat as absent)"
        ) from None
    man_id = manifest.get("save_id")
    npz_id = (str(data["__save_id__"]) if "__save_id__" in data.files
              else None)
    if man_id != npz_id:
        raise CorruptCheckpoint(
            f"checkpoint at {path!r} is torn: manifest save_id "
            f"{man_id!r} != arrays save_id {npz_id!r}"
        )
    want = {f"leaf_{i}" for i in range(len(manifest["keys"]))}
    got = {k for k in data.files if k.startswith("leaf_")}
    if want != got:
        raise CorruptCheckpoint(
            f"checkpoint at {path!r}: manifest lists "
            f"{len(manifest['keys'])} arrays, payload holds {len(got)}"
        )
    return manifest, data


def latest_step(path: str) -> int | None:
    """The ``step`` recorded by the checkpoint at ``path``.

    Distinguishes the previously-conflated cases:

      * no checkpoint at ``path`` at all → raises FileNotFoundError;
      * an incomplete/torn checkpoint → raises `CorruptCheckpoint`
        (callers that can restart should treat it like absent —
        `run_resumable` does);
      * a checkpoint exists but `save` was called without ``step`` →
        returns None.
    """
    manifest, _ = _load_consistent(path)
    return manifest.get("step")


# ----------------------------------------------------------------------
# Resumable round-scan driver (DESIGN.md §13).
# ----------------------------------------------------------------------

def _chunk_programs(sim, mesh, closed: bool):
    """Jitted ``(init_scan, advance_chunk)`` for ``sim``.

    ``model_shards == 1`` jits the plain functions.  ``model_shards > 1``
    wraps both in a `shard_map` over ``mesh`` that shards the ``"w"``
    rows' segment axis along the sim's model axis and replicates
    everything else — the same binding `GridRunner` uses, so the
    per-device chunk program matches the fused grid path.
    """
    if sim.model_shards == 1:
        return jax.jit(sim.init_scan), jax.jit(sim.advance_chunk)
    if mesh is None:
        raise ValueError(
            f"model_shards={sim.model_shards} needs a mesh with a "
            f"'{sim.model_axis}' axis (e.g. launch.mesh.grid_model_mesh)"
        )
    if (sim.model_axis not in mesh.axis_names
            or mesh.shape[sim.model_axis] != sim.model_shards):
        raise ValueError(
            f"mesh axes {dict(mesh.shape)} do not provide "
            f"{sim.model_axis}={sim.model_shards}"
        )
    P = jax.sharding.PartitionSpec
    st = {"w": P(None, sim.model_axis, None), "key": P()}
    if closed:
        st["sig"] = P()
    # Metric/state replication along the model axis is structural (DESIGN.md
    # §13), not something the replication checker can always prove.
    init = shard_map(
        sim.init_scan, mesh=mesh, in_specs=(P(),), out_specs=st,
        check_vma=False,
    )
    adv = shard_map(
        sim.advance_chunk, mesh=mesh, in_specs=(st, P(), P()),
        out_specs=(st, P()), check_vma=False,
    )
    return jax.jit(init), jax.jit(adv)


def _stack_rows(prev: Pytree | None, rows: list) -> Pytree:
    """Stack per-chunk metric rows (host side) and append to ``prev``."""
    if rows:
        new = jax.tree.map(
            lambda *r: np.stack([np.asarray(jax.device_get(x)) for x in r]),
            *rows,
        )
        if prev is None:
            return new
        return jax.tree.map(lambda a, b: np.concatenate([a, b]), prev, new)
    return prev


def run_resumable(
    sim,
    scenario,
    *,
    ckpt_dir: str,
    save_every: int = 1,
    resume: bool = True,
    stop_after: int | None = None,
    mesh=None,
) -> dict | None:
    """Run ``sim`` on ``scenario`` chunk-by-chunk with checkpointing.

    The host loop jits `sim.advance_chunk` ONCE and feeds it chunk
    indices ``0 .. sim.n_chunks - 1`` — the same function
    `sim.run_scenario` scans over, so a run interrupted at any chunk and
    resumed from its checkpoint replays a bitwise-identical program.
    Each checkpoint records the scan state (which carries the PRNG key),
    the metric rows accumulated so far, and the round index.  The rows
    are saved as `advance_chunk` emits them (``loss_in`` / ``loss_end``,
    not ``loss``): chunk c's ``loss`` is taken from chunk c+1's first
    local epoch and the last chunk computes its own, so
    `simulator.chunk_metrics` builds ``loss`` once every row is in.

    Args:
      sim: a `repro.fl.simulator.SimPrograms`.
      scenario: the scenario to run (any class — static, dynamic,
        chunked, closed-loop).
      ckpt_dir: checkpoint directory; overwritten at each save.
      save_every: checkpoint every k-th chunk (the final chunk always
        saves).
      resume: pick up from an existing checkpoint in ``ckpt_dir``; with
        ``resume=False`` the run restarts from round 0 (the old
        checkpoint is overwritten at the first save).
      stop_after: advance at most this many chunks in THIS call, then
        return None (simulated preemption — chunks past the last save
        cadence are recomputed on resume, bitwise identically).
      mesh: required iff ``sim.model_shards > 1``: a mesh providing the
        sim's model axis at size ``model_shards`` (other axes, if any,
        replicate).

    Returns:
      The metrics dict `sim.run_scenario` would return (bias/selected
      flattened across chunks when ``eval_every > 1``), or None when
      ``stop_after`` interrupted the run before completion.
    """
    closed = scenario.policy_id is not None
    init_p, chunk_p = _chunk_programs(sim, mesh, closed)

    # Shape skeletons (no compute) for building restore targets.
    state_sh = jax.eval_shape(init_p, scenario)
    _, row_sh = jax.eval_shape(
        chunk_p, state_sh, scenario, jax.ShapeDtypeStruct((), jnp.int32)
    )

    start = 0
    prev_rows = None
    if resume:
        try:
            step = latest_step(ckpt_dir)
        except (FileNotFoundError, CorruptCheckpoint):
            # Absent or torn: restart from round 0 (the first save
            # overwrites whatever is there) rather than resume from
            # half-written state.
            step = None
        if step is not None:
            like = {
                "state": jax.tree.map(
                    lambda s: np.zeros(s.shape, s.dtype), state_sh
                ),
                "metrics": jax.tree.map(
                    lambda r: np.zeros((step + 1,) + r.shape, r.dtype),
                    row_sh,
                ),
                "round_idx": np.zeros((), np.int32),
            }
            payload = restore(ckpt_dir, like)
            state = payload["state"]
            prev_rows = payload["metrics"]
            start = step + 1
    if start == 0:
        prev_rows = None
        state = init_p(scenario)

    rows: list = []
    advanced = 0
    for c in range(start, sim.n_chunks):
        if stop_after is not None and advanced >= stop_after:
            return None
        state, row = chunk_p(state, scenario, jnp.int32(c))
        rows.append(row)
        advanced += 1
        if (c + 1) % save_every == 0 or c == sim.n_chunks - 1:
            prev_rows = _stack_rows(prev_rows, rows)
            rows = []
            save(
                ckpt_dir,
                {
                    "state": state,
                    "metrics": prev_rows,
                    "round_idx": np.int32((c + 1) * sim.eval_every),
                },
                step=c,
            )

    metrics = _stack_rows(prev_rows, rows)
    if metrics is None:
        raise ValueError("run_resumable: sim has zero chunks to run")
    return simulator.chunk_metrics(metrics)
