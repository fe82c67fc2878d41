"""Batched scenario engine: vmapped multi-seed / multi-PER / multi-protocol
sweeps in a single XLA dispatch, optionally sharded across devices.

The paper's headline results (Figs. 2, 3, 8, 9; Table III) are sweeps over
packet error rates, relay counts, protocols, and seeds.  Because the round
loop (`repro.fl.simulator.round_step`) is a pure jitted function of a
`Scenario` whose parameters are all traced arrays, a whole grid of scenarios
compiles to ONE program and runs as ONE dispatch:

    grid = ScenarioGrid.product(networks=[...], protocols=[...], seeds=[...])
    res = run_grid(init_fn, apply_fn, data, grid, cfg)   # (G, rounds, N)

Scenario axes:

  * seed            — model init + channel realizations,
  * link-PER        — any per-scenario `topology.Network` (packet length,
                      edge density, TX power... all collapse into link_eps),
  * relay count     — networks of different node counts are padded with
                      isolated zero-quality nodes (routing is unaffected),
  * protocol        — ra | aayg | cfl | ideal_cfl | none (traced id),
  * aggregation     — ra_normalized | substitution (traced id),
  * learning rate   — traced scalar.

Dynamic axes (DESIGN.md §8) — a scenario can be a *trajectory* of grid
points, still batched through the same single dispatch:

  * topology schedule — ``schedules=[(label, (T, V, V) link_eps stack)]``
                      (see `topology.markov_link_schedule` /
                      `topology.fading_per_schedule`); round t uses entry
                      t % T, re-routed via vmapped Floyd–Warshall once per
                      scenario, outside the round scan,
  * client sampling — ``participation=[(label, (T, N) or (N,) mask)]``
                      (see `sampling_schedule`); sampled-out clients skip
                      local training and contribute nothing to aggregation,
  * local epochs    — ``local_epochs=(N,)`` per-client vector (heterogeneous
                      compute, masked scan over the static bound).

Closed-loop axes (DESIGN.md §10) — participation as a LIVE policy instead
of a precomputed mask:

  * sampling policy — ``sampling_policies=[(label, policy, frac)]`` with
                      policy in `core.selection.POLICY_IDS` (uniform /
                      loss / grad_norm / bandwidth): each round's mask is
                      computed inside the round scan from per-client
                      signals (trailing loss, update norms, per-round
                      admission scores), dispatched by a traced
                      `lax.switch` — a policy sweep is still ONE dispatch,
                      and `GridResult.selected` records the realized
                      masks.  Any ``participation`` axis becomes the
                      availability base the policies refine.

Codec axes (DESIGN.md §15) — lossy model-exchange compression as a grid
dimension:

  * exchange codec  — ``codecs=[(label, codec, ratio)]`` with codec in
                      `core.compression.CODEC_IDS` (none / topk / quant)
                      and ratio the traced compression intensity in
                      (0, 1]: each client's trained update is encoded
                      between local training and the exchange, the
                      codec's per-segment transmit mask composes with the
                      channel success mask, and a ratio x protocol x PER
                      sweep is still ONE dispatch.  The ``none`` codec is
                      bitwise identical to a codec-free grid.

Grid leaves are kept HOST-SIDE (numpy): the per-dispatch uniform-field
hoisting test then costs no device sync, and arrays only move to devices
at dispatch.

Multi-device grids (DESIGN.md §7): pass ``devices=`` to `run_grid` /
`GridRunner` and the grid axis is sharded over a 1-D ``('grid',)`` mesh
(`repro.launch.mesh.grid_mesh`) via `shard_map` — each device executes the
vmapped round loop on its slice of the batch, with NO cross-device
collectives in the hot loop (scenarios are independent).  Batches that do
not divide the device count are padded with routing-neutral filler
scenarios (every node isolated — the same machinery that pads small
networks) and unpadded on return; results are bit-identical to the
single-device path:

    res = run_grid(init_fn, apply_fn, data, grid, cfg, devices=jax.devices())

`run_sequential` runs the same grid through the same compiled scalar program
one scenario at a time — the per-scenario-dispatch baseline for timing
comparisons (see benchmarks/fig3_sweep.py); `benchmarks/grid_scaling.py`
measures scenarios/sec vs device count through the sharded path.

Public API
----------
  ScenarioGrid.product(...)       build a cross-product grid
                                  (+ schedules= / participation= /
                                  local_epochs= dynamic axes)
  ScenarioGrid.concat(*grids)     join heterogeneous grids (re-pads V and
                                  the time axis, recomputes rho)
  sampling_schedule(...)          (T, N) per-round client-sampling mask
  run_grid(..., devices=None)     one-shot batched (optionally sharded) run
  run_sequential(...)             per-scenario-dispatch baseline
  GridRunner(..., devices=None)   warm-program server for repeated grids
                                  (+ tracker= / max_cached_programs= /
                                  warmup() / validate() — DESIGN.md §11)
  ProgramCache                    bounded LRU of AOT-compiled grid programs
  validate_grid / AdmissionError  admission-time request validation
  GridResult                      stacked trajectories + per-label access
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import Counter, OrderedDict
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core import compression, protocols, selection, topology
from repro.data.synthetic import FederatedDataset
from repro.fl import simulator
from repro.launch import mesh as launch_mesh
from repro.launch import tracker as launch_tracker

Pytree = Any

# Anything `GridRunner` accepts as a device/sharding spec: a prebuilt 1-D
# mesh, a device sequence, a device count, or None (single-device vmap).
DeviceSpec = Any

# `GridRunner.run(devices=...)` default: inherit the runner's spec, so an
# explicit devices=None can still force the single-device vmap path.
_INHERIT = object()

PROTOCOL_IDS = protocols.PROTOCOL_IDS
MODE_IDS = protocols.MODE_IDS


def _pad_link_eps(link_eps, v_max: int) -> np.ndarray:
    """Pad a (..., V, V) link matrix/stack to V=v_max with isolated nodes.

    Padded nodes have zero link quality in/out, so Floyd–Warshall leaves
    every real route untouched and the client block of rho is unchanged.
    Host-side (numpy); handles an optional leading time axis.
    """
    arr = np.asarray(link_eps, np.float32)
    v = arr.shape[-1]
    pad = [(0, 0)] * (arr.ndim - 2) + [(0, v_max - v), (0, v_max - v)]
    return np.pad(arr, pad)


def _tile_schedule(arr: np.ndarray, t_target: int, what: str) -> np.ndarray:
    """Cyclically tile a (T, ...) schedule to ``t_target`` entries.

    Round t reads entry t % T, so tiling to a MULTIPLE of T is semantically
    exact; any other target would silently change the trajectory, so it
    raises instead.
    """
    t = arr.shape[0]
    if t == t_target:
        return arr
    if t_target % t:
        raise ValueError(
            f"cannot align {what} of length {t} to a common time axis of "
            f"{t_target} rounds: {t_target} is not a multiple of {t}"
        )
    return np.tile(arr, (t_target // t,) + (1,) * (arr.ndim - 1))


def _pad_scenario_batch(batch: simulator.Scenario,
                        g_target: int) -> simulator.Scenario:
    """Pad a (G, ...)-leaved scenario batch to ``g_target`` rows.

    Filler rows are routing-neutral whole-scenario analogues of the
    isolated-node padding above: scalar fields copy row 0 (so a
    (protocol, mode)-homogeneous group stays homogeneous and the hoisted
    scalar dispatch survives padding) while ``link_eps`` is all-zero —
    every node isolated, every segment falls back to the sender's own.
    Dynamic fields (participation / local_epochs) copy row 0 like scalars.
    Filler results are dropped on unpad; they never reach a `GridResult`.
    Host-side (numpy), so padding costs no device sync.
    """
    g = batch.link_eps.shape[0]
    if g_target < g:
        raise ValueError(f"cannot pad {g} scenarios down to {g_target}")
    if g_target == g:
        return batch
    n_pad = g_target - g

    def pad_leaf(name: str, leaf):
        if leaf is None:
            return None
        arr = np.asarray(leaf)
        filler = np.broadcast_to(arr[:1], (n_pad,) + arr.shape[1:])
        if name == "link_eps":
            filler = np.zeros_like(filler)
        return np.concatenate([arr, filler])

    return simulator.Scenario(
        **{name: pad_leaf(name, leaf)
           for name, leaf in batch._asdict().items()}
    )


def sampling_schedule(n_clients: int, n_rounds: int, fraction: float, *,
                      seed: int = 0) -> np.ndarray:
    """A (T, N) client-sampling mask: per round, a uniform random subset.

    Each round independently samples ``ceil(fraction * n_clients)`` clients
    without replacement (at least one).  ``fraction=1`` yields the all-ones
    mask (bitwise equivalent to full participation).  Deterministic in
    ``seed``.
    """
    if not 0.0 < fraction <= 1.0:
        raise ValueError(f"fraction must be in (0, 1], got {fraction}")
    k = min(n_clients, max(1, int(np.ceil(fraction * n_clients))))
    rng = np.random.default_rng(seed)
    out = np.zeros((n_rounds, n_clients), np.float32)
    for t in range(n_rounds):
        out[t, rng.choice(n_clients, size=k, replace=False)] = 1.0
    return out


def _resolve_grid_mesh(devices: DeviceSpec,
                       sharding: Any) -> jax.sharding.Mesh | None:
    """Normalize the `devices=` / `sharding=` knobs into a grid mesh.

    ``sharding`` wins over ``devices``; it may be a `jax.sharding.Mesh` —
    1-D (any axis name, the grid axis) or 2-D ``('grid', 'model')``
    (DESIGN.md §13) — or a `NamedSharding` (its mesh is used).
    ``devices`` is anything `launch.mesh.grid_mesh` accepts, or a
    ``(spec, model_shards)`` tuple building a 2-D
    `launch.mesh.grid_model_mesh`.  Both None -> None (the single-device
    vmap path).
    """
    if sharding is not None:
        if isinstance(sharding, NamedSharding):
            sharding = sharding.mesh
        if not isinstance(sharding, jax.sharding.Mesh):
            raise TypeError(f"sharding= must be a Mesh or NamedSharding, "
                            f"got {type(sharding).__name__}")
        names = sharding.axis_names
        if len(names) == 2:
            if tuple(names) != (launch_mesh.GRID_AXIS,
                                launch_mesh.MODEL_AXIS):
                raise ValueError(
                    "2-D grid sharding needs axes "
                    f"('{launch_mesh.GRID_AXIS}', "
                    f"'{launch_mesh.MODEL_AXIS}'), got {names} "
                    "(see launch.mesh.grid_model_mesh)"
                )
        elif len(names) != 1:
            raise ValueError("grid sharding needs a 1-D or 2-D mesh, got "
                             f"axes {names}")
        return sharding
    if devices is None:
        return None
    if (isinstance(devices, tuple) and len(devices) == 2
            and isinstance(devices[1], int)
            and not isinstance(devices[0], jax.Device)):
        spec, model_shards = devices
        return launch_mesh.grid_model_mesh(spec, model_shards=model_shards)
    return launch_mesh.grid_mesh(devices)


def _dedupe_labels(labels: list[str]) -> list[str]:
    """Disambiguate colliding labels deterministically (``label#k``).

    `ScenarioGrid.product` omits single-valued axes from labels, so e.g.
    concatenating two single-seed grids of the same networks yields
    colliding labels — and `GridResult.result(label)` would silently
    return the first.  Every member of a colliding set gets an occurrence
    suffix; unique labels pass through untouched.
    """
    counts = Counter(labels)
    if max(counts.values(), default=0) <= 1:
        return labels
    seen: dict[str, int] = {}
    out = []
    for lbl in labels:
        if counts[lbl] > 1:
            k = seen.get(lbl, 0)
            seen[lbl] = k + 1
            out.append(f"{lbl}#{k}")
        else:
            out.append(lbl)
    return out


def _normalize_participation(leaf, n_ref: int, t_target: int) -> np.ndarray:
    """Batch-leaf participation -> (G, T, N) float32, cyclically tiled."""
    arr = np.asarray(leaf, np.float32)
    if arr.ndim == 2:                       # (G, N) static mask per row
        arr = arr[:, None, :]
    if arr.ndim != 3 or arr.shape[-1] != n_ref:
        raise ValueError(
            f"participation leaves must be (G, N={n_ref}) or (G, T, N), "
            f"got shape {arr.shape}"
        )
    if arr.shape[1] != t_target:
        if t_target % arr.shape[1]:
            raise ValueError(
                f"cannot align participation schedule of length "
                f"{arr.shape[1]} to {t_target} (not a multiple)"
            )
        arr = np.tile(arr, (1, t_target // arr.shape[1], 1))
    return arr


@dataclasses.dataclass
class ScenarioGrid:
    """A flat batch of scenarios: every Scenario leaf stacked on axis 0.

    Leaves are host-side numpy arrays (`product` / `concat` build them that
    way): grouping, padding, and uniform-field hoisting then never sync a
    device, and data moves to devices exactly once per dispatch.

    ``packet_len_bits`` records the distinct PER packet lengths of the
    source networks (where known): `GridRunner.run` validates them against
    the codec's segment size (`simulator.check_packet_len`).
    """

    scenarios: simulator.Scenario   # leaves with leading G axis
    labels: list[str]
    packet_len_bits: tuple[int, ...] = ()

    def __len__(self) -> int:
        return len(self.labels)

    def scenario(self, i: int) -> simulator.Scenario:
        """The i-th scalar Scenario (host-side slice of the batch)."""
        return jax.tree.map(lambda leaf: leaf[i], self.scenarios)

    def take(self, indices: Sequence[int]) -> "ScenarioGrid":
        """The sub-grid of the given rows (host-side fancy indexing).

        The partial-batch re-slice primitive of the serving tier
        (DESIGN.md §12): when some requests of a coalesced dispatch are
        cancelled or expire before the dispatch runs, the dispatcher keeps
        only the surviving rows instead of burning device time on dead
        ones.  Leaves stay host-side numpy; labels and packet lengths
        follow the selection.
        """
        idx = np.asarray(indices, np.intp)
        if idx.ndim != 1:
            raise ValueError(f"take() needs a 1-D index list, got {idx.shape}")
        return ScenarioGrid(
            scenarios=jax.tree.map(
                lambda leaf: np.asarray(leaf)[idx], self.scenarios
            ),
            labels=[self.labels[int(i)] for i in idx],
            packet_len_bits=self.packet_len_bits,
        )

    @staticmethod
    def concat(*grids: "ScenarioGrid") -> "ScenarioGrid":
        """Join grids into one batch, re-padding link matrices to a common V
        (heterogeneous sub-grids — e.g. a relay sweep plus its ideal
        reference — still compile to a single program).

        Static and dynamic grids mix freely: static link matrices are
        promoted to T=1 schedules and cyclically tiled to the longest time
        axis (which must be a multiple of every grid's T); missing
        participation masks are filled with all-ones.  Grids must agree on
        having (or not having) per-client ``local_epochs`` — there is no
        neutral fill-in for "use the config default".  Any derived ``rho``
        is DROPPED and recomputed lazily at `prepare` time: a stale rho
        carried through V-repadding would be inconsistent with the padded
        ``link_eps``.  Colliding labels are disambiguated with an
        occurrence suffix (see `_dedupe_labels`).
        """
        v_max = max(g.scenarios.link_eps.shape[-1] for g in grids)
        ranks = {np.ndim(g.scenarios.link_eps) for g in grids}
        dynamic_t = 4 in ranks              # (G, T, V, V) present
        t_max = max(
            (g.scenarios.link_eps.shape[1] for g in grids
             if np.ndim(g.scenarios.link_eps) == 4),
            default=1,
        )
        has_part = [g.scenarios.participation is not None for g in grids]
        has_epochs = [g.scenarios.local_epochs is not None for g in grids]
        any_policy = any(g.scenarios.policy_id is not None for g in grids)
        any_codec = any(g.scenarios.codec_id is not None for g in grids)
        if any(has_epochs) and not all(has_epochs):
            raise ValueError(
                "cannot concat grids with and without per-client "
                "local_epochs: pass an explicit vector to every grid "
                "(there is no neutral stand-in for the static config value)"
            )
        part_n = None
        if any(has_part):
            ns = {g.scenarios.participation.shape[-1]
                  for g in grids if g.scenarios.participation is not None}
            if len(ns) != 1:
                raise ValueError(f"participation client counts differ: {ns}")
            (part_n,) = ns
            t_part = max(
                (g.scenarios.participation.shape[1] for g in grids
                 if g.scenarios.participation is not None
                 and np.ndim(g.scenarios.participation) == 3),
                default=1,
            )

        def normalize(g: ScenarioGrid) -> simulator.Scenario:
            s = g.scenarios
            le = np.asarray(s.link_eps, np.float32)
            if dynamic_t:
                if le.ndim == 3:
                    le = le[:, None]                    # (G, 1, V, V)
                # Tile along the time axis (leading G axis untouched).
                if le.shape[1] != t_max:
                    if t_max % le.shape[1]:
                        raise ValueError(
                            f"cannot align topology schedule of length "
                            f"{le.shape[1]} to {t_max} (not a multiple)"
                        )
                    le = np.tile(le, (1, t_max // le.shape[1], 1, 1))
            le = _pad_link_eps(le, v_max)
            part = s.participation
            if part_n is not None:
                if part is None:
                    part = np.ones((len(g), 1, part_n), np.float32)
                part = _normalize_participation(part, part_n, t_part)
            pol, frac = s.policy_id, s.select_frac
            if any_policy and pol is None:
                # Neutral fill-in: the uniform policy IS the open-loop path
                # (frac unread), so policy-free grids join bitwise intact.
                pol = np.zeros((len(g),), np.int32)
                frac = np.ones((len(g),), np.float32)
            cod, ratio = s.codec_id, s.compress_ratio
            if any_codec and cod is None:
                # Neutral fill-in: the `none` codec at ratio 1 is bitwise
                # the codec-free exchange, so codec-free grids join intact.
                cod = np.full((len(g),), compression.CODEC_IDS["none"],
                              np.int32)
                ratio = np.ones((len(g),), np.float32)
            return s._replace(link_eps=le, rho=None, participation=part,
                              policy_id=pol, select_frac=frac,
                              codec_id=cod, compress_ratio=ratio)

        stacked = jax.tree.map(
            lambda *leaves: np.concatenate([np.asarray(l) for l in leaves]),
            *(normalize(g) for g in grids)
        )
        labels = _dedupe_labels([lbl for g in grids for lbl in g.labels])
        pkt = tuple(sorted({b for g in grids for b in g.packet_len_bits}))
        return ScenarioGrid(scenarios=stacked, labels=labels,
                            packet_len_bits=pkt)

    @staticmethod
    def product(
        *,
        networks: Sequence[tuple[str, topology.Network]] = (),
        schedules: Sequence[tuple[str, Any]] = (),
        protocols: Sequence[tuple[str, str]] = (("ra", "ra_normalized"),),
        seeds: Iterable[int] = (0,),
        lrs: Iterable[float] = (0.05,),
        participation: Sequence[tuple[str, Any]] | None = None,
        sampling_policies: Sequence[tuple[str, str, float]] | None = None,
        codecs: Sequence[tuple[str, str, float]] | None = None,
        local_epochs: Any = None,
        aggregator: int = 6,
    ) -> "ScenarioGrid":
        """Cross topology x (protocol, mode) x seeds x lrs [x participation
        x sampling policy] into one grid.

        Args:
          networks: (label, Network) pairs — one per STATIC topology/PER
            point.
          schedules: (label, schedule) pairs — one per TIME-VARYING
            topology point; a schedule is a (T, V, V) link_eps stack
            (`topology.markov_link_schedule` / `fading_per_schedule`), a
            sequence of Networks, or a single Network (T=1).  When any
            schedule is present, every topology point (static ones
            included) is promoted to the common time axis: schedules are
            cyclically tiled to the longest T, which must be a multiple of
            each (round t uses entry t % T, so tiling is exact).
          protocols: (protocol, mode) string pairs (PROTOCOL_IDS / MODE_IDS).
          seeds: model-init + channel seeds.
          lrs: local GD step sizes.
          participation: optional axis of (label, mask) pairs; a mask is
            (N,), (T, N) (see `sampling_schedule`), or None for full
            participation (normalized to an all-ones mask so the batch
            stays structurally uniform).
          sampling_policies: optional CLOSED-LOOP axis of (label, policy,
            select_frac) triples — policy a `core.selection.POLICY_IDS`
            name, select_frac the per-round participant fraction in
            (0, 1] (k = ceil(frac * N); unread by ``uniform``).  The
            per-round mask is computed inside the round scan from live
            signals; a ``participation`` axis, when also given, is the
            availability base every policy refines (DESIGN.md §10).
          codecs: optional exchange-codec axis of (label, codec, ratio)
            triples — codec a `core.compression.CODEC_IDS` name (none /
            topk / quant), ratio the traced compression intensity in
            (0, 1] (fraction of segments kept under ``topk``, fraction
            of value bits under ``quant``; unread by ``none``).  Encoded
            between local training and the exchange (DESIGN.md §15); the
            ``none`` codec traces a transmit-everything mask whose
            results are bitwise those of a codec-free grid.
          local_epochs: optional (N,) per-client epoch vector shared by
            every grid point (values clip to the SimConfig bound).
          aggregator: C-FL star center (shared; only read by cfl scenarios).

        Raises ValueError on duplicate labels (e.g. repeated axis labels):
        `GridResult.result(label)` must never be ambiguous.
        """
        seeds = list(seeds)
        lrs = list(lrs)
        if not networks and not schedules:
            raise ValueError("need at least one network or schedule")

        def schedule_links(sched) -> np.ndarray:
            if isinstance(sched, topology.Network):
                return np.asarray(sched.link_eps, np.float32)[None]
            if isinstance(sched, (list, tuple)):
                return np.stack(
                    [np.asarray(s.link_eps, np.float32) for s in sched]
                )
            arr = np.asarray(sched, np.float32)
            if arr.ndim == 2:
                arr = arr[None]
            if arr.ndim != 3 or arr.shape[-1] != arr.shape[-2]:
                raise ValueError(
                    f"schedule must be (T, V, V), got shape {arr.shape}"
                )
            return arr

        # The topology axis: static nets (rank 2) + schedules (rank 3).
        topo_axis: list[tuple[str, np.ndarray]] = [
            (lbl, np.asarray(net.link_eps, np.float32))
            for lbl, net in networks
        ] + [(lbl, schedule_links(sched)) for lbl, sched in schedules]
        pkt_bits = {net.packet_len_bits for _, net in networks
                    if net.packet_len_bits is not None}
        for _, sched in schedules:
            nets = ([sched] if isinstance(sched, topology.Network)
                    else sched if isinstance(sched, (list, tuple)) else ())
            pkt_bits |= {s.packet_len_bits for s in nets
                         if isinstance(s, topology.Network)
                         and s.packet_len_bits is not None}
        v_max = max(links.shape[-1] for _, links in topo_axis)
        if schedules:
            t_max = max(links.shape[0] for _, links in topo_axis
                        if links.ndim == 3)
            topo_axis = [
                (lbl,
                 _tile_schedule(links if links.ndim == 3 else links[None],
                                t_max, f"topology schedule {lbl!r}"))
                for lbl, links in topo_axis
            ]
        topo_axis = [(lbl, _pad_link_eps(links, v_max))
                     for lbl, links in topo_axis]

        # The participation axis (None -> single full-participation point).
        if participation is not None:
            masks = [np.asarray(m, np.float32) for _, m in participation
                     if m is not None]
            if not masks:
                raise ValueError(
                    "participation axis needs at least one non-None mask"
                )
            n_ref = masks[0].shape[-1]
            t_part = 1
            for m in masks:
                if m.ndim == 2:
                    t_part = max(t_part, m.shape[0])
            part_axis = []
            for lbl, m in participation:
                if m is None:
                    m = np.ones((1, n_ref), np.float32)
                m = np.asarray(m, np.float32)
                if m.ndim == 1:
                    m = m[None]
                part_axis.append(
                    (lbl, _normalize_participation(m[None], n_ref,
                                                   t_part)[0])
                )
        else:
            part_axis = [(None, None)]

        # The closed-loop sampling-policy axis (None -> no policy fields:
        # the grid traces the exact open-loop program).
        if sampling_policies is not None:
            if not sampling_policies:
                raise ValueError(
                    "sampling_policies axis needs at least one point"
                )
            pol_axis = []
            for pol_label, policy, frac in sampling_policies:
                if policy not in selection.POLICY_IDS:
                    raise ValueError(
                        f"unknown sampling policy {policy!r}: choose from "
                        f"{sorted(selection.POLICY_IDS)}"
                    )
                if not 0.0 < float(frac) <= 1.0:
                    raise ValueError(
                        f"select_frac must be in (0, 1], got {frac}"
                    )
                pol_axis.append((
                    pol_label,
                    np.asarray(selection.POLICY_IDS[policy], np.int32),
                    np.asarray(frac, np.float32),
                ))
        else:
            pol_axis = [(None, None, None)]

        # The exchange-codec axis (None -> no codec fields: the grid
        # traces the exact codec-free program).
        if codecs is not None:
            if not codecs:
                raise ValueError("codecs axis needs at least one point")
            cod_axis = []
            for cod_label, codec, ratio in codecs:
                if codec not in compression.CODEC_IDS:
                    raise ValueError(
                        f"unknown codec {codec!r}: choose from "
                        f"{sorted(compression.CODEC_IDS)}"
                    )
                if not 0.0 < float(ratio) <= 1.0:
                    raise ValueError(
                        f"compress ratio must be in (0, 1], got {ratio}"
                    )
                cod_axis.append((
                    cod_label,
                    np.asarray(compression.CODEC_IDS[codec], np.int32),
                    np.asarray(ratio, np.float32),
                ))
        else:
            cod_axis = [(None, None, None)]

        epochs_vec = (None if local_epochs is None
                      else np.asarray(local_epochs, np.int32))

        rows, labels = [], []
        for (net_label, links), (proto, mode), seed, lr, (part_label, mask), \
                (pol_label, pol_id, frac), (cod_label, cod_id, cod_ratio) \
                in itertools.product(topo_axis, protocols, seeds, lrs,
                                     part_axis, pol_axis, cod_axis):
            rows.append(simulator.Scenario(
                link_eps=links,
                seed=np.asarray(seed, np.int32),
                protocol_id=np.asarray(PROTOCOL_IDS[proto], np.int32),
                mode_id=np.asarray(MODE_IDS[mode], np.int32),
                aggregator=np.asarray(aggregator, np.int32),
                lr=np.asarray(lr, np.float32),
                participation=mask,
                local_epochs=epochs_vec,
                policy_id=pol_id,
                select_frac=frac,
                codec_id=cod_id,
                compress_ratio=cod_ratio,
            ))
            parts = [net_label, f"{proto}+{mode}"]
            if len(seeds) > 1:
                parts.append(f"s{seed}")
            if len(lrs) > 1:
                parts.append(f"lr{lr:g}")
            if part_label is not None and len(part_axis) > 1:
                parts.append(part_label)
            if pol_label is not None and len(pol_axis) > 1:
                parts.append(pol_label)
            if cod_label is not None and len(cod_axis) > 1:
                parts.append(cod_label)
            labels.append("/".join(parts))
        if len(set(labels)) != len(labels):
            dups = [l for l, c in Counter(labels).items() if c > 1]
            raise ValueError(
                f"duplicate scenario labels {dups}: give each axis point a "
                "distinct label"
            )
        stacked = jax.tree.map(lambda *leaves: np.stack(leaves), *rows)
        return ScenarioGrid(scenarios=stacked, labels=labels,
                            packet_len_bits=tuple(sorted(pkt_bits)))


@dataclasses.dataclass
class GridResult:
    """Stacked per-scenario trajectories from one batched dispatch.

    With eval thinning (``SimConfig.eval_every=k``) acc/loss carry
    ``rounds // k`` rows (row j = round ``(j + 1) * k - 1``); ``bias``
    always stays per-round.  Closed-loop grids (a ``sampling_policies``
    axis) additionally carry ``selected`` — the realized per-round
    participation masks, always per-round; None for open-loop grids.
    """

    acc: np.ndarray        # (G, evals, N)  test accuracy
    loss: np.ndarray       # (G, evals, N)  train loss
    bias: np.ndarray       # (G, rounds)    mean ||Lambda_l||_F^2 (ra only)
    labels: list[str]
    selected: np.ndarray | None = None   # (G, rounds, N) realized masks

    def __len__(self) -> int:
        return len(self.labels)

    @property
    def mean_acc(self) -> np.ndarray:
        """(G, rounds) accuracy averaged across clients."""
        return self.acc.mean(axis=2)

    @property
    def selected_frac(self) -> np.ndarray | None:
        """(G, rounds) realized participation fraction (closed loop only)."""
        return None if self.selected is None else self.selected.mean(axis=2)

    def result(self, key: int | str) -> simulator.SimResult:
        """One scenario's trajectory as a scalar SimResult.

        String keys must match exactly one label: a missing label raises
        KeyError, and so does an ambiguous one (duplicate labels can only
        enter through a hand-built grid — `ScenarioGrid.product` rejects
        them and `.concat` disambiguates — but silently returning the
        first match would hide the collision).
        """
        if isinstance(key, str):
            hits = [i for i, lbl in enumerate(self.labels) if lbl == key]
            if not hits:
                raise KeyError(f"no scenario labeled {key!r}")
            if len(hits) > 1:
                raise KeyError(
                    f"label {key!r} is ambiguous: {len(hits)} scenarios "
                    "carry it (index by position instead)"
                )
            i = hits[0]
        else:
            i = key
        return simulator.SimResult(
            acc_per_client=self.acc[i],
            loss_per_client=self.loss[i],
            bias_norms=self.bias[i],
        )

    def items(self):
        return ((lbl, self.result(i)) for i, lbl in enumerate(self.labels))


def _metrics_to_grid_result(metrics: dict, labels: list[str]) -> GridResult:
    return GridResult(
        acc=np.asarray(metrics["acc"]),
        loss=np.asarray(metrics["loss"]),
        bias=np.asarray(metrics["bias"]),
        labels=list(labels),
        selected=(np.asarray(metrics["selected"])
                  if "selected" in metrics else None),
    )


def _batch_uniform(arr: np.ndarray) -> bool:
    """True if every batch row equals row 0 — NaN-tolerantly.

    A plain ``(arr == arr[:1]).all()`` is False for ANY field containing
    NaN (NaN != NaN), which would silently leave a grid-uniform field
    batched — and a batched protocol/mode selector forces every lax.switch
    branch to execute for every scenario.  Float fields therefore compare
    with ``equal_nan`` (NaN placed equally in every row counts as uniform).
    """
    first = np.broadcast_to(arr[:1], arr.shape)
    if arr.dtype.kind in "fc":
        return bool(np.array_equal(arr, first, equal_nan=True))
    return bool(np.array_equal(arr, first))


def _hoist_uniform(batch: simulator.Scenario):
    """Split a scenario batch into (in_axes, args): leaves constant across
    the batch are hoisted out of the vmap (in_axes=None) so scalar control
    flow (lax.switch / cond) stays scalar — a batched branch index would
    otherwise force EVERY protocol branch to execute for every scenario.

    `seed` always stays mapped so vmap has at least one mapped axis.
    Grid leaves live host-side (numpy — see `ScenarioGrid`), so the
    uniformity test is pure host work: no per-call device sync.
    """
    axes, args = {}, {}
    for name, leaf in batch._asdict().items():
        if leaf is None:
            axes[name], args[name] = None, None
            continue
        arr = np.asarray(leaf)
        if name != "seed" and _batch_uniform(arr):
            axes[name], args[name] = None, jnp.asarray(arr[0])
        else:
            axes[name], args[name] = 0, leaf
    return simulator.Scenario(**axes), simulator.Scenario(**args)


class AdmissionError(ValueError):
    """A scenario grid failed admission-time validation (DESIGN.md §11).

    Raised by `validate_grid` / `GridRunner.validate` with a message naming
    the offending scenario labels, so a serving tier can reject ONE bad
    request actionably instead of letting it surface as a deep trace-time
    failure inside a warm compiled program.
    """


def _aval_sig(tree: simulator.Scenario) -> tuple:
    """Shape/dtype signature of a scenario pytree (host metadata only).

    Part of the program-cache key: two dispatches share a compiled
    executable exactly when their hoist signature, mesh, AND input avals
    match.  Reads only ``.shape`` / ``.dtype`` — never values — so it
    costs no device sync.
    """
    sig = []
    for name, leaf in tree._asdict().items():
        if leaf is None:
            sig.append((name, None))
        else:
            dt = getattr(leaf, "dtype", None)
            if dt is None:                          # plain python scalar
                dt = np.asarray(leaf).dtype
            sig.append((name, tuple(np.shape(leaf)), str(dt)))
    return tuple(sig)


def _bucket_target(g: int, pad_to) -> int:
    """The padded batch size for a ``g``-scenario dispatch group.

    ``pad_to`` declares the warm batch buckets: an int (one bucket) or a
    sequence of ints.  A group pads up to the smallest bucket >= g; a
    group LARGER than every bucket pads to the next multiple of the
    largest (so oversized batches still reuse a bounded family of shapes
    instead of compiling one program per arrival pattern).  ``None``
    disables padding (the one-shot `run_grid` behavior).
    """
    if pad_to is None:
        return g
    buckets = sorted({int(b) for b in
                      ((pad_to,) if isinstance(pad_to, int) else pad_to)})
    if not buckets or buckets[0] < 1:
        raise ValueError(f"pad_to buckets must be positive ints, got {pad_to}")
    for b in buckets:
        if b >= g:
            return b
    top = buckets[-1]
    return -(-g // top) * top


_PROTOCOL_NAMES = {v: k for k, v in PROTOCOL_IDS.items()}
_MODE_NAMES = {v: k for k, v in MODE_IDS.items()}


def _group_label(grid: ScenarioGrid, idx: list[int]) -> str:
    """``<protocol>+<mode>`` of a dispatch group's rows, or ``mixed``."""
    sc = grid.scenarios
    pairs = {(int(sc.protocol_id[i]), int(sc.mode_id[i])) for i in idx}
    if len(pairs) != 1:
        return "mixed"
    ((pid, mid),) = pairs
    return f"{_PROTOCOL_NAMES[pid]}+{_MODE_NAMES[mid]}"


def _stack_rows(*leaves):
    """Stack per-row metric leaves back into the grid axis.

    Rows dispatched on different ``('grid',)`` meshes — the per-group
    mesh shrink gives a 2-row group a 2-device mesh while a 1-row group
    runs on 1 device — live on different device sets, which `jnp.stack`
    refuses to mix.  Commit such rows to a common device first; rows
    from a single mesh (the common case) stack directly, transfer-free.
    """
    device_sets = {frozenset(l.devices()) for l in leaves
                   if hasattr(l, "devices")}
    if len(device_sets) > 1:
        leaves = tuple(jax.device_put(l, jax.devices()[0]) for l in leaves)
    return jnp.stack(leaves)


class ProgramCache:
    """Bounded LRU cache of AOT-compiled grid programs (DESIGN.md §11).

    `GridRunner` previously memoized `jax.jit` wrappers in an unbounded
    dict — a leak for any long-lived server: every distinct hoist
    signature / mesh / batch shape kept a compiled XLA executable alive
    forever.  This cache stores the compiled executables themselves
    (``jit(...).lower(args).compile()`` — ahead-of-time compilation, which
    is also what lets `GridRunner.warmup` build a program WITHOUT paying a
    full dispatch) keyed by (kind, hoist signature, mesh, input avals),
    and evicts the least-recently-used entry beyond ``max_programs``.

    Hits / misses / evictions are counted both on the attached `Tracker`
    (``cache/hit`` / ``cache/miss`` / ``cache/evict``) and on the `stats`
    property — the observable that makes cache lifecycle testable.

    ``max_programs=None`` means unbounded (the one-shot `run_grid` path,
    where the process dies with its programs).  Not thread-safe: callers
    (the serving engine) serialize all compilation + dispatch on one
    thread.
    """

    def __init__(self, max_programs: int | None = None,
                 tracker: launch_tracker.Tracker | None = None):
        if max_programs is not None and max_programs < 1:
            raise ValueError(
                f"max_programs must be >= 1 or None, got {max_programs}"
            )
        self.max_programs = max_programs
        self._entries: OrderedDict = OrderedDict()
        self._tracker = tracker or launch_tracker.NullTracker()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    @property
    def stats(self) -> dict[str, int]:
        return {"programs": len(self._entries), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions}

    def compiled(self) -> list:
        """The cached executables, least recently used first."""
        return list(self._entries.values())

    def lookup(self, key, build: Callable[[], Any]):
        """The cached program for ``key``, compiling (and possibly
        evicting) on miss."""
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
            self.hits += 1
            self._tracker.count("cache/hit")
            return entry
        self.misses += 1
        self._tracker.count("cache/miss")
        entry = build()
        self._entries[key] = entry
        while (self.max_programs is not None
               and len(self._entries) > self.max_programs):
            self._entries.popitem(last=False)
            self.evictions += 1
            self._tracker.count("cache/evict")
        return entry

    def clear(self) -> None:
        self._entries.clear()


def validate_grid(grid: ScenarioGrid, *, n_clients: int | None = None,
                  seg_len: int | None = None,
                  strict_packet: bool = False) -> None:
    """Admission-time structural validation of a scenario grid.

    Checks every constraint that would otherwise surface as a deep
    trace-time failure (or worse, silent nonsense) inside the compiled
    program: leaf ranks and batch-axis consistency, link matrices square /
    finite / within [0, 1], protocol / mode / policy ids in range,
    participation client counts against the bound dataset, select_frac in
    (0, 1], unique labels — and, with ``strict_packet``, the PER-packet vs
    codec-segment consistency of `simulator.check_packet_len` as a hard
    error.  Raises `AdmissionError` naming the offending scenario labels;
    pure host-side numpy (no device sync).
    """
    s = grid.scenarios
    g = len(grid.labels)

    def name_rows(mask) -> str:
        idx = np.nonzero(np.asarray(mask))[0]
        shown = ", ".join(f"{i}:{grid.labels[i]!r}" for i in idx[:3])
        more = f" (+{len(idx) - 3} more)" if len(idx) > 3 else ""
        return shown + more

    def fail(msg: str) -> None:
        raise AdmissionError(f"grid rejected: {msg}")

    le = np.asarray(s.link_eps)
    if le.ndim not in (3, 4):
        fail(f"link_eps must be (G, V, V) or (G, T, V, V), got {le.shape}")
    if le.shape[0] != g:
        fail(f"{g} labels but {le.shape[0]} link_eps rows")
    if le.shape[-1] != le.shape[-2]:
        fail(f"link matrices must be square, got {le.shape}")
    bad = ~np.isfinite(le).reshape(g, -1).all(axis=1)
    if bad.any():
        fail(f"non-finite link_eps in scenario(s) {name_rows(bad)}")
    bad = ((le < 0) | (le > 1)).reshape(g, -1).any(axis=1)
    if bad.any():
        fail(f"link_eps outside [0, 1] in scenario(s) {name_rows(bad)}")

    for field, n_ids, ids in (
        ("protocol_id", len(PROTOCOL_IDS), PROTOCOL_IDS),
        ("mode_id", len(MODE_IDS), MODE_IDS),
    ):
        arr = np.asarray(getattr(s, field))
        if arr.shape != (g,):
            fail(f"{field} must be ({g},), got {arr.shape}")
        bad = (arr < 0) | (arr >= n_ids)
        if bad.any():
            fail(f"{field} out of range [0, {n_ids}) in scenario(s) "
                 f"{name_rows(bad)} — known ids: {sorted(ids)}")

    lr = np.asarray(s.lr)
    bad = ~np.isfinite(lr).reshape(g, -1).all(axis=1)
    if bad.any():
        fail(f"non-finite lr in scenario(s) {name_rows(bad)}")

    if s.participation is not None:
        part = np.asarray(s.participation)
        if part.ndim not in (2, 3) or part.shape[0] != g:
            fail(f"participation must be (G, N) or (G, T, N) with G={g}, "
                 f"got {part.shape}")
        if n_clients is not None and part.shape[-1] != n_clients:
            fail(f"participation covers {part.shape[-1]} clients but the "
                 f"bound dataset has {n_clients}")
        flat = part.reshape(g, -1)
        bad = ~(np.isfinite(flat) & (flat >= 0) & (flat <= 1)).all(axis=1)
        if bad.any():
            fail(f"participation outside [0, 1] in scenario(s) "
                 f"{name_rows(bad)}")

    if s.local_epochs is not None:
        ep = np.asarray(s.local_epochs)
        if n_clients is not None and ep.shape[-1] != n_clients:
            fail(f"local_epochs covers {ep.shape[-1]} clients but the "
                 f"bound dataset has {n_clients}")
        bad = (ep.reshape(g, -1) < 0).any(axis=1)
        if bad.any():
            fail(f"negative local_epochs in scenario(s) {name_rows(bad)}")

    if s.policy_id is not None:
        pol = np.asarray(s.policy_id)
        n_pol = len(selection.POLICY_IDS)
        bad = (pol < 0) | (pol >= n_pol)
        if bad.any():
            fail(f"policy_id out of range [0, {n_pol}) in scenario(s) "
                 f"{name_rows(bad)} — known policies: "
                 f"{sorted(selection.POLICY_IDS)}")
        frac = np.asarray(s.select_frac)
        bad = ~(np.isfinite(frac) & (frac > 0) & (frac <= 1))
        if bad.any():
            fail(f"select_frac outside (0, 1] in scenario(s) "
                 f"{name_rows(bad)}")

    if s.codec_id is not None:
        cod = np.asarray(s.codec_id)
        n_cod = len(compression.CODEC_IDS)
        bad = (cod < 0) | (cod >= n_cod)
        if bad.any():
            fail(f"codec_id out of range [0, {n_cod}) in scenario(s) "
                 f"{name_rows(bad)} — known codecs: "
                 f"{sorted(compression.CODEC_IDS)}")
        ratio = np.asarray(s.compress_ratio)
        bad = ~(np.isfinite(ratio) & (ratio > 0) & (ratio <= 1))
        if bad.any():
            fail(f"compress_ratio outside (0, 1] in scenario(s) "
                 f"{name_rows(bad)}")

    dup = [lbl for lbl, c in Counter(grid.labels).items() if c > 1]
    if dup:
        fail(f"duplicate labels {dup[:3]} — results would be ambiguous")

    if strict_packet and seg_len is not None:
        for bits in getattr(grid, "packet_len_bits", ()):
            try:
                simulator.check_packet_len(bits, seg_len, strict=True)
            except ValueError as e:
                raise AdmissionError(f"grid rejected: {e}") from None


class GridRunner:
    """Compiled scenario-grid server: build once, dispatch many grids.

    Binds (init, apply, data, statics) into the pure scenario program and
    caches every compiled variant, so repeated `run()` calls with
    same-shaped grids pay ZERO recompilation — the production serving loop
    for many-scenario workloads.  Programs are AOT-compiled executables
    cached PER (hoist signature, mesh, input avals) in a bounded LRU
    (`ProgramCache`; ``max_cached_programs``): a runner can serve
    single-device and sharded grids (and different device subsets) side by
    side, each staying warm, without leaking executables over a long-lived
    server's life.  `warmup` compiles declared shapes ahead of traffic;
    `validate` rejects malformed grids at admission time
    (`AdmissionError`); the streaming front-end on top of this is
    `repro.launch.serving.ScenarioServer` (DESIGN.md §11).

    Args:
      init_fn: model init, `key -> params` pytree.
      apply_fn: forward pass, `(params, x) -> logits`.
      data: the shared `FederatedDataset` (per-scenario knobs live in
        the grid, NOT here).
      cfg: static knobs baked into the compiled program — seg_len,
        local_epochs, n_rounds, aayg_mixes, plus the compute knobs
        agg_impl / eval_every / track_bias (DESIGN.md §9).  Per-scenario
        fields of `cfg` (protocol, mode, lr, seed) are ignored by the
        runner.
      devices: default device spec for `run()` — a device sequence, an
        int (first k devices), or None for the single-device vmap path.
        Overridable per call.
      tracker: metrics sink (`repro.launch.tracker.Tracker`) for cache
        hit/miss/evict counters, batch fill ratios, the
        ``grid/train_loss_reused`` counter (chunk-end train losses taken
        from the next chunk's first local epoch instead of a pass of their
        own, per real scenario) and the ``grid/*`` spans of each `run`
        (``run``, ``validate``, per dispatch group ``prepare`` and
        ``dispatch``, ``collect``); defaults to the no-op NullTracker.
      max_cached_programs: LRU bound on the compiled-program cache
        (DESIGN.md §11).  None = unbounded — fine for one-shot figure
        runs, a leak for a long-lived server (the serving engine always
        sets a bound).
    """

    def __init__(
        self,
        init_fn: Callable[[jax.Array], Pytree],
        apply_fn: Callable[[Pytree, jnp.ndarray], jnp.ndarray],
        data: FederatedDataset,
        cfg: simulator.SimConfig,
        *,
        devices: DeviceSpec = None,
        tracker: launch_tracker.Tracker | None = None,
        max_cached_programs: int | None = None,
    ):
        self._build_sim = lambda dm: simulator.build_sim(
            init_fn, apply_fn, data,
            seg_len=cfg.seg_len, local_epochs=cfg.local_epochs,
            n_rounds=cfg.n_rounds, aayg_mixes=cfg.aayg_mixes,
            agg_impl=cfg.agg_impl, eval_every=cfg.eval_every,
            track_bias=cfg.track_bias, model_shards=dm,
            model_axis=launch_mesh.MODEL_AXIS,
            local_optimizer=cfg.local_optimizer,
        )
        self.sim = self._build_sim(1)
        # One SimPrograms binding per model-axis width (DESIGN.md §13):
        # `model_shards` is static (it sizes the local segment window), so
        # a runner serving 1-D and 2-D meshes side by side keeps one sim
        # per Dm — tiny host objects; the heavy compiled programs live in
        # the bounded ProgramCache below.
        self._sims: dict[int, simulator.SimPrograms] = {1: self.sim}
        self.devices = devices
        self.tracker = tracker or launch_tracker.NullTracker()
        self._calls = 0         # `run` calls so far: the spans' ``run`` id
        self._seg_len = cfg.seg_len
        # Bounded LRU of AOT-compiled executables, keyed by (kind, hoist
        # signature, mesh, input avals) — see ProgramCache.
        self.programs = ProgramCache(max_cached_programs,
                                     tracker=self.tracker)
        # Donate the scenario batch on accelerators: the (G, ...) stacks are
        # re-transferred from the host-side grid each dispatch, so their
        # device buffers never outlive one call (no double-buffering of the
        # round-loop state against its inputs).  No-op on CPU.
        self._donate = simulator.donate_kwargs()
        self._scalar = jax.jit(self.sim.run_scenario, **self._donate)

    def _sim_for(self, model_shards: int) -> simulator.SimPrograms:
        sim = self._sims.get(model_shards)
        if sim is None:
            sim = self._sims[model_shards] = self._build_sim(model_shards)
        return sim

    def validate(self, grid: ScenarioGrid, *,
                 strict_packet: bool = False) -> None:
        """Admission-time grid validation against this runner's binding
        (client count, codec segment size) — see `validate_grid`.  Raises
        `AdmissionError` naming the offending scenario labels."""
        validate_grid(grid, n_clients=self.sim.n_clients,
                      seg_len=self._seg_len, strict_packet=strict_packet)

    def _index_groups(self, grid: ScenarioGrid,
                      group_by_protocol: bool) -> list[list[int]]:
        """The (protocol, mode)-homogeneous dispatch partition of a grid."""
        g = len(grid)
        if not group_by_protocol:
            return [list(range(g))]
        pid = np.asarray(grid.scenarios.protocol_id)
        mid = np.asarray(grid.scenarios.mode_id)
        groups: dict[tuple, list[int]] = {}
        for i in range(g):
            groups.setdefault((int(pid[i]), int(mid[i])), []).append(i)
        return list(groups.values())

    def run(self, grid: ScenarioGrid, *,
            group_by_protocol: bool = True,
            devices: DeviceSpec = _INHERIT,
            sharding: Any = None,
            pad_to: int | Sequence[int] | None = None,
            validate: bool = True) -> GridResult:
        """Run the whole grid through ONE jitted, vmapped training loop.

        With ``group_by_protocol`` (default), scenarios are partitioned
        into (protocol, mode)-homogeneous sub-batches: the protocol
        selector is then a hoisted scalar, so each scenario executes only
        ITS branch instead of all five (a vmapped lax.switch lowers to
        select-over-all-branches).  Equal-sized groups share one compiled
        program — e.g. a figure sweeping 3 protocol rows over 9 networks
        compiles once and dispatches 3 times.  ``group_by_protocol=False``
        forces the single fully-batched dispatch.

        ``devices=`` (or a prebuilt 1-D ``sharding=`` mesh) shards each
        sub-batch over a ``('grid',)`` mesh via shard_map: the batch is
        padded to a multiple of the device count with routing-neutral
        filler scenarios, every device runs the vmapped loop on its slice
        (no collectives), and results are gathered + unpadded —
        bit-identical to the single-device path.  Defaults to the
        runner's ``devices``; an explicit ``devices=None`` forces the
        single-device vmap path regardless of the runner default.

        ``pad_to=`` declares warm batch-size buckets (an int or a
        sequence): each (protocol, mode) sub-batch is padded with
        routing-neutral filler scenarios up to the smallest bucket that
        fits (see `_bucket_target`), so a serving tier dispatching
        variable-size coalesced batches reuses a BOUNDED family of
        compiled programs instead of compiling per arrival pattern.
        Filler rows are dropped on unpad — results are bit-identical to
        the unpadded dispatch.

        ``validate=False`` skips admission validation (`validate_grid`)
        for callers that already validated at submission time.
        """
        self._calls += 1
        run = self._calls
        with self.tracker.span("grid/run", run=run):
            mesh = _resolve_grid_mesh(
                self.devices if devices is _INHERIT else devices, sharding
            )
            with self.tracker.span("grid/validate", run=run):
                # Surface PER-packet vs codec-segment mismatches on the
                # grid path too (one-time warning; see
                # simulator.check_packet_len).  The per-value bit width
                # follows the bound model's state dtype.
                for bits in getattr(grid, "packet_len_bits", ()):
                    simulator.check_packet_len(
                        bits, self._seg_len,
                        bits_per_value=self.sim.bits_per_value,
                    )
                if validate:
                    self.validate(grid)
                index_groups = self._index_groups(grid, group_by_protocol)

            rows: list[dict | None] = [None] * len(grid)
            for idx in index_groups:
                target = _bucket_target(len(idx), pad_to)
                meta = {"run": run, "group": _group_label(grid, idx),
                        "batch": target}
                with self.tracker.span("grid/prepare", **meta):
                    sub = jax.tree.map(
                        lambda leaf: leaf[np.asarray(idx)], grid.scenarios
                    )
                    if target != len(idx):
                        sub = _pad_scenario_batch(sub, target)
                    self.tracker.observe("grid/batch_fill", len(idx) / target)
                    if mesh is None:
                        program, args = self._program_vmap(sub)
                    else:
                        program, args = self._program_sharded(sub, mesh)
                with self.tracker.span("grid/dispatch", **meta):
                    metrics = program(args)
                    # Each real scenario took n_chunks - 1 of its chunk-end
                    # train losses from the next chunk's first local epoch.
                    self.tracker.count("grid/train_loss_reused", len(idx) * (
                        self.sim.n_chunks - self.sim.train_loss_passes))
                    # Unpad: filler rows (j >= len(idx)) are never read.
                    # The row slices queue on the device behind this
                    # program, while the next group's program runs.
                    for j, i in enumerate(idx):
                        rows[i] = jax.tree.map(lambda leaf: leaf[j], metrics)

            with self.tracker.span("grid/collect", run=run):
                stacked = jax.tree.map(_stack_rows, *rows)
                return _metrics_to_grid_result(stacked, grid.labels)

    def warmup(self, grid: ScenarioGrid, *,
               group_by_protocol: bool = True,
               devices: DeviceSpec = _INHERIT,
               sharding: Any = None,
               pad_to: int | Sequence[int] | None = None) -> int:
        """AOT-compile every program `run()` would need for this grid —
        WITHOUT dispatching it.

        The declared-shape warmup of DESIGN.md §11: a server warms the
        (protocol, mode) x bucket shapes it expects before opening for
        traffic, so first requests never pay compilation.  Compilation
        goes through the same `ProgramCache` as `run` (same keys — a
        warmed program IS the served program), counting toward the LRU
        bound.  Returns the number of programs actually compiled (0 when
        everything was already warm).
        """
        mesh = _resolve_grid_mesh(
            self.devices if devices is _INHERIT else devices, sharding
        )
        misses0 = self.programs.misses
        for idx in self._index_groups(grid, group_by_protocol):
            sub = jax.tree.map(
                lambda leaf: leaf[np.asarray(idx)], grid.scenarios
            )
            target = _bucket_target(len(idx), pad_to)
            if target != len(idx):
                sub = _pad_scenario_batch(sub, target)
            if mesh is None:
                self._program_vmap(sub)
            else:
                self._program_sharded(sub, mesh)
        return self.programs.misses - misses0

    def _program_vmap(self, sub: simulator.Scenario):
        """Single-device path: the AOT-compiled jit(vmap) program for this
        sub-batch's hoist signature + avals, plus its call args."""
        axes, args = _hoist_uniform(sub)
        sig = ("vmap", tuple(axes._asdict().items()), _aval_sig(args))

        def build():
            fn = jax.jit(
                jax.vmap(self.sim.run_scenario, in_axes=(axes,)),
                **self._donate,
            )
            return fn.lower(args).compile()

        return self.programs.lookup(sig, build), args

    def _program_sharded(self, sub: simulator.Scenario,
                         mesh: jax.sharding.Mesh):
        """Sharded path: pad to a device multiple, shard_map the vmap.

        Each device runs `vmap(run_scenario)` over its (g_pad / Dg)-slice;
        scenarios are independent, so on a 1-D ``('grid',)`` mesh the
        lowered per-device program has no cross-device collectives — XLA
        only gathers the stacked metrics at the end.  On a 2-D
        ``('grid', 'model')`` mesh (DESIGN.md §13) each scenario's segment
        axis is additionally split across the ``model`` groups: the
        per-device sim carries the local (N, L_local, K) window, training
        `all_gather`s full rows within the group, and metrics come out
        replicated along the model axis (out_specs name only the grid
        axis).  The returned program's leaves keep the PADDED leading
        axis.

        A mesh whose grid axis is wider than the sub-batch is shrunk to
        its first g grid rows (keeping every model shard): the excess
        devices would only ever compute filler trajectories.
        """
        names = tuple(mesh.axis_names)
        axis_name = names[0]
        dm = int(mesh.shape[names[1]]) if len(names) == 2 else 1
        sim = self._sim_for(dm)
        g = sub.link_eps.shape[0]
        dev = mesh.devices.reshape(-1, dm)
        if dev.shape[0] > g:
            dev = dev[:g]
            mesh = jax.sharding.Mesh(
                dev if len(names) == 2 else dev.reshape(-1), names
            )
        d = dev.shape[0]
        sub = _pad_scenario_batch(sub, -(-g // d) * d)
        axes, args = _hoist_uniform(sub)
        specs = simulator.Scenario(**{
            name: P(axis_name) if ax == 0 else P()
            for name, ax in axes._asdict().items()
        })
        args = simulator.Scenario(**{
            name: leaf if leaf is None else jax.device_put(
                leaf, NamedSharding(mesh, getattr(specs, name)))
            for name, leaf in args._asdict().items()
        })
        sig = ("shard", tuple(axes._asdict().items()),
               launch_mesh.mesh_fingerprint(mesh), _aval_sig(args))

        def build():
            sharded = shard_map(
                jax.vmap(sim.run_scenario, in_axes=(axes,)),
                mesh=mesh, in_specs=(specs,), out_specs=P(axis_name),
                # Grid axis: no collectives inside; model axis: metrics are
                # replicated.  Skip the replication check (it rejects some
                # primitives in the RNG/scan body).
                check_vma=False,
            )
            return jax.jit(sharded, **self._donate).lower(args).compile()

        return self.programs.lookup(sig, build), args

    def run_sequential(self, grid: ScenarioGrid) -> GridResult:
        """Per-scenario-dispatch baseline: the compiled scalar program,
        called once per grid row.  Semantically identical to `run()` (same
        pure program, no vmap) — the timing baseline for dispatch-overhead
        comparisons and equivalence tests."""
        metrics = [self._scalar(grid.scenario(i)) for i in range(len(grid))]
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *metrics)
        return _metrics_to_grid_result(stacked, grid.labels)


def run_grid(
    init_fn: Callable[[jax.Array], Pytree],
    apply_fn: Callable[[Pytree, jnp.ndarray], jnp.ndarray],
    data: FederatedDataset,
    grid: ScenarioGrid,
    cfg: simulator.SimConfig,
    *,
    group_by_protocol: bool = True,
    devices: DeviceSpec = None,
    sharding: Any = None,
) -> GridResult:
    """One-shot batched grid run (see GridRunner.run).

    `cfg` supplies the static (shared) knobs: seg_len, local_epochs,
    n_rounds, aayg_mixes.  Per-scenario knobs live in the grid.
    ``devices=`` / ``sharding=`` shard the grid axis across a device mesh
    (bit-identical results; see the module docstring and DESIGN.md §7).
    """
    runner = GridRunner(init_fn, apply_fn, data, cfg)
    return runner.run(grid, group_by_protocol=group_by_protocol,
                      devices=devices, sharding=sharding)


def run_sequential(
    init_fn: Callable[[jax.Array], Pytree],
    apply_fn: Callable[[Pytree, jnp.ndarray], jnp.ndarray],
    data: FederatedDataset,
    grid: ScenarioGrid,
    cfg: simulator.SimConfig,
) -> GridResult:
    """One-shot per-scenario-dispatch baseline (see GridRunner)."""
    runner = GridRunner(init_fn, apply_fn, data, cfg)
    return runner.run_sequential(grid)
