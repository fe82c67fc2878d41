"""D-FL training simulator: N clients, local epochs, protocol exchange.

Reproduces the paper's experimental loop (Sec. V): every round, each client
trains I full-batch epochs on its local shard (vmapped across clients), then
models are exchanged and locally aggregated under the selected protocol
(R&A / AaYG / C-FL / ideal C-FL) with the selected aggregation mechanism
(adaptive normalization / model substitution).

The round loop is a PURE jitted function: a `Scenario` carries every
per-scenario parameter as a traced array (protocol id, aggregation-mode id,
link qualities, seed, learning rate), so one compiled program serves an
arbitrary scenario — and `repro.fl.scenarios.run_grid` can `jax.vmap` the
whole training loop across a scenario grid in a single XLA dispatch (and,
with ``devices=``, shard that grid across a device mesh; DESIGN.md §7).

Dynamic scenarios (DESIGN.md §8): a `Scenario` may also be a *trajectory*
of grid points —

  * ``link_eps`` with a leading time axis ``(T, V, V)`` (round t uses
    entry ``t % T``; `prepare` derives the matching ``(T, V, V)`` rho
    stack once, outside the round scan),
  * a ``participation`` mask ``(N,)`` or ``(T, N)`` (client sampling:
    masked-out clients skip local training, contribute nothing to any
    aggregation, and keep their parameters untouched),
  * a per-client ``local_epochs`` vector ``(N,)`` (heterogeneous compute;
    the static ``SimConfig.local_epochs`` is the compiled scan bound and
    per-client values are clipped to it).

All three default to the static behavior (None / rank-2 ``link_eps``), in
which case `run_scenario` traces the exact pre-dynamic program; a neutral
dynamic axis (T=1 schedule, all-ones mask) is the same semantics in a
different program, equal within float32 round-off (DESIGN.md §16).

Closed-loop selection (DESIGN.md §10): a `Scenario` may additionally carry
a ``policy_id`` / ``select_frac`` pair (`core.selection.POLICY_IDS`); the
participation mask is then computed INSIDE the round scan, per round, from
live per-client signals (trailing train loss + local update norms) carried
in the scan state — dispatched by `lax.switch` like protocols, so a grid
sweeping policies stays one vmapped/sharded dispatch.  ``policy_id=None``
(the default) traces the exact pre-policy program; the ``uniform`` policy
has the open-loop participation path's semantics (the same masks, and
trajectories equal within float32 round-off, DESIGN.md §16).

Segment-native state + model-axis sharding (DESIGN.md §13): the round
scan carries the paper's exchange representation — client-stacked segment
rows ``(N, S, seg_len)`` — natively; the pytree <-> segment codec runs
once per `run_scenario`, at the boundary, and each local epoch takes
each client's pytree out of its row and lays the update back.
``build_sim(model_shards=Dm)`` additionally shards the segment axis over a
``model`` mesh axis inside each scenario (`run_scenario` then runs under
`shard_map`; see `repro.fl.scenarios` / `launch.mesh.grid_model_mesh`),
and the
``init_scan`` / ``advance_chunk`` pair exposes the scan state for the
preemption-safe checkpoint runner (`repro.checkpoint.checkpoint`).

Static compute knobs (DESIGN.md §9): `SimConfig.agg_impl` selects the
aggregation substrate (jnp reference vs the fused/batched Pallas kernel;
auto = native Pallas on TPU only), `eval_every=k` thins per-round metric
evaluation to every k-th round (static ``(n_rounds // k,)`` metric axis;
the trained trajectory is unchanged), and `track_bias=False`
drops the R&A ||Lambda||^2 diagnostic from the hot loop.

The simulator is model-agnostic: pass any (init, apply) pair from
`repro.models.smallnets` (or a closure).

Public API
----------
  SimConfig                 static + default per-scenario knobs
  Scenario / make_scenario  one grid point, all fields traced arrays
  Scenario.at_round(t)      per-round view of a dynamic scenario
  build_sim(...)            bind (init, apply, data, statics) -> SimPrograms
  SimPrograms.run_scenario  scenario -> metrics dict (scanned n_rounds)
  SimPrograms.init_scan / advance_chunk   the round API, a chunk at a time
  chunk_metrics             stacked advance_chunk rows -> metrics dict
  ROUND_SCOPES              the round's phases as `jax.named_scope`s

The one-scenario entry point is `repro.fl.scenarios.simulate`: the
one-point grid, so a scalar run IS the grid program.

Purity contract: `run_scenario`, `init_scan` and `advance_chunk` are
side-effect free functions of their arguments plus the statics bound by
`build_sim` — jit/vmap/shard_map-safe by construction (see
tests/test_scenarios.py).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, ClassVar, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression, errors, protocols, routing, selection, topology
from repro.data.synthetic import FederatedDataset
from repro.models.smallnets import accuracy, ce_loss

Pytree = Any

# Default mesh axis name for model-axis (segment) sharding — DESIGN.md §13.
# `launch.mesh.MODEL_AXIS` re-exports it for the mesh-builder layer.
MODEL_AXIS = "model"

# fold_in tag deriving the codec's private key from the round key.  The
# round key itself still feeds the exchange UNTOUCHED, so configuring
# codec="none" draws the same channel randomness as no codec at all —
# load-bearing for the neutral codec's equivalence (DESIGN.md §15).
_CODEC_KEY_TAG = 0x434F4445  # "CODE"

# The round's phases as `jax.named_scope`s: every op of local training,
# of the exchange (codec, mask draw, aggregation, bias) and of metric
# evaluation carries its phase in its ``op_name`` metadata, so a device
# trace splits a round by phase.  Scopes add metadata only: the compiled
# instructions are the same with or without them.
ROUND_SCOPES = ("local_train", "exchange", "evaluate")
_TRAIN_SCOPE, _EXCHANGE_SCOPE, _EVAL_SCOPE = ROUND_SCOPES


class PacketLengthMismatchWarning(UserWarning):
    """The codec's segment size and the network's PER packet length differ."""


@jax.custom_batching.custom_vmap
def _fusion_barrier(tree: Pytree) -> Pytree:
    """`lax.optimization_barrier` that composes with vmap (identity values).

    `local_train` takes each client's leaves out of its segment row behind
    this barrier every epoch.  It holds correctness, not round-off: on a
    TPU v5e the gradient taken through the row views without it left
    ResNet-18-GN at about twice the plain reference's loss after one step
    (the cause is not known), and it gives the leaves buffers of their own,
    without which the CharRNN program's temporaries grow by 0.9 GB.
    `optimization_barrier` has no batching rule, so `run_grid`'s vmap needs
    this custom one: the barrier is elementwise identity, hence batching
    passes straight through.
    """
    return jax.lax.optimization_barrier(tree)


@_fusion_barrier.def_vmap
def _fusion_barrier_vmap(axis_size, in_batched, tree):
    del axis_size
    return jax.lax.optimization_barrier(tree), in_batched[0]


@dataclasses.dataclass
class SimConfig:
    """Simulation knobs.

    Static fields (seg_len, local_epochs, n_rounds, aayg_mixes) are baked
    into the compiled program; the rest are per-scenario defaults that
    `make_scenario` lifts into traced `Scenario` fields (a `ScenarioGrid`
    overrides them per grid point and ignores them here).
    """

    protocol: str = "ra"          # ra | aayg | cfl | ideal_cfl | none
    mode: str = "ra_normalized"   # ra_normalized | substitution
    seg_len: int = 1024           # K float32 values per segment (32*K bits)
    local_epochs: int = 5         # I (scan bound for per-client vectors)
    lr: float = 0.05
    n_rounds: int = 50
    aayg_mixes: int = 1           # J
    cfl_aggregator: int = 6       # paper: node 7 (index 6)
    seed: int = 0
    # Static compute knobs (DESIGN.md §9) — they change the compiled
    # program, not the trained trajectory:
    agg_impl: str = "auto"        # auto | jnp | pallas (aggregation substrate)
    eval_every: int = 1           # evaluate acc/loss every k-th round
    track_bias: bool = True       # False: skip the R&A bias diagnostic
    # Exchange codec (DESIGN.md §15) — per-scenario defaults like protocol:
    codec: str | None = None      # None | none | topk | quant
    compress_ratio: float = 1.0   # traced codec intensity, (0, 1]
    # Local-update rule (static; None = the paper's plain full-batch GD):
    local_optimizer: Any = None   # None | optimizers name | Optimizer | factory

    @property
    def packet_len_bits(self) -> int:
        """Bits per transmitted packet implied by ``seg_len`` (32 * K).

        NOTE the paper's experimental defaults are internally inconsistent:
        its PER model uses 25,000-bit packets (`topology.paper_network`)
        while a 1024-float32 segment is 32,768 bits — 25,000 is not even a
        multiple of 32.  We keep both paper defaults and surface the
        mismatch via `check_packet_consistency` (a one-time warning) rather
        than silently rescaling either; pass
        ``packet_len_bits=cfg.packet_len_bits`` to the network builders for
        a self-consistent channel.
        """
        return errors.packet_len_bits(self.seg_len)


class Scenario(NamedTuple):
    """One grid point (or a trajectory of them), every field a traced array.

    ``link_eps`` is a (V, V) per-link packet success matrix — or a
    (T, V, V) *schedule* of them (round t uses entry ``t % T``); scenarios
    with fewer physical nodes (e.g. fewer relays) are padded with isolated
    zero-quality nodes, which leaves the routed client block unchanged.
    ``rho`` is the derived E2E success matrix (matching rank) — None until
    `prepare`.  ``participation`` is an optional (N,) or (T, N) client
    sampling mask; ``local_epochs`` an optional (N,) per-client epoch
    vector.  ``policy_id`` / ``select_frac`` select a CLOSED-LOOP sampling
    policy (`core.selection.POLICY_IDS`): the per-round mask is then
    computed inside the round scan from live signals, with the
    ``participation`` schedule acting as the availability base.
    ``codec_id`` / ``compress_ratio`` select an exchange codec
    (`core.compression.CODEC_IDS`, DESIGN.md §15): local models are
    encoded between training and delivery — and the "budget" sampling
    policy overrides the ratio per client from its slot-budget waterfill.
    All dynamic fields default to the static behavior.
    """

    link_eps: jnp.ndarray         # (V, V) or (T, V, V)
    seed: jnp.ndarray             # () int32   model-init / channel seed
    protocol_id: jnp.ndarray      # () int32   protocols.PROTOCOL_IDS
    mode_id: jnp.ndarray          # () int32   protocols.MODE_IDS
    aggregator: jnp.ndarray       # () int32   C-FL star center
    lr: jnp.ndarray               # () float32 local GD step size
    rho: Any = None               # (V, V) / (T, V, V) E2E success (derived)
    participation: Any = None     # (N,) / (T, N) float32 sampling mask
    local_epochs: Any = None      # (N,) int32 per-client local epochs
    policy_id: Any = None         # () int32   selection.POLICY_IDS
    select_frac: Any = None       # () float32 participant fraction
    codec_id: Any = None          # () int32   compression.CODEC_IDS
    compress_ratio: Any = None    # () float32 codec intensity, (0, 1]

    def prepare(self) -> "Scenario":
        """Fill the derived min-E2E-PER success matrix (idempotent).

        Rank-3 ``link_eps`` schedules are re-routed per entry (vmapped
        Floyd–Warshall over the time axis) ONCE, outside the round scan.
        """
        if self.rho is not None:
            return self
        if jnp.ndim(self.link_eps) == 3:
            rho = jax.vmap(lambda le: routing.e2e_success(le)[0])(
                jnp.asarray(self.link_eps)
            )
        else:
            rho, _ = routing.e2e_success(self.link_eps)
        return self._replace(rho=rho)

    @property
    def is_dynamic(self) -> bool:
        """True if any trajectory axis is active (topology schedule,
        client sampling, or heterogeneous local epochs)."""
        return (jnp.ndim(self.link_eps) == 3
                or self.participation is not None
                or self.local_epochs is not None)

    @property
    def is_closed_loop(self) -> bool:
        """True if a live sampling policy decides participation in-loop."""
        return self.policy_id is not None

    def at_round(self, t: jnp.ndarray) -> "Scenario":
        """The static per-round view of a (possibly dynamic) scenario.

        Time-leaved fields are sliced at ``t`` modulo their own schedule
        length (a T=1 schedule is therefore exactly a static scenario);
        already-static fields pass through untouched.  The round body
        consumes these views — it never sees a time axis.
        """
        s = self
        if jnp.ndim(s.link_eps) == 3:
            tt = t % s.link_eps.shape[0]
            rho = None if s.rho is None else s.rho[tt]
            s = s._replace(link_eps=s.link_eps[tt], rho=rho)
        if s.participation is not None and jnp.ndim(s.participation) == 2:
            s = s._replace(
                participation=s.participation[t % s.participation.shape[0]]
            )
        return s


# One-time-warned (packet_len_bits, seg_len, bits_per_value) triples.
_WARNED_PACKET_PAIRS: set[tuple[int, ...]] = set()


def validate_eval_schedule(n_rounds: int, eval_every: int) -> None:
    """Raise (actionably) unless ``eval_every`` divides ``n_rounds``.

    The metric thinning of DESIGN.md §9 needs a static ``(n_rounds // k,)``
    axis, so the divisibility constraint is structural.  `build_sim`
    enforces it at build time, and the serving tier re-checks it at
    admission (`repro.launch.serving`) so a misconfigured request surfaces
    as a per-request error instead of killing a warm server.
    """
    if eval_every < 1 or n_rounds % eval_every:
        raise ValueError(
            f"eval_every={eval_every} must be >= 1 and divide "
            f"n_rounds={n_rounds} (metrics keep a static shape); the "
            f"nearest valid values are the divisors of {n_rounds}"
        )


def check_packet_len(recorded_bits: int | None, seg_len: int,
                     *, bits_per_value: int = errors.FLOAT_BITS,
                     strict: bool = False) -> bool:
    """Validate the codec segment size against a recorded PER packet length.

    The channel model samples per-*packet* errors for packets of
    ``recorded_bits`` bits, while the codec transmits segments of
    ``bits_per_value * seg_len`` bits; if they differ, the simulated PER
    applies to a packet size the codec never sends (the paper itself ships
    this mismatch: 25,000-bit PER packets vs 1024-float32 segments — see
    `SimConfig.packet_len_bits`).  ``bits_per_value`` comes from the bound
    model's state dtype (`errors.dtype_bits`; `SimPrograms.bits_per_value`)
    — before it existed, bf16 segment state was silently priced as float32
    packets.  Returns True when consistent (or when no packet length was
    recorded); warns ONCE per distinct (recorded_bits, seg_len,
    bits_per_value) triple otherwise.  Both the scalar path
    (`make_scenario`) and the grid path (`scenarios.GridRunner.run`, via
    `ScenarioGrid.packet_len_bits`) call this.

    ``strict=True`` (the serving-admission mode, DESIGN.md §11) raises a
    ValueError instead of warning: a long-lived server rejects the one
    inconsistent request rather than letting the mismatch ride silently.
    """
    if recorded_bits is None:
        return True
    implied = errors.packet_len_bits(seg_len, bits_per_value)
    if int(recorded_bits) == implied:
        return True
    msg = (
        f"network PER model uses {int(recorded_bits)}-bit packets but "
        f"seg_len={seg_len} transmits {implied}-bit "
        f"({bits_per_value}-bit-value) segments; pass "
        "packet_len_bits=cfg.packet_len_bits to the network builder "
        "for a self-consistent channel (the paper's own defaults "
        "carry this mismatch)"
    )
    if strict:
        raise ValueError(msg)
    key = (int(recorded_bits), int(seg_len), int(bits_per_value))
    if key not in _WARNED_PACKET_PAIRS:
        _WARNED_PACKET_PAIRS.add(key)
        warnings.warn(msg, PacketLengthMismatchWarning, stacklevel=3)
    return False


def check_packet_consistency(net: topology.Network, seg_len: int,
                             bits_per_value: int = errors.FLOAT_BITS) -> bool:
    """`check_packet_len` against a network's recorded packet length."""
    return check_packet_len(getattr(net, "packet_len_bits", None), seg_len,
                            bits_per_value=bits_per_value)


def make_scenario(
    net: topology.Network,
    cfg: SimConfig,
    *,
    link_schedule: jnp.ndarray | None = None,
    participation: jnp.ndarray | None = None,
    local_epochs: jnp.ndarray | None = None,
    sampling_policy: str | None = None,
    select_frac: float = 0.5,
    codec: str | None = None,
    compress_ratio: float | None = None,
) -> Scenario:
    """Lift a (Network, SimConfig) pair into a traced Scenario.

    Optional dynamic axes: ``link_schedule`` replaces the network's static
    link matrix with a (T, V, V) stack (see `topology.markov_link_schedule`
    / `topology.fading_per_schedule` / `topology.mobility_link_schedule`);
    ``participation`` is an (N,) or (T, N) sampling mask; ``local_epochs``
    an (N,) per-client vector.  ``sampling_policy`` (a
    `core.selection.POLICY_IDS` name) turns participation CLOSED-LOOP:
    each round selects ``ceil(select_frac * N)`` clients from live signals
    (the ``participation`` schedule, when also given, is the availability
    base — see DESIGN.md §10).  ``codec`` (a `core.compression.CODEC_IDS`
    name; defaults to ``cfg.codec``) encodes the exchange — top-k segment
    sparsification or stochastic quantization at ``compress_ratio``
    (defaults to ``cfg.compress_ratio``); codec "none" is the traced
    neutral point, the semantics of no codec at all (DESIGN.md §15).
    """
    codec = cfg.codec if codec is None else codec
    if codec is not None and codec not in compression.CODEC_IDS:
        raise ValueError(
            f"unknown codec {codec!r}: "
            f"choose from {sorted(compression.CODEC_IDS)}"
        )
    ratio = cfg.compress_ratio if compress_ratio is None else compress_ratio
    if codec is not None and not 0.0 < float(ratio) <= 1.0:
        raise ValueError(f"compress_ratio must be in (0, 1], got {ratio}")
    check_packet_consistency(net, cfg.seg_len)
    link_eps = net.link_eps if link_schedule is None else link_schedule
    if sampling_policy is not None and sampling_policy not in selection.POLICY_IDS:
        raise ValueError(
            f"unknown sampling_policy {sampling_policy!r}: "
            f"choose from {sorted(selection.POLICY_IDS)}"
        )
    return Scenario(
        link_eps=jnp.asarray(link_eps, jnp.float32),
        seed=jnp.asarray(cfg.seed, jnp.int32),
        protocol_id=jnp.asarray(protocols.PROTOCOL_IDS[cfg.protocol], jnp.int32),
        mode_id=jnp.asarray(protocols.MODE_IDS[cfg.mode], jnp.int32),
        aggregator=jnp.asarray(cfg.cfl_aggregator, jnp.int32),
        lr=jnp.asarray(cfg.lr, jnp.float32),
        participation=(None if participation is None
                       else jnp.asarray(participation, jnp.float32)),
        local_epochs=(None if local_epochs is None
                      else jnp.asarray(local_epochs, jnp.int32)),
        policy_id=(None if sampling_policy is None
                   else jnp.asarray(selection.POLICY_IDS[sampling_policy],
                                    jnp.int32)),
        select_frac=(None if sampling_policy is None
                     else jnp.asarray(select_frac, jnp.float32)),
        codec_id=(None if codec is None
                  else jnp.asarray(compression.CODEC_IDS[codec], jnp.int32)),
        compress_ratio=(None if codec is None
                        else jnp.asarray(ratio, jnp.float32)),
    )


@dataclasses.dataclass
class SimResult:
    acc_per_client: np.ndarray    # (rounds, N) test accuracy
    loss_per_client: np.ndarray   # (rounds, N) train loss
    bias_norms: np.ndarray        # (rounds,) mean ||Lambda_l||_F^2 (ra only)

    @property
    def mean_acc(self) -> np.ndarray:
        return self.acc_per_client.mean(axis=1)


def _pad_shards(data: FederatedDataset) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pad client shards to a common size (full-batch GD per paper)."""
    max_sz = max(len(x) for x in data.train_x)

    def pad(x):
        reps = -(-max_sz // len(x))
        return np.tile(x, (reps,) + (1,) * (x.ndim - 1))[:max_sz]

    xs = jnp.asarray(np.stack([pad(x) for x in data.train_x]))
    ys = jnp.asarray(np.stack([pad(y) for y in data.train_y]))
    return xs, ys


@dataclasses.dataclass(frozen=True)
class SimPrograms:
    """Pure functions of one (init, apply, data, statics) binding.

    ``run_scenario(scenario) -> metrics`` runs the full segment-native
    scan; it is jit/vmap-safe, and it is what `scenarios.run_grid` vmaps
    across a grid (a one-scenario run is that grid at G = 1,
    `scenarios.simulate`).

    Checkpointable scan API (DESIGN.md §13): ``init_scan(scenario)`` builds
    the segment-native scan state ``{"w": (N, L_local, K) rows, "key": key
    [, "sig": SelectionSignals]}`` and ``advance_chunk(state, scenario, c)``
    advances chunk ``c`` (= ``eval_every`` rounds, one metrics row).
    `run_scenario` itself is a `lax.scan` of `advance_chunk` followed by
    `chunk_metrics`, so a host loop that jits `advance_chunk` once, feeds
    chunks ``0..n_chunks-1`` and applies `chunk_metrics` to the stacked
    rows (see `repro.checkpoint.checkpoint.run_resumable`) replays the
    same per-chunk program whether or not it was interrupted in between —
    that, not floating-point luck, is the bitwise-resume guarantee.  The
    host loop and the fused scan are two XLA programs of the same math:
    they agree within float32 round-off, not bit for bit (DESIGN.md §16).
    Chunk c's train ``loss`` comes from chunk c+1's first local epoch;
    only the last chunk runs a train-loss pass of its own
    (``train_loss_passes``).

    With ``model_shards > 1`` the ``"w"`` rows are the LOCAL model-axis
    shard and `run_scenario` / `init_scan` / `advance_chunk` must run
    inside a `shard_map` binding the ``model_axis`` axis name
    (`scenarios.GridRunner` and `checkpoint.run_resumable` do this).
    """

    run_scenario: Callable[[Scenario], dict]
    n_clients: int
    n_rounds: int
    init_scan: Callable[[Scenario], dict]
    advance_chunk: Callable[[dict, Scenario, jnp.ndarray], tuple[dict, dict]]
    n_chunks: int
    eval_every: int
    model_shards: int
    model_axis: str
    n_segments: int       # S: global segment count of the bound model
    local_segments: int   # L_local = ceil(S / model_shards)
    seg_len: int
    bits_per_value: int = errors.FLOAT_BITS  # from the bound state dtype
    # Standalone train-loss forwards per scenario: only the last chunk's;
    # every other chunk's loss is the next chunk's first local epoch.
    train_loss_passes: ClassVar[int] = 1


def build_sim(
    init_fn: Callable[[jax.Array], Pytree],
    apply_fn: Callable[[Pytree, jnp.ndarray], jnp.ndarray],
    data: FederatedDataset,
    *,
    seg_len: int,
    local_epochs: int,
    n_rounds: int,
    aayg_mixes: int = 1,
    agg_impl: str = "auto",
    eval_every: int = 1,
    track_bias: bool = True,
    model_shards: int = 1,
    model_axis: str = MODEL_AXIS,
    local_optimizer: Any = None,
) -> SimPrograms:
    """Bind data + statics into the pure scenario programs.

    The scan state is SEGMENT-NATIVE (DESIGN.md §13): the round loop
    carries the paper's exchange representation — client-stacked segment
    rows ``(N, S, seg_len)`` — and the pytree <-> segment codec
    (`protocols._to_segments` / `_from_segments`) runs exactly once per
    `run_scenario`, at the boundary, never inside the round scan.  Local
    training takes each client's pytree as exact layout views of its row
    (reshape/split/slice) every epoch, differentiates the loss on those
    leaves and lays the updated leaves back by one concatenation, so the
    trained trajectory is the pytree carry's math on a segment layout.

    Args:
      init_fn: model init, `key -> params` pytree (one shared init; the
        paper assumes a common model structure + starting point).
      apply_fn: forward pass, `(params, x) -> logits`.
      data: federated dataset; client shards are padded to a common size
        (full-batch GD per the paper) and closed over as constants.
      seg_len: K values per packet segment (static).
      local_epochs: I full-batch GD epochs per round (static).
      n_rounds: scan length of `run_scenario` (static).
      aayg_mixes: J one-hop mix iterations for AaYG (static).
      agg_impl: aggregation substrate (auto | jnp | pallas — resolved once
        here; see `core.aggregation.apply_mode` / DESIGN.md §9).
      eval_every: evaluate test accuracy / train loss only every k-th round
        (must divide ``n_rounds``).  `run_scenario` metrics then carry a
        static ``(n_rounds // k,)`` leading axis for acc/loss — row j is
        round ``(j + 1) * k - 1`` — while ``bias`` stays per-round; grids
        batch exactly as before.  ``k=1`` advances one round per chunk,
        with no inner scan.
      track_bias: False skips the R&A ||Lambda||^2 diagnostic (bias is NaN
        for every round; its mask reductions leave the compiled hot loop).
      model_shards: Dm, the model-axis mesh size (static).  With
        ``model_shards > 1`` the scan state holds only this shard's
        ``L_local = ceil(S / Dm)`` segment window and `run_scenario` must
        execute inside a `shard_map` binding ``model_axis``: training
        `all_gather`s the full rows (replicated compute), the O(N²·L·K)
        exchange runs on the local window with full-width mask draws
        sliced per shard (`protocols.dispatch_round_seg` seg_total /
        seg_start), and metrics come out replicated.  ``model_shards=1``
        (default) needs no mesh and IS the single-device program.
      model_axis: the mesh axis name the sharded program binds.
      local_optimizer: the per-client local-update rule (STATIC).  ``None``
        (default) is the paper's plain full-batch GD — the exact historical
        trace.  Otherwise an `repro.optim.optimizers` name ("sgd",
        "adamw", ...), an `optimizers.Optimizer` instance (its own lr wins
        over the scenario's), or a factory ``lr -> Optimizer``.  Named
        optimizers are built per trace with the TRACED scenario lr, so an
        lr grid axis still batches; optimizer state is fresh each round
        (local Adam à la FedAvg: moments do not persist across exchange).
        ``sgd`` with momentum 0 is the same `p - lr*g` update expression
        as the built-in GD path (the same semantics, DESIGN.md §16).

    Returns:
      `SimPrograms` with `run_scenario` / `init_scan` / `advance_chunk`
      pure functions.
    """
    from repro.core import aggregation
    from repro.optim import optimizers

    validate_eval_schedule(n_rounds, eval_every)
    if model_shards < 1:
        raise ValueError(f"model_shards={model_shards} must be >= 1")
    agg_impl = aggregation.resolve_impl(agg_impl)

    if local_optimizer is None:
        opt_factory = None
    elif isinstance(local_optimizer, str):
        optimizers.get(local_optimizer, 0.0)   # fail on unknown names NOW
        _name = local_optimizer

        def opt_factory(lr):
            return optimizers.get(_name, lr)
    elif isinstance(local_optimizer, optimizers.Optimizer):
        _opt = local_optimizer

        def opt_factory(lr):
            return _opt
    elif callable(local_optimizer):
        opt_factory = local_optimizer
    else:
        raise ValueError(
            "local_optimizer must be None, an optimizer name, an "
            f"Optimizer, or a factory lr -> Optimizer; got "
            f"{local_optimizer!r}"
        )
    n = data.n_clients
    p = jnp.asarray(data.weights())
    xs, ys = _pad_shards(data)
    test_x = jnp.asarray(data.test_x)
    test_y = jnp.asarray(data.test_y)

    # Static segment layout, computed ONCE at build time: the scan carries
    # (N, L_local, K) rows and every pytree view below is pure layout.
    leaves0, treedef = jax.tree_util.tree_flatten(
        jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    )
    leaf_shapes = [tuple(l.shape) for l in leaves0]
    leaf_sizes = [int(np.prod(s)) for s in leaf_shapes]
    leaf_splits = np.cumsum(leaf_sizes)[:-1]
    m_params = int(sum(leaf_sizes))
    s_total = errors.num_segments(m_params, seg_len)
    l_local = -(-s_total // model_shards)
    # Segments carry the promoted state dtype (stack_to_matrix concatenates
    # the leaves), so packet accounting prices THAT — not a hard-coded 32.
    state_dtype = jnp.result_type(*(l.dtype for l in leaves0))
    bits_per_value = errors.dtype_bits(state_dtype)

    def _leaf_views(row: jnp.ndarray) -> Pytree:
        """One client's parameter pytree as pure layout views of its row.

        ``row`` is a full (S, K) — or flattened-compatible — segment row;
        entries past ``m_params`` are codec padding (zero; local training
        carries them over unchanged, see `_row_of`).
        """
        flat = row.reshape(-1)[:m_params]
        parts = jnp.split(flat, leaf_splits)
        return jax.tree_util.tree_unflatten(
            treedef, [pt.reshape(sh) for pt, sh in zip(parts, leaf_shapes)]
        )

    _views_batch = jax.vmap(_leaf_views)

    def _seg_start():
        if model_shards == 1:
            return 0
        return jax.lax.axis_index(model_axis) * l_local

    def _full_rows(w_loc: jnp.ndarray) -> jnp.ndarray:
        """Local (N, L_local, K) shard -> full (N, S_pad, K) rows."""
        if model_shards == 1:
            return w_loc
        return jax.lax.all_gather(w_loc, model_axis, axis=1, tiled=True)

    def _init_rows(key: jax.Array) -> jnp.ndarray:
        # Same init on every client (paper: common model structure + start);
        # the ONLY _to_segments of the whole scan.
        params0 = init_fn(key)
        stacked = jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf[None], (n,) + leaf.shape),
            params0,
        )
        w_seg, _spec, _m = protocols._to_segments(stacked, seg_len)
        if model_shards == 1:
            return w_seg
        w_seg = jnp.pad(
            w_seg, ((0, 0), (0, l_local * model_shards - s_total), (0, 0))
        )
        return jax.lax.dynamic_slice_in_dim(
            w_seg, _seg_start(), l_local, axis=1
        )

    def loss(params, x, y):
        return ce_loss(apply_fn(params, x), y)

    def _row_loss(row, x, y):
        return loss(_leaf_views(row), x, y)

    def _row_of(params: Pytree, row: jnp.ndarray) -> jnp.ndarray:
        """Lay one client's ``params`` back into ``row``'s layout; the codec
        padding past ``m_params`` is carried over from ``row``."""
        flat = row.reshape(-1)
        parts = [leaf.reshape(-1) for leaf in jax.tree_util.tree_leaves(params)]
        return jnp.concatenate(parts + [flat[m_params:]]).reshape(row.shape)

    def local_train(rows, lr, epochs=None):
        """Local training for `local_epochs` epochs (paper eq. 3), per client.

        ``rows`` are FULL segment rows (N, S[_pad], K), and the epoch scan
        carries them.  Each epoch takes the client's pytree out of its row
        (`_leaf_views`, behind a fusion barrier), differentiates the loss on
        those leaves and lays the updated leaves back (`_row_of`).  On a
        TPU v5e the two nearby forms departed from a plain reference: the
        gradient taken through the row views (ResNet-18-GN, a loss after
        one step about twice the reference's) and the leaves carried
        through the epochs (the two-epoch CNN, loss gaps 5e-3 to 4e-2
        against 5e-4).  The barrier gives the leaves buffers of their own:
        without it the CharRNN program's temporaries grow by 0.9 GB.
        ``epochs`` (optional, (N,) int32) enables heterogeneous compute: the
        scan still runs the static `local_epochs` bound, but client m's
        update is masked out after its own epoch count (values clip to the
        bound).  ``epochs=None`` keeps the exact static trace.

        With a bound ``local_optimizer`` the scan carries (row, opt_state)
        per client — state freshly `init`-ed each call (= each round) on
        the leaves — and the heterogeneous-epochs mask freezes BOTH row and
        state past a client's own epoch count.  ``local_optimizer=None`` is
        plain GD.

        Returns ``(rows, loss0)``: ``loss0`` (N,) is each client's
        full-batch train loss on the rows it ENTERED with — the value of
        the first epoch's forward (`jax.value_and_grad`), computed for
        every client, masked-out and zero-epoch ones included.  It is the
        previous chunk's end-of-chunk train loss (`advance_chunk`).
        """
        opt = None if opt_factory is None else opt_factory(lr)

        def step(r, st, x, y):
            prm = _fusion_barrier(_leaf_views(r))
            val, g = jax.value_and_grad(loss)(prm, x, y)
            if opt is None:
                new = jax.tree.map(lambda a, b: a - lr * b, prm, g)
            else:
                new, st = opt.update(prm, g, st)
            return (_row_of(new, r), st), val

        def init_state(row):
            return None if opt is None else opt.init(_leaf_views(row))

        def first_loss(vals, row, x, y):
            # A zero-epoch scan runs no forward: take the loss on its own.
            return vals[0] if local_epochs else _row_loss(row, x, y)

        if epochs is None:
            def train_one(row, x, y):
                def body(carry, _):
                    r, st = carry
                    return step(r, st, x, y)

                (out, _), vals = jax.lax.scan(
                    body, (row, init_state(row)), None, length=local_epochs
                )
                return out, first_loss(vals, row, x, y)

            return jax.vmap(train_one)(rows, xs, ys)

        epochs = jnp.minimum(jnp.asarray(epochs, jnp.int32), local_epochs)

        def train_one_masked(row, x, y, ep):
            def body(carry, i):
                r, st = carry
                (r2, st2), val = step(r, st, x, y)
                keep = i < ep
                r2 = jnp.where(keep, r2, r)
                if st is not None:
                    st2 = jax.tree.map(
                        lambda a, b: jnp.where(keep, a, b), st2, st
                    )
                return (r2, st2), val

            (out, _), vals = jax.lax.scan(body, (row, init_state(row)),
                                          jnp.arange(local_epochs))
            return out, first_loss(vals, row, x, y)

        return jax.vmap(train_one_masked)(rows, xs, ys, epochs)

    def evaluate(rows):
        def one(row):
            return accuracy(apply_fn(_leaf_views(row), test_x), test_y)

        return jax.vmap(one)(rows)

    def train_loss(rows):
        return jax.vmap(_row_loss)(rows, xs, ys)

    def _local_window(full: jnp.ndarray) -> jnp.ndarray:
        if model_shards == 1:
            return full
        return jax.lax.dynamic_slice_in_dim(
            full, _seg_start(), l_local, axis=1
        )

    def _round_core(w_loc: jnp.ndarray, rng: jax.Array, scenario: Scenario,
                    part: jnp.ndarray | None,
                    ratio_override: jnp.ndarray | None = None):
        """The shared round body: train -> (mask) -> encode -> exchange.

        ``w_loc`` is this shard's (N, L_local, K) window (== the full
        (N, S, K) rows when ``model_shards == 1``).  ``part`` is the
        realized (N,) participation mask (None = full, the exact
        pre-dynamic trace).  Returns ``(new_loc, trained_full, old_full,
        bias, loss_in)`` — the full-row trained / previous states feed the
        closed loop's signal refresh; ``loss_in`` (N,) is every client's
        train loss on ``old_full``, from `local_train`'s first epoch.
        Both `_advance` and `_advance_closed` run THIS code, so the open-
        and closed-loop paths share one round semantics.

        The codec (DESIGN.md §15) slots between training and delivery: it
        encodes the REPLICATED full rows (transmit mask + quantization
        noise are therefore identical across model shards — see
        `compression.stochastic_quantize`), the lossy protocols exchange
        the encoded segments under the (N, S) transmit mask, and the
        exchange-free branches plus every non-participating receiver keep
        the UNENCODED state (`dispatch_round_seg` w_raw; the explicit
        restore below) — nobody's parameters get quantized without an
        actual transmission.  ``ratio_override`` ((N,), optional) is the
        budget policy's per-client waterfill (`_advance_closed`).
        """
        w_full = _full_rows(w_loc)
        with jax.named_scope(_TRAIN_SCOPE):
            trained, loss_in = local_train(w_full, scenario.lr,
                                           scenario.local_epochs)
            if part is not None:
                trained = jnp.where(part[:, None, None] > 0, trained, w_full)
        with jax.named_scope(_EXCHANGE_SCOPE):
            tx_mask = None
            w_send = trained
            if scenario.codec_id is not None:
                ratio = (scenario.compress_ratio if ratio_override is None
                         else ratio_override)
                w_send, tx_full = compression.encode(
                    scenario.codec_id, trained, ratio,
                    jax.random.fold_in(rng, _CODEC_KEY_TAG),
                    n_real=s_total, dtype_bits=bits_per_value,
                )
                tx_mask = tx_full[:, :s_total]
            w_ex = _local_window(w_send)
            w_raw = (None if scenario.codec_id is None
                     else _local_window(trained))
            new_loc, _e, bias = protocols.dispatch_round_seg(
                w_ex, p, scenario.rho, scenario.link_eps, rng,
                scenario.protocol_id, scenario.mode_id, scenario.aggregator,
                n_mixes=aayg_mixes, participation=part,
                tx_mask=tx_mask, w_raw=w_raw,
                agg_impl=agg_impl, track_bias=track_bias,
                seg_total=None if model_shards == 1 else s_total,
                seg_start=_seg_start(),
            )
            if scenario.codec_id is not None and part is not None:
                # dispatch restores sampled-out receivers to its exchange
                # INPUT (the encoded w_ex); a client that sat the round out
                # must keep its unencoded state instead.
                new_loc = jnp.where(part[:, None, None] > 0, new_loc, w_raw)
        return new_loc, trained, w_full, bias, loss_in

    def _advance(w_loc: jnp.ndarray, rng: jax.Array, scenario: Scenario):
        """Train + exchange, NO metric evaluation: (w_loc, bias, loss_in)."""
        part = scenario.participation
        if part is not None:
            part = part[:n]
        new_loc, _trained, _old, bias, loss_in = _round_core(
            w_loc, rng, scenario, part
        )
        return new_loc, bias, loss_in

    def _advance_closed(w_loc: jnp.ndarray, rng: jax.Array,
                        scenario_t: Scenario,
                        signals: selection.SelectionSignals):
        """Closed-loop round (DESIGN.md §10): select -> train -> exchange.

        The participation mask is computed HERE, inside the scan, from the
        live ``signals`` (the policy decides who trains this round); the
        scenario's own ``participation`` schedule is the availability base.
        Returns (w_loc, new_signals, mask, bias, loss_in) — participants'
        trailing loss / update-norm signals are refreshed, everyone else
        keeps the score they last earned.  Signals reduce over the
        per-leaf VIEWS of the full rows, never the raw (possibly padded)
        rows, so their reduction grouping — and the selection trajectory —
        is independent of ``model_shards``.
        """
        base = scenario_t.participation
        base = (jnp.ones((n,), jnp.float32) if base is None
                else jnp.asarray(base, jnp.float32)[:n])
        mask = selection.select_clients(
            scenario_t.policy_id, base, signals, p,
            scenario_t.rho[:n, :n], scenario_t.select_frac,
        )
        ratio_override = None
        if scenario_t.codec_id is not None:
            # Joint selection + compression (DESIGN.md §15): under the
            # "budget" policy the slot-budget waterfill also decides HOW
            # MUCH each selected client compresses; other policies keep
            # the scenario's scalar ratio (broadcast, value-identical).
            ratio_override = selection.budget_ratio(
                scenario_t.policy_id, base, p, scenario_t.rho[:n, :n],
                scenario_t.select_frac, scenario_t.compress_ratio,
            )
        new_loc, trained, old_full, bias, loss_in = _round_core(
            w_loc, rng, scenario_t, mask, ratio_override
        )
        upd = selection.update_norms(_views_batch(trained),
                                     _views_batch(old_full))
        new_signals = selection.SelectionSignals(
            loss=jnp.where(mask > 0, train_loss(_full_rows(new_loc)),
                           signals.loss),
            upd_norm=jnp.where(mask > 0, upd, signals.upd_norm),
        )
        return new_loc, new_signals, mask, bias, loss_in

    # ------------------------------------------------------------------
    # The scan: ONE chunked structure for every scenario class.
    # state = {"w": (N, L_local, K) rows, "key": PRNGKey
    #          [, "sig": SelectionSignals]}; a chunk is `eval_every`
    # rounds ending in one metrics row.  `run_scenario` scans
    # `advance_chunk` over chunk indices; `checkpoint.run_resumable`
    # drives the SAME function from a host loop (bitwise resume).
    # ------------------------------------------------------------------
    n_chunks = n_rounds // eval_every

    def _scan_init(scenario: Scenario, key: jax.Array) -> dict:
        state = {"key": key, "w": _init_rows(key)}
        if scenario.policy_id is not None:
            state["sig"] = selection.init_signals(
                train_loss(_full_rows(state["w"]))
            )
        return state

    def _round(state: dict, t: jnp.ndarray, scenario: Scenario):
        key, k_round = jax.random.split(state["key"])
        sc_t = scenario.at_round(t)
        if scenario.policy_id is not None:
            w, sig, mask, bias, loss_in = _advance_closed(
                state["w"], k_round, sc_t, state["sig"]
            )
            return ({"key": key, "w": w, "sig": sig},
                    {"bias": bias, "selected": mask, "loss_in": loss_in})
        w, bias, loss_in = _advance(state["w"], k_round, sc_t)
        return {"key": key, "w": w}, {"bias": bias, "loss_in": loss_in}

    def advance_chunk(state: dict, scenario: Scenario, c: jnp.ndarray):
        """Advance chunk ``c`` (= rounds c*k .. (c+1)*k - 1, k=eval_every).

        Returns (state, metrics-row): per-round ``bias`` (and ``selected``
        for closed-loop scenarios), chunk-end ``acc``, ``loss_in`` and
        ``loss_end``.  ``loss_in`` is the train loss on the state the
        chunk ENTERED with, from its first local epoch (`local_train`):
        chunk c's ``loss`` is taken from chunk c+1's ``loss_in``, so only
        the last chunk computes its own, as ``loss_end`` (a `lax.cond` on
        the unbatched chunk index; zeros on every other chunk).
        `chunk_metrics` builds ``loss`` from the stacked rows.
        ``eval_every == 1`` advances the single round inline — no inner
        scan — so the per-round program is exactly the unchunked one.
        """
        scenario = scenario.prepare()
        if eval_every == 1:
            state, extras = _round(state, c, scenario)
        else:
            state, extras = jax.lax.scan(
                lambda s, t: _round(s, t, scenario),
                state, c * eval_every + jnp.arange(eval_every),
            )
            extras["loss_in"] = extras["loss_in"][0]
        full = _full_rows(state["w"])
        with jax.named_scope(_EVAL_SCOPE):
            loss_end = jax.lax.cond(
                c == n_chunks - 1, train_loss,
                lambda rows: jnp.zeros((n,), extras["loss_in"].dtype), full,
            )
            metrics = {"acc": evaluate(full), "loss_end": loss_end,
                       **extras}
        return state, metrics

    def init_scan(scenario: Scenario) -> dict:
        """The segment-native scan state at round 0 (pre-training)."""
        scenario = scenario.prepare()
        return _scan_init(scenario, jax.random.PRNGKey(scenario.seed))

    def run_scenario(scenario: Scenario) -> dict:
        scenario = scenario.prepare()
        state = _scan_init(scenario, jax.random.PRNGKey(scenario.seed))
        _, rows = jax.lax.scan(
            lambda s, c: advance_chunk(s, scenario, c),
            state, jnp.arange(n_chunks),
        )
        return chunk_metrics(rows)

    return SimPrograms(
        run_scenario=_float32_matmuls(run_scenario),
        n_clients=n,
        n_rounds=n_rounds,
        init_scan=_float32_matmuls(init_scan),
        advance_chunk=_float32_matmuls(advance_chunk),
        n_chunks=n_chunks,
        eval_every=eval_every,
        model_shards=model_shards,
        model_axis=model_axis,
        n_segments=s_total,
        local_segments=l_local,
        seg_len=seg_len,
        bits_per_value=bits_per_value,
    )


def chunk_metrics(rows: dict) -> dict:
    """Stacked `advance_chunk` rows -> the `run_scenario` metrics dict.

    Chunk c's ``loss`` is chunk c+1's ``loss_in`` (the next chunk's first
    local-epoch forward, on the same parameters and shard), and the last
    chunk's is its own ``loss_end``.  Per-round ``bias`` / ``selected``
    flatten across chunks to ``(n_rounds, ...)``.  Works on traced arrays
    (`run_scenario`) and on host numpy rows
    (`checkpoint.run_resumable`) alike.
    """
    xp = np if isinstance(rows["loss_in"], np.ndarray) else jnp
    out = {k: v for k, v in rows.items() if k not in ("loss_in", "loss_end")}
    out["loss"] = xp.concatenate([rows["loss_in"][1:], rows["loss_end"][-1:]])
    out["bias"] = rows["bias"].reshape(-1)
    if "selected" in rows:
        sel = rows["selected"]
        out["selected"] = sel.reshape(-1, sel.shape[-1])
    return out


def _float32_matmuls(fn: Callable) -> Callable:
    """``fn`` traced with float32 matmuls and convolutions on every backend.

    TPU's default precision multiplies float32 operands in one bf16 pass.
    The simulated models end their early rounds with near-flat logits, so
    at that precision test accuracy depends on how XLA batched the program:
    a one-scenario program and a two-scenario program of the same scenario
    measured 0.28 and 0.55 final accuracy on a v5e (FashionMNIST CNN, Fig. 9
    network), with train losses equal to 1e-4.  A scenario's result must not
    depend on what it was batched with (served vs `run_grid`, sharded vs
    not), so the programs keep the float32 semantics of the CPU reference.
    """
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)

    return wrapped


def donate_kwargs() -> dict:
    """`jax.jit` kwargs donating the scenario argument (argnum 0).

    The dispatched scenario batch — and with it the (G, ...) link/rho
    stacks feeding the (G, N, L, K) round-loop state — is consumed by
    exactly one dispatch (grid leaves live host-side and are re-transferred
    per call), so its device buffers can be donated to the outputs instead
    of double-buffering.  CPU does not implement donation (XLA warns every
    dispatch), so this resolves to no-op kwargs there.
    """
    return {} if jax.default_backend() == "cpu" else {"donate_argnums": 0}
