"""Closed-loop client-selection policies (DESIGN.md §10).

The paper's participation model — and PR 3's `participation` scenario axis —
is OPEN-loop: who trains each round is decided before the run (a precomputed
`(T, N)` mask).  Tram-FL (arXiv:2308.04762) routes training by data utility
and joint routing/pruning D-FL (arXiv:2405.12894) co-designs participation
with bandwidth-constrained routes; both argue selection should react to the
*live* state of training and of the network.  This module makes that an
in-loop policy: every round, the participation mask is computed INSIDE the
round scan from per-client signals carried in the scan state.

Policies (``POLICY_IDS``, dispatched by a traced ``lax.switch`` exactly like
protocol ids — a grid sweeping policies stays ONE vmapped/sharded dispatch):

  * ``uniform``    — the neutral policy: return the scenario's precomputed
                     participation mask unchanged (all-ones when absent).
                     The open-loop path's masks exactly, and its
                     trajectories within float32 round-off (DESIGN.md §16).
  * ``loss``       — loss-proportional importance: the k clients with the
                     largest trailing train loss participate (they need
                     training the most).
  * ``grad_norm``  — gradient-norm importance: the k clients whose last
                     local update moved the furthest (largest parameter-
                     update norm) participate.
  * ``bandwidth``  — bandwidth-aware admission: the k sources whose
                     homologous route-sets the paper's Section-IV rule
                     admits first — score ``(p_m^2 + p_m) * sum_n (1 -
                     rho_{m,n})`` (`routing.admission_scores`) — get to
                     send.  Masking participation of the other sources is
                     exactly `routing.admitted_rho_mask` at the
                     success-mask level (`aggregation.mask_senders` zeroes
                     the same sender rows).
  * ``budget``     — JOINT selection + compression under a per-round slot
                     budget (DESIGN.md §15): ``select_frac * N`` full-model
                     transmission equivalents are waterfilled down the
                     Section-IV admission ranking (`budget_allocation`) —
                     each client gets a per-client compress ratio in
                     [0, 1], the budget decides both WHO participates
                     (allocation > 0) and HOW MUCH each participant
                     compresses (`budget_ratio` feeds the scenario codec).

Every policy composes with the scenario's open-loop mask: clients the
precomputed schedule rules out are unavailable (score ``-inf``) and never
selected, so closed-loop selection refines — never overrides — the
schedule.  ``k = clip(ceil(select_frac * N), 1, N)`` with a TRACED
``select_frac``, so fractions are a sweepable grid axis too.

Signals (`SelectionSignals`) are carried through the round scan by
`repro.fl.simulator.run_scenario`: ``loss`` is the trailing per-client
train loss (initialized to the round-0 loss of the common init, refreshed
for participants after each exchange) and ``upd_norm`` the trailing local
parameter-update norm (initialized to +inf so never-trained clients keep
priority until they participate once — see `init_signals`).
Non-participants keep their carried signals, so a client sampled out today
competes with the score it last earned — selection cannot starve on a mask
it itself produced.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import routing

# Traced policy selector values (order = lax.switch branch order).
POLICY_IDS = {"uniform": 0, "loss": 1, "grad_norm": 2, "bandwidth": 3,
              "budget": 4}


class SelectionSignals(NamedTuple):
    """Live per-client signals carried in the round-scan state.

    ``loss`` — trailing train loss, (N,) float32.
    ``upd_norm`` — trailing local parameter-update norm, (N,) float32.
    """

    loss: jnp.ndarray
    upd_norm: jnp.ndarray


def init_signals(loss0: jnp.ndarray) -> SelectionSignals:
    """Round-0 signals: the common init's per-client loss, OPTIMISTIC
    (+inf) update norms.

    The update norm of a client that has never trained is unknown, and
    initializing it to 0 would starve it forever under ``grad_norm`` (it
    can only earn a real score by being selected).  +inf gives every
    untrained client priority until it has participated once — among
    all-inf ties the stable sort picks lowest indices first.  The trailing
    ``loss`` signal needs no such trick: a non-participant's parameters
    are untouched, so its carried loss stays exact, not stale.
    """
    loss0 = jnp.asarray(loss0, jnp.float32)
    return SelectionSignals(loss=loss0,
                            upd_norm=jnp.full_like(loss0, jnp.inf))


def select_count(select_frac: jnp.ndarray, n: int) -> jnp.ndarray:
    """Traced participant count k = clip(ceil(frac * N), 1, N).

    The product is nudged down by an epsilon before the ceil: float32
    cannot represent fractions like 0.3 exactly (0.3 * 50 evaluates to
    15.000001, and a raw ceil would admit 16 clients instead of the
    documented 15).  The epsilon is far below 1/N for any realistic N, so
    exact products are unaffected.
    """
    frac = jnp.asarray(select_frac, jnp.float32)
    k = jnp.ceil(frac * n - 1e-6).astype(jnp.int32)
    return jnp.clip(k, 1, n)


def topk_mask(scores: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """(N,) float32 mask of the k highest-scoring clients.

    ``k`` is TRACED (``lax.top_k`` needs a static k), so the mask is built
    from descending ranks: stable argsort → rank < k.  Ties break toward
    the LOWER client index, deterministically; ``-inf`` scores (unavailable
    clients) rank last and are only reached once every finite score is in.
    """
    n = scores.shape[0]
    order = jnp.argsort(-scores)                     # descending, stable
    ranks = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    return (ranks < k).astype(jnp.float32)


def budget_allocation(
    base_mask: jnp.ndarray,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    select_frac: jnp.ndarray,
) -> jnp.ndarray:
    """Per-client transmit budget waterfill (the ``budget`` policy's core).

    The round's communication budget is ``B = select_frac * N`` full-model
    transmission equivalents (Section-IV slot units: one unit = one
    client's uncompressed model through its homologous route set).  The
    budget is waterfilled down the Section-IV admission ranking
    (`routing.admission_scores`, availability-gated): the client ranked r
    receives ``clip(B - r, 0, 1)`` — full models while budget remains, one
    fractional allocation at the boundary, nothing after.  The result is a
    per-client compress ratio in [0, 1] with ``sum <= B`` by construction:
    a single quantity decides both WHO participates (allocation > 0) and
    HOW MUCH each participant compresses.
    """
    n = base_mask.shape[0]
    budget = jnp.asarray(select_frac, jnp.float32) * n
    avail = base_mask > 0
    scores = jnp.where(avail, routing.admission_scores(p, rho[:n, :n]),
                       -jnp.inf)
    order = jnp.argsort(-scores)                     # descending, stable
    ranks = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    alloc = jnp.clip(budget - ranks.astype(jnp.float32), 0.0, 1.0)
    # Leftover budget must never reach unavailable (-inf-ranked) clients.
    return alloc * avail.astype(jnp.float32)


def budget_ratio(
    policy_id: jnp.ndarray,
    base_mask: jnp.ndarray,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    select_frac: jnp.ndarray,
    base_ratio: jnp.ndarray,
) -> jnp.ndarray:
    """The (N,) per-client compress ratio a codec scenario realizes.

    Under the ``budget`` policy: the waterfill allocation scaled by the
    scenario's own ``compress_ratio`` (so the grid axis still modulates
    intensity).  Every other policy broadcasts the scalar ratio unchanged
    — value-identical to the scalar the open loop would have used.
    Zero-allocation clients get ratio 0; they are exactly the clients the
    budget mask rules out, so their codec output never transmits (and
    `compression.keep_count` / `quant_bits` clip at 1 regardless).
    """
    n = base_mask.shape[0]
    scalar = jnp.broadcast_to(
        jnp.asarray(base_ratio, jnp.float32).reshape(()), (n,)
    )
    alloc = budget_allocation(base_mask, p, rho, select_frac)
    return jnp.where(policy_id == POLICY_IDS["budget"], alloc * scalar,
                     scalar)


def select_clients(
    policy_id: jnp.ndarray,
    base_mask: jnp.ndarray,
    signals: SelectionSignals,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    select_frac: jnp.ndarray,
) -> jnp.ndarray:
    """The per-round participation mask under a TRACED policy.

    Args:
      policy_id: () int32 — `POLICY_IDS` branch selector.
      base_mask: (N,) float32 — the scenario's open-loop participation mask
        for this round (all-ones when the scenario has none): clients it
        rules out are unavailable to every policy.
      signals: trailing per-client signals (see `SelectionSignals`).
      p: (N,) aggregation weights (bandwidth policy).
      rho: (N, N) client-block E2E success matrix of THIS round's topology
        (bandwidth policy) — under a mobility/churn schedule the admission
        scores follow the network round by round.
      select_frac: () float32 — participant fraction; k = ceil(frac * N),
        clipped to [1, N].  Ignored by ``uniform``.

    Returns:
      (N,) float32 mask in {0, 1}.
    """
    n = base_mask.shape[0]
    k = select_count(select_frac, n)
    avail = base_mask > 0

    def gated(scores):
        return jnp.where(avail, scores, -jnp.inf)

    def b_uniform(_):
        return base_mask

    def b_loss(_):
        return topk_mask(gated(signals.loss), k) * base_mask

    def b_grad_norm(_):
        return topk_mask(gated(signals.upd_norm), k) * base_mask

    def b_bandwidth(_):
        scores = routing.admission_scores(p, rho[:n, :n])
        return topk_mask(gated(scores), k) * base_mask

    def b_budget(_):
        alloc = budget_allocation(base_mask, p, rho, select_frac)
        return (alloc > 0).astype(jnp.float32)

    return jax.lax.switch(
        policy_id, (b_uniform, b_loss, b_grad_norm, b_bandwidth, b_budget),
        None,
    )


def update_norms(new_stacked, old_stacked) -> jnp.ndarray:
    """Per-client L2 norm of the parameter update between two stacked pytrees.

    Both pytrees carry a leading N client axis on every leaf; the norm
    reduces over everything else.  This is the ``grad_norm`` policy's
    signal: for I local full-batch GD epochs it is ``lr * ||sum_i grad_i||``
    up to curvature, i.e. a gradient-norm importance measure that costs one
    subtraction (no extra gradient evaluation).
    """
    sq = jax.tree.map(
        lambda a, b: jnp.sum(
            jnp.square(a - b), axis=tuple(range(1, jnp.ndim(a)))
        ),
        new_stacked, old_stacked,
    )
    return jnp.sqrt(sum(jax.tree.leaves(sq))).astype(jnp.float32)
