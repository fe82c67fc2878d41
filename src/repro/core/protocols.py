"""One-round model-exchange protocols (paper Sec. III + benchmarks Sec. V).

All protocols consume a *client-stacked* parameter pytree (every leaf has a
leading N axis), the aggregation weights p, link/E2E quality matrices, and a
PRNG key, and return the new client-stacked pytree after local aggregation.

  * ``ra_round``   — Route-and-Aggregate D-FL (the paper's proposal):
                     models are delivered along min-E2E-PER routes; each
                     segment survives with prob rho_{m,n}; receivers run
                     adaptive normalization (or substitution baseline).
  * ``aayg_round`` — Aggregate-as-You-Go gossip [12]-[14]: J one-hop
                     broadcast+aggregate iterations; a segment of a direct
                     neighbor survives with the one-hop packet success rate.
  * ``cfl_round``  — Centralized FL via routes: lossy uplink to a chosen
                     aggregator, lossy downlink broadcast back; erroneous
                     downlink segments are replaced by the receiver's own.

Two layers:

  * ``*_round_seg`` functions operate on segment tensors (N, L, K) with
    TRACED protocol parameters (mode_id, aggregator) — the substrate of the
    batched scenario engine (`repro.fl.scenarios`), where one compiled
    program serves every grid point.  ``dispatch_round_seg`` selects the
    protocol itself by a traced ``protocol_id`` (`PROTOCOL_IDS`).
  * the original pytree-level wrappers (``ra_round`` et al.) keep the
    static-string API for interactive use and tests.

Everything is jit-compatible; `seg_len` and `n_mixes` are static.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import aggregation, errors

Pytree = Any

# Traced protocol selector values (order = lax.switch branch order).
PROTOCOL_IDS = {"ra": 0, "aayg": 1, "cfl": 2, "ideal_cfl": 3, "none": 4}
MODE_IDS = aggregation.MODE_IDS


def _to_segments(stacked: Pytree, seg_len: int):
    mat, spec = errors.stack_to_matrix(stacked)
    m_params = mat.shape[1]
    return errors.segment(mat, seg_len), spec, m_params


def _from_segments(seg: jnp.ndarray, spec, m_params: int) -> Pytree:
    return errors.matrix_to_stack(errors.unsegment(seg, m_params), spec)


# ---------------------------------------------------------------------------
# Segment-level protocol rounds (traced mode / aggregator).
# ---------------------------------------------------------------------------
def ra_round_seg(
    w_seg: jnp.ndarray,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    key: jax.Array,
    mode_id: jnp.ndarray,
    participation: jnp.ndarray | None = None,
    *,
    tx_mask: jnp.ndarray | None = None,
    agg_impl: str | None = None,
    seg_total: int | None = None,
    seg_start: jnp.ndarray | int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """R&A local aggregation on segments; returns (out, e) with the sampled
    success mask (packed bool_) exposed for bias/Λ diagnostics.

    With a ``participation`` mask (N,), sampled-out senders are removed
    from ``e`` (adaptive normalization renormalizes over the sampled
    senders automatically) and sampled-out receivers keep their own
    segments untouched.  ``participation=None`` keeps the exact static
    trace.  ``agg_impl`` selects the aggregation substrate (STATIC — see
    `aggregation.apply_mode`).

    ``tx_mask`` is the codec layer's optional (N, S) per-segment TRANSMIT
    mask at the FULL segment width (`repro.core.compression`): pruned
    segments were never sent, so they compose into ``e`` exactly like
    sampled-out senders (`aggregation.apply_transmit_mask`) — and the
    returned ``e`` (hence the bias diagnostic) reflects the realized,
    transmit-masked coefficients.  The aggregation pass receives the mask
    separately so the Pallas substrate can run its sparsity-aware kernel
    variant.  ``tx_mask=None`` keeps the exact pre-codec trace.

    Model-axis sharding (DESIGN.md §13): with ``seg_total=S`` (STATIC, the
    GLOBAL segment count) the success mask is sampled at the FULL
    (N, N, S) shape from the shared ``key`` and then sliced to this
    shard's ``[seg_start, seg_start + L_local)`` window — every shard
    draws the same uniforms, so the per-global-segment masks are bitwise
    identical to the unsharded run's (and with them the aggregated
    model has the unsharded semantics).  The returned ``e`` is the FULL
    (participation-masked) mask, so the bias diagnostic reduces over
    every global segment on every shard (replicated, equal to the
    unsharded value).  ``seg_total=None`` (the default) keeps the exact
    single-shard trace.
    """
    n, l = w_seg.shape[0], w_seg.shape[1]
    e = errors.sample_success(key, rho, l if seg_total is None else seg_total,
                              n_clients=n)
    if participation is not None:
        e = aggregation.mask_senders(e, participation)
    e_loc = e if seg_total is None else errors.local_slice(e, l, seg_start)
    tx_loc = None
    if tx_mask is not None:
        e = aggregation.apply_transmit_mask(e, tx_mask)
        tx_loc = (tx_mask if seg_total is None
                  else errors.local_slice(tx_mask, l, seg_start))
    out = aggregation.apply_mode(mode_id, w_seg, p, e_loc, tx=tx_loc,
                                 impl=agg_impl)
    if participation is not None:
        out = aggregation.keep_nonparticipants(participation, out, w_seg)
    return out, e


def aayg_round_seg(
    w_seg: jnp.ndarray,
    p: jnp.ndarray,
    link_eps: jnp.ndarray,
    key: jax.Array,
    mode_id: jnp.ndarray,
    *,
    n_mixes: int = 1,
    participation: jnp.ndarray | None = None,
    tx_mask: jnp.ndarray | None = None,
    agg_impl: str | None = None,
    seg_total: int | None = None,
    seg_start: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """Aggregate-as-You-Go gossip: J = n_mixes one-hop mix iterations.

    ``link_eps`` is the (V, V) one-hop packet success matrix (0 where not
    adjacent); only the leading N-client block participates (AaYG cannot
    exploit routing-only relay nodes — Fig. 9 note).  A ``participation``
    mask silences sampled-out clients for the WHOLE round: they neither
    broadcast nor update in any of the J mixes.  ``seg_total``/``seg_start``
    select a model-shard window of full-segment-count mask draws (same
    contract as `ra_round_seg`).

    The codec's ``tx_mask`` ((N, S) full width) is applied to EVERY mix:
    the codec runs once per round, before the exchange, so a pruned
    segment stays off the air for all J broadcasts (intermediate mix
    results are not re-encoded — matching the gossip-with-compression
    baseline of arXiv 2405.12894, which compresses the local state once
    per communication round).
    """
    n, l, _ = w_seg.shape
    eps = link_eps[:n, :n]

    def mix(w, key):
        u = jax.random.uniform(
            key, (n, n, l if seg_total is None else seg_total)
        )
        e = u < eps[:, :, None]                     # packed bool_ mask
        if participation is not None:
            e = e & (participation[:n, None, None] > 0)
        if tx_mask is not None:
            e = e & (tx_mask[:n, None, :] > 0)
        e = e | jnp.eye(n, dtype=jnp.bool_)[:, :, None]  # own model present
        if seg_total is not None:
            e = errors.local_slice(e, l, seg_start)
        out = aggregation.apply_mode(mode_id, w, p, e, impl=agg_impl)
        if participation is not None:
            out = aggregation.keep_nonparticipants(participation[:n], out, w)
        return out

    keys = jax.random.split(key, n_mixes)
    return jax.lax.fori_loop(0, n_mixes, lambda j, w: mix(w, keys[j]), w_seg)


def cfl_round_seg(
    w_seg: jnp.ndarray,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    key: jax.Array,
    mode_id: jnp.ndarray,
    aggregator: jnp.ndarray,
    participation: jnp.ndarray | None = None,
    *,
    tx_mask: jnp.ndarray | None = None,
    seg_total: int | None = None,
    seg_start: jnp.ndarray | int = 0,
) -> jnp.ndarray:
    """C-FL benchmark: star aggregation at `aggregator` via min-PER routes.

    Uplink: segment l of client m reaches the aggregator w.p. rho[m, a].
    Downlink: the global segment reaches client n w.p. rho[a, n]; on failure
    the client keeps its own local segment (paper's C-FL description).
    With a ``participation`` mask, sampled-out clients neither upload nor
    receive the downlink (they keep their own segments).  The star center
    is infrastructure: C-FL cannot run a round without its aggregator, so
    the aggregator's own mask entry is IGNORED (it always participates) —
    this also keeps every per-segment normalization denominator >= p_agg,
    so no receiver can be handed a zero model when all sampled uplinks
    fail.

    The codec's ``tx_mask`` ((N, S) full width) prunes the uplink — a
    client never uploads a pruned segment — composed BEFORE the
    aggregator's own-row restore (the star center holds its own model
    locally; no transmission is involved).  On the downlink the aggregator
    is the sender, so ITS row prunes the broadcast; receivers fall back to
    their own segments exactly like a downlink erasure.
    """
    n, l, k = w_seg.shape
    l_draw = l if seg_total is None else seg_total
    kup, kdn = jax.random.split(key)
    aggregator = jnp.asarray(aggregator, jnp.int32)
    if participation is not None:
        participation = jnp.maximum(
            participation[:n], jax.nn.one_hot(aggregator, n, dtype=jnp.float32)
        )
    tx_f = None if tx_mask is None else (tx_mask[:n] > 0).astype(jnp.float32)

    # Uplink success mask for each sender/segment, destination = aggregator.
    rho_up = jnp.take(rho[:n], aggregator, axis=1)            # (N,)
    e_up = (jax.random.uniform(kup, (n, l_draw)) < rho_up[:, None]).astype(
        jnp.float32
    )
    if tx_f is not None:
        e_up = e_up * tx_f
    e_up = e_up.at[aggregator].set(1.0)
    if participation is not None:
        e_up = e_up * participation[:, None]
    if seg_total is not None:
        e_up = errors.local_slice(e_up, l, seg_start)
    w_own = jnp.take(w_seg, aggregator, axis=0)               # (L, K)

    def _normalized(_):
        wts = p[:, None] * e_up                               # (N, L)
        denom = jnp.maximum(jnp.sum(wts, axis=0), 1e-12)      # (L,)
        return jnp.einsum("ml,mlk->lk", wts, w_seg) / denom[:, None]

    def _substitution(_):  # aggregator substitutes its own segments
        recv = jnp.einsum("ml,mlk->lk", p[:, None] * e_up, w_seg)
        miss = jnp.einsum("ml->l", p[:, None] * (1.0 - e_up))
        return recv + miss[:, None] * w_own

    g = jax.lax.cond(mode_id == 0, _normalized, _substitution, None)

    # Downlink: erroneous global segments replaced by the receiver's own.
    rho_dn = jnp.take(rho[:, :n], aggregator, axis=0)         # (N,)
    e_dn = (jax.random.uniform(kdn, (n, l_draw)) < rho_dn[:, None]).astype(
        jnp.float32
    )
    if tx_f is not None:
        e_dn = e_dn * jnp.take(tx_f, aggregator, axis=0)[None, :]
    e_dn = e_dn.at[aggregator].set(1.0)
    if participation is not None:
        e_dn = e_dn * participation[:, None]
    if seg_total is not None:
        e_dn = errors.local_slice(e_dn, l, seg_start)
    return e_dn[:, :, None] * g[None] + (1.0 - e_dn)[:, :, None] * w_seg


def ideal_round_seg(w_seg: jnp.ndarray, p: jnp.ndarray,
                    participation: jnp.ndarray | None = None) -> jnp.ndarray:
    """Error-free C-FL (the paper's ideal reference in Fig. 9).

    With a ``participation`` mask the global average renormalizes over the
    sampled clients and only they receive it (`aggregation.ideal`)."""
    return aggregation.ideal(w_seg, p, participation=participation)


def dispatch_round_seg(
    w_seg: jnp.ndarray,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    link_eps: jnp.ndarray,
    key: jax.Array,
    protocol_id: jnp.ndarray,
    mode_id: jnp.ndarray,
    aggregator: jnp.ndarray,
    *,
    n_mixes: int = 1,
    participation: jnp.ndarray | None = None,
    tx_mask: jnp.ndarray | None = None,
    w_raw: jnp.ndarray | None = None,
    agg_impl: str | None = None,
    track_bias: bool = True,
    seg_total: int | None = None,
    seg_start: jnp.ndarray | int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One exchange round with a fully traced (protocol, mode, aggregator).

    Returns (new_w_seg, e, bias) where ``e`` is the sampled (N, N, L) success
    mask for R&A (packed bool_; all-ones for other protocols) and ``bias``
    is the mean ||Lambda_l||_F^2 diagnostic (NaN where undefined, 0 for
    ideal C-FL) — matching the scalar simulator's per-protocol bookkeeping.

    ``seg_total``/``seg_start`` (DESIGN.md §13) run the exchange on a
    model-axis shard: ``w_seg`` is the LOCAL (N, L_local, K) window of a
    global (N, S=seg_total, K) segment tensor starting at traced segment
    ``seg_start``.  Every success indicator is sampled at the FULL segment
    count from the shared key and sliced to the local window, so sharded
    and unsharded runs draw bitwise-identical masks per global segment;
    ``e`` (and with it the bias diagnostic) stays FULL-width (N, N, S) —
    replicated across shards.  ``seg_total=None`` keeps the exact
    single-shard trace.

    ``participation`` (optional (N,) client sampling mask) threads through
    every branch: sampled-out clients contribute to no aggregation and keep
    their segments untouched (for R&A the bias diagnostic is computed from
    the participation-masked ``e`` — the realized coefficients).  One
    carve-out: C-FL's star center always participates (see
    `cfl_round_seg`).  None (the default) keeps the exact static trace.

    The codec layer (`repro.core.compression`) threads in through two
    optional arguments: ``tx_mask`` — the (N, S) full-width per-segment
    transmit mask, composed into every LOSSY protocol's channel draw (R&A
    and AaYG success masks, C-FL up/downlink) — and ``w_raw`` — the
    UNENCODED segments, used by the exchange-free branches (ideal C-FL and
    "none"): a codec transforms what goes over the air, and those branches
    put nothing on the air, so they must not see encoded values.  Both are
    STATIC presence choices; None keeps the exact pre-codec trace.

    Two STATIC compute knobs (they change the compiled program, not its
    semantics): ``agg_impl`` selects the aggregation substrate
    (`aggregation.apply_mode`), and ``track_bias=False`` skips the R&A bias
    diagnostic entirely (bias is NaN; the two (N, L) mask reductions of
    `aggregation.bias_sq_norm_fused` drop out of the hot loop).
    """
    n, l, _ = w_seg.shape
    e_ones = jnp.ones((n, n, l if seg_total is None else seg_total),
                      jnp.bool_)
    nan = jnp.asarray(jnp.nan, jnp.float32)
    w_keep = w_seg if w_raw is None else w_raw

    def b_ra(_):
        out, e = ra_round_seg(w_seg, p, rho, key, mode_id, participation,
                              tx_mask=tx_mask, agg_impl=agg_impl,
                              seg_total=seg_total, seg_start=seg_start)
        bias = (jnp.mean(aggregation.bias_sq_norm_fused(p, e))
                if track_bias else nan)
        return out, e, bias

    def b_aayg(_):
        out = aayg_round_seg(w_seg, p, link_eps, key, mode_id, n_mixes=n_mixes,
                             participation=participation, tx_mask=tx_mask,
                             agg_impl=agg_impl,
                             seg_total=seg_total, seg_start=seg_start)
        return out, e_ones, nan

    def b_cfl(_):
        out = cfl_round_seg(w_seg, p, rho, key, mode_id, aggregator,
                            participation, tx_mask=tx_mask,
                            seg_total=seg_total, seg_start=seg_start)
        return out, e_ones, nan

    def b_ideal(_):
        out = ideal_round_seg(w_keep, p, participation)
        return out, e_ones, jnp.asarray(0.0, jnp.float32)

    def b_none(_):
        # "none" never exchanges; non-participants are untouched trivially.
        return w_keep, e_ones, nan

    return jax.lax.switch(
        protocol_id, (b_ra, b_aayg, b_cfl, b_ideal, b_none), None
    )


# ---------------------------------------------------------------------------
# Pytree-level wrappers (static string API — tests / interactive use).
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("seg_len", "mode"))
def ra_round(
    stacked: Pytree,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    key: jax.Array,
    *,
    seg_len: int,
    mode: str = "ra_normalized",
) -> tuple[Pytree, jnp.ndarray]:
    """R&A D-FL local aggregation round.

    Returns (new_stacked, e) where e is the (N, N, L) success mask actually
    sampled (exposed for bias/Λ diagnostics).
    """
    w_seg, spec, m_params = _to_segments(stacked, seg_len)
    out, e = ra_round_seg(w_seg, p, rho, key, MODE_IDS[mode])
    return _from_segments(out, spec, m_params), e


@partial(jax.jit, static_argnames=("seg_len", "mode", "n_mixes"))
def aayg_round(
    stacked: Pytree,
    p: jnp.ndarray,
    link_eps: jnp.ndarray,
    key: jax.Array,
    *,
    seg_len: int,
    mode: str = "ra_normalized",
    n_mixes: int = 1,
) -> Pytree:
    """Aggregate-as-You-Go gossip round (see aayg_round_seg)."""
    w_seg, spec, m_params = _to_segments(stacked, seg_len)
    out = aayg_round_seg(w_seg, p, link_eps, key, MODE_IDS[mode],
                         n_mixes=n_mixes)
    return _from_segments(out, spec, m_params)


@partial(jax.jit, static_argnames=("seg_len", "mode", "aggregator"))
def cfl_round(
    stacked: Pytree,
    p: jnp.ndarray,
    rho: jnp.ndarray,
    key: jax.Array,
    *,
    seg_len: int,
    mode: str = "ra_normalized",
    aggregator: int = 6,
) -> Pytree:
    """C-FL benchmark round (see cfl_round_seg)."""
    w_seg, spec, m_params = _to_segments(stacked, seg_len)
    out = cfl_round_seg(w_seg, p, rho, key, MODE_IDS[mode], aggregator)
    return _from_segments(out, spec, m_params)


@partial(jax.jit, static_argnames=("seg_len",))
def ideal_cfl_round(stacked: Pytree, p: jnp.ndarray, *, seg_len: int) -> Pytree:
    """Error-free C-FL (the paper's ideal reference in Fig. 9)."""
    w_seg, spec, m_params = _to_segments(stacked, seg_len)
    return _from_segments(ideal_round_seg(w_seg, p), spec, m_params)
