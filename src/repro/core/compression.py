"""Traced exchange codecs: what goes over the air, segment by segment.

The paper ships every model segment as full float32 packets; its sequel
("Joint Routing and Model Pruning for D-FL in Bandwidth-Constrained
Multi-Hop Wireless Networks", arXiv 2603.15188) makes WHAT is transmitted a
design axis alongside WHERE it is routed.  This module puts a codec between
local training and delivery:

  * ``none``  — the neutral codec: every segment ships untouched.  The
                semantics of the pre-codec exchange path, equal to it
                within float32 round-off (DESIGN.md §16).
  * ``topk``  — top-k segment sparsification: each client transmits only
                its ``ceil(ratio * S)`` largest-L2-norm segments.  Pruned
                segments are NEVER SENT — they are neither an error nor a
                delivery, so the per-segment transmit mask composes with the
                channel's success mask exactly like `aggregation.mask_senders`
                composes participation (see `aggregation.apply_transmit_mask`).
                Receivers fall back per aggregation mode: adaptive
                normalization renormalizes over the transmitted AND delivered
                senders; substitution folds the pruned mass onto the
                receiver's own block.
  * ``quant`` — stochastic uniform quantization: every segment ships, but
                values are rounded to ``ceil(ratio * dtype_bits)``-bit
                levels on a per-segment max-abs scale, with stochastic
                (unbiased) rounding: E[decode(encode(w))] = w, and the
                round-trip error is bounded by one quantization step
                (scale / levels) per value.

Dispatch mirrors protocols/modes/policies: ``CODEC_IDS`` are stable array
values selected by a traced ``lax.switch``, and ``compress_ratio`` is a
traced scalar — so a ratio x protocol x topology sweep stays ONE
`run_grid` dispatch.  ``compress_ratio`` may also be a per-client (N,)
vector (the joint selection+compression budget policy of
`core.selection.budget_allocation` produces one).

Model-axis sharding (DESIGN.md §13): codecs run on the REPLICATED full
segment rows, before the per-shard window slice — the transmit mask is a
deterministic function of the rows, and the quantization noise is drawn at
the canonical ``n_real`` segment width from the shared key — so any
``model_shards`` produces bitwise identical codec output per global
segment (the same full-width-draw contract as `errors.sample_success`).

Packet accounting: `bits_fraction` / `host_factor` give the realized
fraction of the uncompressed payload each codec ships — `core.overhead`
scales Table-III traffic/slot numbers with it, and
`errors.packet_len_bits(seg_len, bits_per_value)` prices the quantized
packets themselves.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# Traced codec selector values (order = lax.switch branch order).
CODEC_IDS = {"none": 0, "topk": 1, "quant": 2}

# The same epsilon nudge as `selection.select_count`: float32 cannot
# represent ratios like 0.3 exactly, and a raw ceil would round the
# artifact up (keep 16 of 50 segments instead of the documented 15).
_CEIL_EPS = 1e-6


def keep_count(compress_ratio: jnp.ndarray, n_real: int) -> jnp.ndarray:
    """Traced kept-segment count k = clip(ceil(ratio * S), 1, S).

    ``compress_ratio`` may be a scalar or a per-client (N,) vector; the
    result has the same shape.  ratio=1 keeps every real segment exactly.
    """
    r = jnp.asarray(compress_ratio, jnp.float32)
    k = jnp.ceil(r * n_real - _CEIL_EPS).astype(jnp.int32)
    return jnp.clip(k, 1, n_real)


def quant_bits(compress_ratio: jnp.ndarray,
               dtype_bits: int = 32) -> jnp.ndarray:
    """Traced per-value bit width b = clip(ceil(ratio * dtype_bits), 1, B)."""
    r = jnp.asarray(compress_ratio, jnp.float32)
    b = jnp.ceil(r * dtype_bits - _CEIL_EPS).astype(jnp.int32)
    return jnp.clip(b, 1, dtype_bits)


def topk_transmit_mask(w_rows: jnp.ndarray, compress_ratio: jnp.ndarray,
                       *, n_real: int | None = None) -> jnp.ndarray:
    """(N, S) bool transmit mask: each client's top-k segments by L2 norm.

    ``w_rows`` is the client-stacked (N, S, K) segment tensor (possibly
    shard-padded past ``n_real`` real segments with zero rows — zero-norm
    padding ranks last, after every real segment, under the stable sort).
    ``k = keep_count(ratio, n_real)`` per client (ratio scalar or (N,)).
    Like `selection.topk_mask`, k is traced, so the mask is built from
    stable descending ranks; ties break toward the lower segment index.
    """
    n, s, _ = w_rows.shape
    n_real = s if n_real is None else n_real
    norms = jnp.sum(jnp.square(w_rows.astype(jnp.float32)), axis=2)  # (N, S)
    order = jnp.argsort(-norms, axis=1)                 # descending, stable
    ranks = jnp.zeros((n, s), jnp.int32)
    ranks = ranks.at[jnp.arange(n)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (n, s))
    )
    k = jnp.broadcast_to(keep_count(compress_ratio, n_real), (n,))
    return ranks < k[:, None]


def stochastic_quantize(w_rows: jnp.ndarray, compress_ratio: jnp.ndarray,
                        key: jax.Array, *, dtype_bits: int = 32,
                        n_real: int | None = None) -> jnp.ndarray:
    """Unbiased stochastic uniform quantization on a per-segment scale.

    Each (client, segment) block is scaled by its max-abs value, rounded
    stochastically to ``levels = 2^bits - 1`` uniform steps, and rescaled:
    E[q(w)] = w exactly, and |q(w) - w| <= scale / levels per value.
    All-zero segments (codec/shard padding included) stay exactly zero.

    The noise is drawn at the canonical ``(N, n_real, K)`` width and
    zero-padded to the (possibly shard-padded) row width, so every
    ``model_shards`` draws the same uniforms per global segment — sharded
    quantization is bitwise identical to unsharded (DESIGN.md §13).
    """
    n, s, k_len = w_rows.shape
    n_real = s if n_real is None else n_real
    bits = jnp.broadcast_to(quant_bits(compress_ratio, dtype_bits), (n,))
    levels = jnp.exp2(bits.astype(jnp.float32)) - 1.0           # (N,)
    w = w_rows.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=2, keepdims=True)          # (N, S, 1)
    safe = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
    y = w / safe * levels[:, None, None]
    lo = jnp.floor(y)
    u = jax.random.uniform(key, (n, n_real, k_len))
    if n_real != s:
        u = jnp.pad(u, ((0, 0), (0, s - n_real), (0, 0)))
    q = lo + (u < (y - lo)).astype(jnp.float32)
    out = q / levels[:, None, None] * safe
    out = jnp.where(scale > 0, out, 0.0)
    return out.astype(w_rows.dtype)


def encode(
    codec_id: jnp.ndarray,
    w_rows: jnp.ndarray,
    compress_ratio: jnp.ndarray,
    key: jax.Array,
    *,
    n_real: int | None = None,
    dtype_bits: int = 32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply a TRACED codec to the full client-stacked segment rows.

    Returns ``(w_tx, tx_mask)``: the segments as transmitted (quantization
    transforms values; sparsification leaves them untouched) and the
    (N, S) packed-bool per-segment transmit mask (all-ones except under
    ``topk``).  The ``none`` branch is an exact pass-through — the traced
    dispatch itself adds no arithmetic to the neutral path.
    """
    n, s, _ = w_rows.shape
    ones = jnp.ones((n, s), jnp.bool_)

    def b_none(_):
        return w_rows, ones

    def b_topk(_):
        return w_rows, topk_transmit_mask(w_rows, compress_ratio,
                                          n_real=n_real)

    def b_quant(_):
        return stochastic_quantize(w_rows, compress_ratio, key,
                                   dtype_bits=dtype_bits,
                                   n_real=n_real), ones

    return jax.lax.switch(codec_id, (b_none, b_topk, b_quant), None)


def bits_fraction(codec_id: jnp.ndarray, compress_ratio: jnp.ndarray,
                  n_segments: int, *, dtype_bits: int = 32) -> jnp.ndarray:
    """Traced realized fraction of the uncompressed payload actually sent.

    none -> 1; topk -> k/S (kept-segment fraction); quant -> bits/B.
    """
    r = jnp.asarray(compress_ratio, jnp.float32)

    def b_none(_):
        return jnp.ones_like(r)

    def b_topk(_):
        return keep_count(r, n_segments).astype(jnp.float32) / n_segments

    def b_quant(_):
        return quant_bits(r, dtype_bits).astype(jnp.float32) / dtype_bits

    return jax.lax.switch(codec_id, (b_none, b_topk, b_quant), None)


def host_factor(codec: str, compress_ratio: float, *,
                n_segments: int | None = None,
                dtype_bits: int = 32) -> float:
    """Host-side (numpy) mirror of `bits_fraction` for overhead accounting.

    `core.overhead.Overhead.compressed` scales Table-III traffic and slot
    counts with this factor; it matches the traced math exactly so the
    accounting and the simulated exchange agree on what was shipped.
    """
    if codec not in CODEC_IDS:
        raise ValueError(
            f"unknown codec {codec!r}: choose from {sorted(CODEC_IDS)}"
        )
    if not 0.0 < float(compress_ratio) <= 1.0:
        raise ValueError(
            f"compress_ratio must be in (0, 1], got {compress_ratio}"
        )
    if codec == "none":
        return 1.0
    if codec == "topk":
        if n_segments is None:
            raise ValueError("topk factor needs n_segments (S)")
        k = int(np.clip(math.ceil(compress_ratio * n_segments - _CEIL_EPS),
                        1, n_segments))
        return k / n_segments
    b = int(np.clip(math.ceil(compress_ratio * dtype_bits - _CEIL_EPS),
                    1, dtype_bits))
    return b / dtype_bits
