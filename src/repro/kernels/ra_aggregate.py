"""Pallas TPU kernel: R&A segment aggregation (eq. 6), both modes, batched.

The paper's aggregation hot spot, for every receiver n and segment l:

  * ``ra_normalized`` (eq. 6, adaptive normalization):
        out[n, l] = sum_m p_m e[m,n,l] w[m,l] / sum_m p_m e[m,n,l]
  * ``substitution`` (baseline [12], fused):
        out[n, l] = sum_m p_m e[m,n,l] w[m,l]
                    + (sum_m p_m (1 - e[m,n,l])) * w[n,l]

Naive jnp materializes the (N, N, L) coefficient tensor and an einsum over
N x L x K in HBM.  On TPU the op is memory-bound (one pass over N copies of
the model), so the kernel streams (BL, BK) tiles of every sender's segments
through VMEM and fuses mask-weighting, reduction, and renormalization (or
own-segment substitution) in a single pass.

Batching: the public `ra_aggregate` accepts rank-3 ``w_seg`` (one scenario)
or rank-4 (a leading batch axis, folded into the Pallas grid), and carries a
`jax.custom_batching.custom_vmap` rule so `jax.vmap` over a scenario-grid
axis — including the vmap inside `scenarios.run_grid` / its `shard_map`
wrapper — lowers onto the batched kernel instead of falling off it.  Nested
vmaps flatten into the same single batch grid dimension.

Layout (Mosaic's rule: a block's last two dims are multiples of (8, 128) or
equal to the array's):

  * ``w``/``own``/``out``: (B, N, L, K) blocks (1, N | 1, BL, BK) — segments
    on sublanes, packet values on lanes.
  * ``e``: receiver-major (B, N_recv, N_send, L) blocks (1, 1, N, BL) and
    ``tx`` (B, N, L) blocks (1, N, BL) — the SEGMENT axis on lanes, the full
    sender axis on sublanes.  The mask stays packed in HBM: ``bool_`` and
    ``uint8`` are cast to ``int8`` (same bytes) at the kernel edge, since
    Mosaic casts neither to float32; float masks enter as they are.  Each
    step casts its (N, BL) block to float32 once and transposes it in VMEM,
    so each sender's coefficients become a (BL, 1) column that broadcasts
    over the packet lanes.
  * ``p``: (B, 1, N) float32 in SMEM, one (1, 1, N) block per scenario,
    read as scalars.

Grid (B, L/BL, K/BK, N_recv), receiver innermost: the (N, BL, BK) sender
block keeps its block index across the receiver sweep, so it is copied
from HBM once per (scenario, tile) rather than once per receiver.

Tiling: BL is a multiple of the 128-lane width (``block_l``, default 128),
or all of L when L <= block_l (a full-dim block needs no padding).  L is
padded UP to a multiple of BL (padded segments carry an all-zero mask and
are sliced off the output) — never the block shrunk to a divisor, which for
prime L (e.g. L=1181) would degenerate the segment axis.  BK is the largest
multiple-of-128 divisor of K whose sender block fits ``_W_BLOCK_BYTES``
(4 MiB), else all of K.

VMEM budget on TPU v5e (16 MiB scoped default of 128 MiB physical), f32,
N = 10, BL = 128, every buffer double-buffered by the pipeline:

  ============================  =================  ==================
  shape (B, N, L, K)            BK                 VMEM per step
  ============================  =================  ==================
  (8, 10, 412, 1024)  CNN       512 (w 2.5 MiB)    6.5 MiB
  (4, 10, 2225, 512)  NWP       512 (w 2.5 MiB)    6.5 MiB
  ============================  =================  ==================

(2 x w + 2 x out + 2 x own + two (BL, BK) f32 temporaries; the mask and
tx blocks are a few KiB.)  ``vmem_limit_bytes`` is 5/4 of the estimate,
at least the scoped default: many senders raise it, up to 100 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MODES = ("ra_normalized", "substitution")

LANES = 128
_W_BLOCK_BYTES = 4 * 2**20         # per sender-block buffer
_SCOPED_VMEM_BYTES = 16 * 2**20    # v5e default scoped VMEM limit
_MAX_VMEM_BYTES = 100 * 2**20      # below v5e's 128 MiB physical VMEM


def _kernel(*refs, mode, has_tx):
    """One grid step: (scenario, segment tile, value tile, receiver).

    Block views (N senders, BL segments, BK values):
      p_ref:   (1, 1, N)      SMEM aggregation weights of this scenario
      e_ref:   (1, 1, N, BL)  success mask column of THIS receiver
      tx_ref:  (1, N, BL)     optional per-segment transmit mask
      w_ref:   (1, N, BL, BK) sender segments
      own_ref: (1, 1, BL, BK) the receiver's own segments (substitution)
      out_ref: (1, 1, BL, BK)
    """
    refs = list(refs)
    p_ref, e_ref = refs.pop(0), refs.pop(0)
    tx_ref = refs.pop(0) if has_tx else None
    w_ref = refs.pop(0)
    own_ref = refs.pop(0) if mode == "substitution" else None
    (out_ref,) = refs
    n = w_ref.shape[1]

    e = e_ref[0, 0].astype(jnp.float32)                  # (N, BL)
    if has_tx:
        # Pruned sender segments (tx == 0) drop out of the delivered set;
        # the receiver's own row is restored to 1 (its own segments never
        # cross the air) — `aggregation.apply_transmit_mask` per block, so
        # the packed (N, L) transmit mask is read instead of a pre-composed
        # (N, N, L) one.
        sender = jax.lax.broadcasted_iota(jnp.int32, e.shape, 0)
        e = jnp.where(sender == pl.program_id(3), 1.0,
                      e * tx_ref[0].astype(jnp.float32))
    e_t = e.T                                            # (BL, N)

    num = jnp.zeros(out_ref.shape[2:], jnp.float32)      # (BL, BK)
    den = jnp.zeros((e_t.shape[0], 1), jnp.float32)      # (BL, 1)
    p_sum = jnp.float32(0.0)
    for m in range(n):
        coeff = p_ref[0, 0, m] * e_t[:, m:m + 1]            # (BL, 1)
        num = num + coeff * w_ref[0, m].astype(jnp.float32)
        den = den + coeff
        p_sum = p_sum + p_ref[0, 0, m]
    if mode == "substitution":
        # Lost-sender mass sum_m p_m (1 - e) = sum(p) - sum(coeff): no
        # (1 - e) tensor is built.
        out = num + (p_sum - den) * own_ref[0, 0].astype(jnp.float32)
    else:
        out = num / jnp.maximum(den, 1e-12)
    out_ref[0, 0] = out.astype(out_ref.dtype)


def _tiles(n, l, k, itemsize, block_l):
    """(BL, padded L, BK, VMEM bytes per step) — see the module docstring."""
    if block_l < 1 or block_l % LANES:
        raise ValueError(
            f"block_l must be a positive multiple of {LANES} (the segment "
            f"axis is on lanes), got {block_l}"
        )
    bl = l if l <= block_l else block_l
    lp = -(-l // bl) * bl
    bk = k
    if k % LANES == 0:
        fits = [d for d in range(LANES, k + 1, LANES)
                if k % d == 0 and n * bl * d * itemsize <= _W_BLOCK_BYTES]
        bk = fits[-1] if fits else LANES
    tile = bl * bk
    vmem = 2 * (n * tile * itemsize) + 4 * tile * itemsize + 2 * tile * 4
    return bl, lp, bk, vmem


def _packed(mask):
    """A 0/1 mask as the kernel reads it: float masks as-is, else int8."""
    return mask if jnp.issubdtype(mask.dtype, jnp.floating) else \
        mask.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("mode", "block_l", "interpret"))
def _ra_call(w_seg, p, e, tx=None, *, mode, block_l, interpret):
    """The batched pallas_call: w_seg (B, N, L, K), p (B, N), e (B, N, N, L).

    ``tx`` (optional, (B, N, L) packed) selects the sparsity-aware kernel
    variant; its presence is a static (trace-level) choice.
    """
    b, n, l, k = w_seg.shape
    bl, lp, bk, vmem = _tiles(n, l, k, w_seg.dtype.itemsize, block_l)
    # Receiver-major mask (B, receiver, sender, L); packed masks travel as
    # int8 (Mosaic casts neither bool_ nor uint8 refs to float32).
    e_rm = _packed(jnp.swapaxes(e, 1, 2))
    if tx is not None:
        tx = _packed(tx)
    if lp != l:
        # Pad L UP to a block multiple (zero mask + zero segments: the padded
        # tail is sliced off below) instead of shrinking BL to a divisor.
        w_seg = jnp.pad(w_seg, ((0, 0), (0, 0), (0, lp - l), (0, 0)))
        e_rm = jnp.pad(e_rm, ((0, 0), (0, 0), (0, 0), (0, lp - l)))
        if tx is not None:
            tx = jnp.pad(tx, ((0, 0), (0, 0), (0, lp - l)))

    in_specs = [
        pl.BlockSpec((1, 1, n), lambda bi, s, c, r: (bi, 0, 0),
                     memory_space=pltpu.SMEM),                      # p
        pl.BlockSpec((1, 1, n, bl), lambda bi, s, c, r: (bi, r, 0, s)),  # e
    ]
    args = [p.astype(jnp.float32)[:, None, :], e_rm]
    if tx is not None:
        in_specs.append(pl.BlockSpec((1, n, bl),
                                     lambda bi, s, c, r: (bi, 0, s)))
        args.append(tx)
    in_specs.append(pl.BlockSpec((1, n, bl, bk),
                                 lambda bi, s, c, r: (bi, 0, s, c)))  # w
    args.append(w_seg)
    own_spec = pl.BlockSpec((1, 1, bl, bk), lambda bi, s, c, r: (bi, r, s, c))
    if mode == "substitution":
        in_specs.append(own_spec)
        args.append(w_seg)

    out = pl.pallas_call(
        functools.partial(_kernel, mode=mode, has_tx=tx is not None),
        grid=(b, lp // bl, k // bk, n),
        in_specs=in_specs,
        out_specs=own_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, lp, k), w_seg.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(max(vmem * 5 // 4, _SCOPED_VMEM_BYTES),
                                 _MAX_VMEM_BYTES)),
        interpret=interpret,
    )(*args)
    return out[:, :, :l] if lp != l else out


def _broadcast_unbatched(axis_size, in_batched, args):
    """Give every unbatched arg the leading batch axis of the batched ones."""
    return tuple(
        arg if batched
        else jnp.broadcast_to(arg[None], (axis_size,) + arg.shape)
        for batched, arg in zip(in_batched, args)
    )


@functools.lru_cache(maxsize=None)
def _batched_fn(mode: str, block_l: int, interpret: bool):
    """The rank-4 entry point, with a vmap rule that FOLDS any further batch
    axis into the existing one (so arbitrarily nested vmaps stay on the
    kernel: each nesting level flattens into the single batch grid dim)."""

    @jax.custom_batching.custom_vmap
    def fnb(w_seg, p, e):
        return _ra_call(w_seg, p, e, mode=mode, block_l=block_l,
                        interpret=interpret)

    @fnb.def_vmap
    def _rule(axis_size, in_batched, w_seg, p, e):  # noqa: ANN001
        w_seg, p, e = _broadcast_unbatched(axis_size, in_batched,
                                           (w_seg, p, e))
        inner = w_seg.shape[1]
        flat = fnb(
            w_seg.reshape((axis_size * inner,) + w_seg.shape[2:]),
            p.reshape((axis_size * inner,) + p.shape[2:]),
            e.reshape((axis_size * inner,) + e.shape[2:]),
        )
        return flat.reshape((axis_size, inner) + flat.shape[1:]), True

    return fnb


@functools.lru_cache(maxsize=None)
def _scalar_fn(mode: str, block_l: int, interpret: bool):
    """The rank-3 (single scenario) entry point; its vmap rule routes to the
    batched kernel with the batch axis folded into the Pallas grid."""

    @jax.custom_batching.custom_vmap
    def fn(w_seg, p, e):
        return _ra_call(w_seg[None], p[None], e[None], mode=mode,
                        block_l=block_l, interpret=interpret)[0]

    @fn.def_vmap
    def _rule(axis_size, in_batched, w_seg, p, e):  # noqa: ANN001
        w_seg, p, e = _broadcast_unbatched(axis_size, in_batched,
                                           (w_seg, p, e))
        return _batched_fn(mode, block_l, interpret)(w_seg, p, e), True

    return fn


@functools.lru_cache(maxsize=None)
def _batched_fn_tx(mode: str, block_l: int, interpret: bool):
    """Rank-4 sparsity-aware entry point (same fold-the-batch vmap rule)."""

    @jax.custom_batching.custom_vmap
    def fnb(w_seg, p, e, tx):
        return _ra_call(w_seg, p, e, tx, mode=mode, block_l=block_l,
                        interpret=interpret)

    @fnb.def_vmap
    def _rule(axis_size, in_batched, w_seg, p, e, tx):  # noqa: ANN001
        w_seg, p, e, tx = _broadcast_unbatched(axis_size, in_batched,
                                               (w_seg, p, e, tx))
        inner = w_seg.shape[1]
        flat = fnb(
            w_seg.reshape((axis_size * inner,) + w_seg.shape[2:]),
            p.reshape((axis_size * inner,) + p.shape[2:]),
            e.reshape((axis_size * inner,) + e.shape[2:]),
            tx.reshape((axis_size * inner,) + tx.shape[2:]),
        )
        return flat.reshape((axis_size, inner) + flat.shape[1:]), True

    return fnb


@functools.lru_cache(maxsize=None)
def _scalar_fn_tx(mode: str, block_l: int, interpret: bool):
    """Rank-3 sparsity-aware entry point; vmap routes to the batched form."""

    @jax.custom_batching.custom_vmap
    def fn(w_seg, p, e, tx):
        return _ra_call(w_seg[None], p[None], e[None], tx[None], mode=mode,
                        block_l=block_l, interpret=interpret)[0]

    @fn.def_vmap
    def _rule(axis_size, in_batched, w_seg, p, e, tx):  # noqa: ANN001
        w_seg, p, e, tx = _broadcast_unbatched(axis_size, in_batched,
                                               (w_seg, p, e, tx))
        return _batched_fn_tx(mode, block_l, interpret)(w_seg, p, e, tx), True

    return fn


def ra_aggregate(
    w_seg: jnp.ndarray,
    p: jnp.ndarray,
    e: jnp.ndarray,
    tx: jnp.ndarray | None = None,
    *,
    mode: str = "ra_normalized",
    block_l: int = LANES,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused R&A aggregation. See ref.ra_aggregate_ref for semantics.

    Args:
      w_seg: (N, L, K) — or (B, N, L, K) batched — float32/bf16 segments.
      p:     (N,) / (B, N) float32 weights.
      e:     (N, N, L) / (B, N, N, L) success mask (sender, receiver,
             segment); bool_/uint8/float32 accepted (one cast at the edge).
      tx:    optional (N, L) / (B, N, L) per-segment TRANSMIT mask (the
             codec layer's packed-bool output, `repro.core.compression`) —
             selects the sparsity-aware kernel variant, which composes the
             pruned-sender semantics of `aggregation.apply_transmit_mask`
             in VMEM instead of pre-materializing a composed (N, N, L)
             success mask in HBM.
      mode: "ra_normalized" (eq. 6) or "substitution" (fused baseline [12]).
      block_l: segments per VMEM tile, a multiple of 128 (L pads up to a
             multiple; L <= block_l is one full-L tile).
      interpret: run in Pallas interpret mode; None resolves through
             `repro.kernels.ops.interpret_default` (native only on TPU).

    `jax.vmap` over a leading axis of any argument lowers onto the batched
    kernel (custom_vmap rule) — the grid engine's vmap/shard_map included.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if interpret is None:
        from repro.kernels import ops   # ops imports this module
        interpret = ops.interpret_default()
    if w_seg.ndim == 4:
        b, n, l, _ = w_seg.shape
        if p.ndim == 1:   # shared weights across the batch
            p = jnp.broadcast_to(p[None], (b,) + p.shape)
        if e.ndim == 3:   # shared mask across the batch
            e = jnp.broadcast_to(e[None], (b,) + e.shape)
        if p.shape != (b, n) or e.shape != (b, n, n, l):
            raise ValueError(
                f"batched ra_aggregate: w_seg {w_seg.shape} needs p "
                f"(N,)/(B, N) and e (N, N, L)/(B, N, N, L); got p {p.shape}, "
                f"e {e.shape}"
            )
        if tx is None:
            return _batched_fn(mode, block_l, bool(interpret))(w_seg, p, e)
        if tx.ndim == 2:  # shared transmit mask across the batch
            tx = jnp.broadcast_to(tx[None], (b,) + tx.shape)
        if tx.shape != (b, n, l):
            raise ValueError(
                f"batched ra_aggregate: tx must be (N, L)/(B, N, L), got "
                f"{tx.shape} for w_seg {w_seg.shape}"
            )
        return _batched_fn_tx(mode, block_l, bool(interpret))(w_seg, p, e, tx)
    n, l, _ = w_seg.shape
    if p.shape != (n,) or e.shape != (n, n, l):
        raise ValueError(
            f"ra_aggregate: w_seg {w_seg.shape} needs p (N,) and e "
            f"(N, N, L); got p {p.shape}, e {e.shape}"
        )
    if tx is None:
        return _scalar_fn(mode, block_l, bool(interpret))(w_seg, p, e)
    if tx.shape != (n, l):
        raise ValueError(
            f"ra_aggregate: tx must be (N, L) = ({n}, {l}), got {tx.shape}"
        )
    return _scalar_fn_tx(mode, block_l, bool(interpret))(w_seg, p, e, tx)
