"""Public jit'd wrappers for the Pallas kernels.

Interpret-mode selection: ``REPRO_PALLAS_INTERPRET=0/1`` forces native/
interpret lowering; unset, kernels compile natively on TPU and fall back to
interpret mode elsewhere (the kernel body executes in Python on CPU,
validating semantics).  `repro.core.aggregation.apply_mode` routes the
simulator's aggregation through `ra_aggregate` when the ``pallas`` substrate
is selected (DESIGN.md §9).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ra_aggregate as _ra
from repro.kernels import rwkv6_scan as _rwkv

# Tri-state: True/False when the env var decides, None -> backend default.
_RAW = os.environ.get("REPRO_PALLAS_INTERPRET")
INTERPRET: bool | None = None if _RAW is None else _RAW != "0"


def interpret_default() -> bool:
    """Resolved interpret flag: env override, else native only on TPU."""
    if INTERPRET is not None:
        return INTERPRET
    return jax.default_backend() != "tpu"


def ra_aggregate(w_seg, p, e, *, tx=None, mode: str = "ra_normalized",
                 block_l: int = _ra.LANES, interpret: bool | None = None):
    """Fused R&A aggregation (paper eq. 6 / fused substitution baseline).

    w_seg: (N, L, K) or batched (B, N, L, K); p: (N,)/(B, N);
    e: (N, N, L)/(B, N, N, L) in bool_/uint8/float32 -> same rank as w_seg.
    ``tx`` ((N, L)/(B, N, L), optional) selects the sparsity-aware variant
    that composes the codec's per-segment transmit mask in-kernel.
    `jax.vmap` over a grid axis lowers onto the batched kernel.
    """
    return _ra.ra_aggregate(w_seg, p, e, tx, mode=mode, block_l=block_l,
                            interpret=interpret)


def rwkv6_scan(r, k, v, w, u, *, chunk: int = 64, interpret: bool | None = None):
    """Chunked rwkv6 linear-attention scan.

    r/k/v/w: (B, S, H, D); u: (H, D) -> (B, S, H, D).
    """
    it = interpret_default() if interpret is None else interpret
    return _rwkv.rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=it)


def flash_attention(q, k, v, *, scale, causal=True, block_q=128, block_k=128,
                    interpret: bool | None = None):
    """Pallas flash-attention forward (causal GQA).

    q: (B,S,H,D); k/v: (B,S,KV,D) -> (B,S,H,D).
    """
    from repro.kernels import flash_attention as _fa

    it = interpret_default() if interpret is None else interpret
    return _fa.flash_attention_fwd(q, k, v, scale=scale, causal=causal,
                                   block_q=block_q, block_k=block_k,
                                   interpret=it)
