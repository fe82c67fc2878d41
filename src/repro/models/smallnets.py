"""The paper's FL experiment models (Sec. V-A.1), pure JAX.

  * CNN      — 2 conv layers (32/64 filters) + pool + 2 FC, ReLU
               (Fed-fashionMNIST task).
  * ResNet   — CIFAR-style ResNet-n (n=18, 56) with shortcut connections,
               or any stages and widths, optionally with GroupNorm
               (ResNet-18-GN at He et al.'s widths).
  * CharRNN  — embedding + 2-layer LSTM (256 hidden) + FC output
               (Shakespeare next-character prediction, vocab 90).
  * MLP      — small classifier for fast CPU-scale FL experiments.

All expose init(key, ...) -> params and apply(params, x) -> logits, plus a
shared `loss_and_acc`.  Widths are configurable so the CPU experiments can
run at reduced scale (recorded per experiment in EXPERIMENTS.md).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


def _conv_init(key, h, w, cin, cout, dtype=jnp.float32):
    fan_in = h * w * cin
    return (jax.random.normal(key, (h, w, cin, cout)) * np.sqrt(2.0 / fan_in)).astype(dtype)


def _fc_init(key, din, dout, dtype=jnp.float32):
    return {
        "w": (jax.random.normal(key, (din, dout)) * np.sqrt(2.0 / din)).astype(dtype),
        "b": jnp.zeros((dout,), dtype),
    }


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def conv2d(x, w, stride=1):
    """x: (B, H, W, C); w: (kh, kw, cin, cout); SAME padding.

    A custom VJP in place of JAX's transposed convolution: under a
    `shard_map` (the sharded scenario grid) JAX cannot batch that
    convolution over a second vmap (scenarios over clients) and raises
    NotImplementedError.  The input gradient is `jax.vjp` of the forward.
    The weight gradient is one plain convolution of x with the upstream
    gradient where cin > 1 (`_dw_conv`): it reads both once, where one
    contraction per kernel tap reads them kh * kw times.  Where cin == 1
    it stays per tap (`_dw_taps`): there the reads are small, and the
    convolution form makes the TPU compiler add layout copies of the
    layer's (B, H, W, scenarios x clients x cout) tensors that cost more
    than it saves.
    """
    return _conv(x, w, stride)


def _conv2d_fwd(x, w, stride):
    return _conv(x, w, stride), (x, w)


def _same_pads(x, w, stride):
    return jax.lax.padtype_to_pads(x.shape[1:3], w.shape[:2],
                                   (stride, stride), "SAME")


def _dw_taps(x, g, w, stride):
    """The weight gradient as one (samples x positions) contraction per tap."""
    kh, kw = w.shape[:2]
    xp = jnp.pad(x, ((0, 0), *_same_pads(x, w, stride), (0, 0)))
    ho, wo = g.shape[1:3]
    return jnp.stack([
        jnp.stack([
            jnp.einsum("bhwc,bhwo->co",
                       xp[:, i:i + (ho - 1) * stride + 1:stride,
                          j:j + (wo - 1) * stride + 1:stride], g)
            for j in range(kw)
        ])
        for i in range(kh)
    ])


def _dw_conv(x, g, w, stride):
    """The weight gradient as one convolution contracting samples and
    positions: samples are x's features and g's input features, g is the
    kernel dilated by the stride, and the output's spatial dims are the
    taps.  The forward's SAME pads, with the high side set (trimmed where
    negative) so the window count is exactly (kh, kw); no feature or batch
    groups, so vmaps fold into groups as they do for the forward."""
    pads = [(lo, (o - 1) * stride + k - n - lo) for (lo, _), o, k, n in
            zip(_same_pads(x, w, stride), g.shape[1:3], w.shape[:2],
                x.shape[1:3])]
    return jax.lax.conv_general_dilated(
        x, g, (1, 1), pads, rhs_dilation=(stride, stride),
        dimension_numbers=("CHWN", "IHWO", "HWNC"),
    )


def _conv2d_bwd(stride, res, g):
    x, w = res
    _, vjp_x = jax.vjp(lambda x_: _conv(x_, w, stride), x)
    dw_fn = _dw_taps if x.shape[-1] == 1 else _dw_conv
    return vjp_x(g)[0], dw_fn(x, g, w, stride).astype(w.dtype)


conv2d.defvjp(_conv2d_fwd, _conv2d_bwd)


def avgpool(x, k=2):
    return jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, k, k, 1), (1, k, k, 1), "VALID"
    ) / (k * k)


# ---------------------------------------------------------------------------
# CNN (paper: 2 conv (32, 64) + pool + 2 FC)
# ---------------------------------------------------------------------------
def init_cnn(key, *, in_hw=(28, 28), in_ch=1, n_classes=10,
             c1=32, c2=64, fc=128) -> Pytree:
    ks = jax.random.split(key, 4)
    h, w = in_hw
    flat = (h // 4) * (w // 4) * c2  # two 2x2 pools
    return {
        "conv1": _conv_init(ks[0], 3, 3, in_ch, c1),
        "conv2": _conv_init(ks[1], 3, 3, c1, c2),
        "fc1": _fc_init(ks[2], flat, fc),
        "fc2": _fc_init(ks[3], fc, n_classes),
    }


def apply_cnn(params, x):
    x = jax.nn.relu(conv2d(x, params["conv1"]))
    x = avgpool(x)
    x = jax.nn.relu(conv2d(x, params["conv2"]))
    x = avgpool(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    return x @ params["fc2"]["w"] + params["fc2"]["b"]


# ---------------------------------------------------------------------------
# Group normalisation (Wu & He, arXiv 1803.08494)
# ---------------------------------------------------------------------------
GN_EPS = 1e-5


def init_group_norm(groups: int, channels: int) -> Pytree:
    """Scale 1 and bias 0, each shaped (groups, channels // groups): the
    group count is the leading dim, so `group_norm` reads it from the
    parameters.  Row-major, the values lie in channel order."""
    if channels % groups:
        raise ValueError(f"{channels} channels do not split into "
                         f"{groups} groups")
    shape = (groups, channels // groups)
    return {"scale": jnp.ones(shape, jnp.float32),
            "bias": jnp.zeros(shape, jnp.float32)}


def group_norm(x, p, eps=GN_EPS):
    """x: (B, H, W, C).  Each sample's channels fall into G contiguous
    groups; every group is normalised by the mean and (biased) variance
    over its H * W * C/G values, then scaled and shifted per channel."""
    with jax.named_scope("group_norm"):
        b, h, w, c = x.shape
        g, cg = p["scale"].shape
        xg = x.reshape(b, h, w, g, cg)
        mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
        var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
        y = (xg - mean) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"] + p["bias"]).reshape(b, h, w, c)


# ---------------------------------------------------------------------------
# ResNet (CIFAR-style: a 3x3 stem at stride 1, no max-pool, stages of basic
# blocks, the first stage at stride 1 and each later one entered at stride 2)
# ---------------------------------------------------------------------------
def _depth_blocks(depth: int) -> tuple[int, ...]:
    if depth == 18:
        return (2, 2, 2)
    if depth == 56:
        return (9, 9, 9)
    n = (depth - 2) // 6
    return (n, n, n)


def init_resnet(key, *, depth=18, in_ch=3, n_classes=10, width=16,
                stage_blocks=None, stage_widths=None, gn_groups=0) -> Pytree:
    """Basic-block ResNet.

    By default three stages at ``width`` x (1, 2, 4) with the blocks per
    stage from ``depth`` (18 -> (2, 2, 2), 56 -> (9, 9, 9), else
    (depth - 2) // 6 each), and no normalisation.  ``stage_blocks`` and
    ``stage_widths`` (one entry per stage) replace that; the stem has the
    first stage's width.  ``gn_groups`` > 0 puts a `group_norm` of that
    many groups after every convolution, the projection shortcut's too
    (He et al.'s ResNet-18, arXiv 1512.03385, with GroupNorm: stage_blocks
    (2, 2, 2, 2), stage_widths (64, 128, 256, 512), gn_groups 2).  A
    stage whose width differs from its input's is entered through a 1x1
    projection.  Keys come from one split in layer order (stem, each
    block's conv1, conv2 and projection, fc); norms draw none.
    """
    blocks = (_depth_blocks(depth) if stage_blocks is None
              else tuple(stage_blocks))
    widths = (tuple(width * 2**s for s in range(len(blocks)))
              if stage_widths is None else tuple(stage_widths))
    if len(widths) != len(blocks):
        raise ValueError(f"{len(blocks)} stages but {len(widths)} widths")

    norm = partial(init_group_norm, gn_groups)
    ks = iter(jax.random.split(key, 4 + 2 * sum(blocks) + len(blocks)))
    p: dict = {"stem": _conv_init(next(ks), 3, 3, in_ch, widths[0])}
    if gn_groups:
        p["stem_norm"] = norm(widths[0])
    cin = widths[0]
    for s, (nb, cout) in enumerate(zip(blocks, widths)):
        stage = []
        for _ in range(nb):
            blk = {
                "conv1": _conv_init(next(ks), 3, 3, cin, cout),
                "conv2": _conv_init(next(ks), 3, 3, cout, cout),
            }
            if cin != cout:
                blk["proj"] = _conv_init(next(ks), 1, 1, cin, cout)
            if gn_groups:
                blk["norm1"], blk["norm2"] = norm(cout), norm(cout)
                if "proj" in blk:
                    blk["proj_norm"] = norm(cout)
            stage.append(blk)
            cin = cout
        p[f"stage{s}"] = stage
    p["fc"] = _fc_init(next(ks), cin, n_classes)
    return p


def _norm(p, name, x):
    """`group_norm` by ``p[name]`` where the model has that norm."""
    return group_norm(x, p[name]) if name in p else x


def _basic_block(blk, x, stride):
    h = jax.nn.relu(_norm(blk, "norm1", conv2d(x, blk["conv1"], stride=stride)))
    h = _norm(blk, "norm2", conv2d(h, blk["conv2"]))
    sc = x
    if "proj" in blk:
        sc = _norm(blk, "proj_norm", conv2d(x, blk["proj"], stride=stride))
    elif stride != 1:
        sc = x[:, ::stride, ::stride]
    return jax.nn.relu(h + sc)


# From a stem of `REMAT_WIDTH` channels up (ResNet-18's 64), each block's
# activations are recomputed in the backward pass from its input: full-batch
# training of every client model of a dispatch would otherwise keep all 18
# layers' activations at once (about 7 MB per 32x32 image and model at
# ResNet-18's widths).  Narrower nets, such as the default width 16, keep
# them and pay no extra forward pass.
REMAT_WIDTH = 64
_remat_block = jax.checkpoint(_basic_block, static_argnums=(2,))


def apply_resnet(params, x):
    """x: (B, H, W, C) -> logits; the stages, their strides and the norms
    are read from the parameter tree."""
    block = (_remat_block if params["stem"].shape[-1] >= REMAT_WIDTH
             else _basic_block)
    with jax.named_scope("resnet"):
        with jax.named_scope("stem"):
            x = jax.nn.relu(_norm(params, "stem_norm",
                                  conv2d(x, params["stem"])))
        s = 0
        while f"stage{s}" in params:
            with jax.named_scope(f"stage{s}"):
                for i, blk in enumerate(params[f"stage{s}"]):
                    x = block(blk, x, 2 if s > 0 and i == 0 else 1)
            s += 1
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        return x @ params["fc"]["w"] + params["fc"]["b"]


# ---------------------------------------------------------------------------
# Char-RNN (embedding + 2-layer LSTM + FC; paper Sec. V-A.1)
# ---------------------------------------------------------------------------
def init_lstm_cell(key, din, dh):
    ks = jax.random.split(key, 2)
    return {
        "wx": (jax.random.normal(ks[0], (din, 4 * dh)) / np.sqrt(din)).astype(jnp.float32),
        "wh": (jax.random.normal(ks[1], (dh, 4 * dh)) / np.sqrt(dh)).astype(jnp.float32),
        "b": jnp.zeros((4 * dh,), jnp.float32),
    }


def lstm_cell(params, carry, x):
    h, c = carry
    z = x @ params["wx"] + h @ params["wh"] + params["b"]
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return (h, c), h


def init_charrnn(key, *, vocab=90, embed=8, hidden=256) -> Pytree:
    ks = jax.random.split(key, 4)
    return {
        "embed": (jax.random.normal(ks[0], (vocab, embed)) * 0.1).astype(jnp.float32),
        "lstm1": init_lstm_cell(ks[1], embed, hidden),
        "lstm2": init_lstm_cell(ks[2], hidden, hidden),
        "fc": _fc_init(ks[3], hidden, vocab),
    }


def apply_charrnn(params, tokens):
    """tokens: (B, S) int32 -> logits (B, S, V)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)  # (B,S,E)
    dh = params["lstm1"]["wh"].shape[0]

    def run_layer(lp, seq):
        def step(carry, xt):
            return lstm_cell(lp, carry, xt)
        carry = (jnp.zeros((b, dh)), jnp.zeros((b, dh)))
        _, hs = jax.lax.scan(step, carry, jnp.swapaxes(seq, 0, 1))
        return jnp.swapaxes(hs, 0, 1)

    h = run_layer(params["lstm1"], x)
    h = run_layer(params["lstm2"], h)
    return h @ params["fc"]["w"] + params["fc"]["b"]


# ---------------------------------------------------------------------------
# MLP classifier (fast CPU-scale FL experiments)
# ---------------------------------------------------------------------------
def init_mlp_clf(key, *, d_in=32, d_hidden=64, n_classes=10) -> Pytree:
    ks = jax.random.split(key, 3)
    return {
        "fc1": _fc_init(ks[0], d_in, d_hidden),
        "fc2": _fc_init(ks[1], d_hidden, d_hidden),
        "fc3": _fc_init(ks[2], d_hidden, n_classes),
    }


def apply_mlp_clf(params, x):
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    x = jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"])
    return x @ params["fc3"]["w"] + params["fc3"]["b"]


# ---------------------------------------------------------------------------
# Shared losses
# ---------------------------------------------------------------------------
def ce_loss(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def accuracy(logits, labels):
    return jnp.mean(jnp.argmax(logits, -1) == labels)


MODELS = {
    "cnn": (init_cnn, apply_cnn),
    "resnet": (init_resnet, apply_resnet),
    "charrnn": (init_charrnn, apply_charrnn),
    "mlp": (init_mlp_clf, apply_mlp_clf),
}
