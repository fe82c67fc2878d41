"""The paper's FL experiment models (Sec. V-A.1), pure JAX.

  * CNN      — 2 conv layers (32/64 filters) + pool + 2 FC, ReLU
               (Fed-fashionMNIST task).
  * ResNet   — CIFAR-style ResNet-n (n=18, 56) with shortcut connections.
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

    The weight gradient is one contraction per kernel tap instead of JAX's
    transposed convolution: under a `shard_map` (the sharded scenario grid)
    JAX cannot batch that convolution over a second vmap (scenarios over
    clients) and raises NotImplementedError.
    """
    return _conv(x, w, stride)


def _conv2d_fwd(x, w, stride):
    return _conv(x, w, stride), (x, w)


def _conv2d_bwd(stride, res, g):
    x, w = res
    _, vjp_x = jax.vjp(lambda x_: _conv(x_, w, stride), x)
    kh, kw = w.shape[:2]
    pads = jax.lax.padtype_to_pads(x.shape[1:3], (kh, kw), (stride, stride),
                                   "SAME")
    xp = jnp.pad(x, ((0, 0), *pads, (0, 0)))
    ho, wo = g.shape[1:3]
    dw = jnp.stack([
        jnp.stack([
            jnp.einsum("bhwc,bhwo->co",
                       xp[:, i:i + (ho - 1) * stride + 1:stride,
                          j:j + (wo - 1) * stride + 1:stride], g)
            for j in range(kw)
        ])
        for i in range(kh)
    ])
    return vjp_x(g)[0], dw.astype(w.dtype)


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
# ResNet (CIFAR-style: 3 stages, 2n blocks per stage for ResNet-6n+2)
# ---------------------------------------------------------------------------
def init_resnet(key, *, depth=18, in_ch=3, n_classes=10, width=16) -> Pytree:
    """depth in {18 -> (2,2,2) basic-ish stages at width; 56 -> (9,9,9)}."""
    if depth == 18:
        blocks = (2, 2, 2)
    elif depth == 56:
        blocks = (9, 9, 9)
    else:
        n = (depth - 2) // 6
        blocks = (n, n, n)
    ks = iter(jax.random.split(key, 4 + 2 * sum(blocks) + len(blocks)))
    p: dict = {"stem": _conv_init(next(ks), 3, 3, in_ch, width)}
    cin = width
    for s, nb in enumerate(blocks):
        cout = width * (2**s)
        stage = []
        for b in range(nb):
            blk = {
                "conv1": _conv_init(next(ks), 3, 3, cin, cout),
                "conv2": _conv_init(next(ks), 3, 3, cout, cout),
            }
            if cin != cout:
                blk["proj"] = _conv_init(next(ks), 1, 1, cin, cout)
            stage.append(blk)
            cin = cout
        p[f"stage{s}"] = stage
    p["fc"] = _fc_init(next(ks), cin, n_classes)
    return p


def apply_resnet(params, x):
    x = jax.nn.relu(conv2d(x, params["stem"]))
    s = 0
    while f"stage{s}" in params:
        stride = 1 if s == 0 else 2
        for i, blk in enumerate(params[f"stage{s}"]):
            st = stride if i == 0 else 1
            h = jax.nn.relu(conv2d(x, blk["conv1"], stride=st))
            h = conv2d(h, blk["conv2"])
            sc = x
            if "proj" in blk:
                sc = conv2d(x, blk["proj"], stride=st)
            elif st != 1:
                sc = x[:, ::st, ::st]
            x = jax.nn.relu(h + sc)
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
