"""Reference model and FLOP counter of the `resnet18gn_cifar10` configuration.

ResNet-18 (He et al., arXiv 1512.03385, Table 1) in its CIFAR form, with
each BatchNorm replaced by a GroupNorm of 2 groups (Reddi et al., arXiv
2003.00295): a 3x3 stem conv at stride 1 and no max-pool, four stages of
two basic blocks at 64/128/256/512, stages 1-3 entered at stride 2 with a
1x1 projection shortcut, a GroupNorm after every convolution and on each
projection, global average pool, a 10-way output; 32x32x3 input
(11,173,962 parameters).  Plain `jax.numpy` in float32; products go
through the reference's `Matmuls`, so the precision is the caller's.

`Matmuls.conv` is a stride-1 SAME convolution.  A 3x3 SAME convolution at
stride 2 on an even input is that output at ``[1::2, 1::2]`` (SAME pads one
row and column at the high side only), and a 1x1 one at stride 2 is the
1x1 convolution of the input at ``[::2, ::2]``.

Initialisation follows the simulator's documented one (He-normal weights
from ``split(key, 4 + 2 * blocks + stages)`` in layer order - stem, each
block's conv1, conv2 and projection, fc - zero biases; GroupNorm scale 1
and bias 0, stored as (groups, channels / groups)), so that the reference
starts from the model the seed defines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GN_EPS = 1e-5


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape) * np.float32(np.sqrt(2.0 / fan_in))


def _norm(w: dict, c: int) -> dict:
    g = w["gn_groups"]
    return {"scale": jnp.ones((g, c // g), jnp.float32),
            "bias": jnp.zeros((g, c // g), jnp.float32)}


def init(key, w: dict) -> dict:
    blocks, widths = w["stage_blocks"], w["stage_widths"]
    ks = list(jax.random.split(key, 4 + 2 * sum(blocks) + len(blocks)))
    ks.reverse()
    c0 = widths[0]
    params = {"stem": _he(ks.pop(), (3, 3, w["in_ch"], c0), 9 * w["in_ch"]),
              "stem_norm": _norm(w, c0)}
    cin = c0
    for s, (nb, cout) in enumerate(zip(blocks, widths)):
        stage = []
        for _ in range(nb):
            blk = {"conv1": _he(ks.pop(), (3, 3, cin, cout), 9 * cin),
                   "conv2": _he(ks.pop(), (3, 3, cout, cout), 9 * cout),
                   "norm1": _norm(w, cout), "norm2": _norm(w, cout)}
            if cin != cout:
                blk["proj"] = _he(ks.pop(), (1, 1, cin, cout), cin)
                blk["proj_norm"] = _norm(w, cout)
            stage.append(blk)
            cin = cout
        params[f"stage{s}"] = stage
    params["fc"] = {"w": _he(ks.pop(), (cin, w["n_classes"]), cin),
                    "b": jnp.zeros((w["n_classes"],), jnp.float32)}
    return params


def group_norm(x, p):
    """Per sample, channels in G contiguous groups, each normalised by its
    mean and biased variance over (H, W, channels of the group)."""
    b, h, wd, c = x.shape
    g = p["scale"].shape[0]
    xg = x.reshape(b, h, wd, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mu) / jnp.sqrt(var + GN_EPS)).reshape(b, h, wd, c)
    return xn * p["scale"].reshape(c) + p["bias"].reshape(c)


def _conv(mm, x, k, stride):
    if stride == 1:
        return mm.conv(x, k)
    if k.shape[0] == 1:
        return mm.conv(x[:, ::stride, ::stride], k)
    return mm.conv(x, k)[:, 1::stride, 1::stride]


def apply(params, x, mm, w: dict):
    """x: (B, 32, 32, 3) float32 -> logits (B, classes)."""
    x = jax.nn.relu(group_norm(mm.conv(x, params["stem"]),
                               params["stem_norm"]))
    for s in range(len(w["stage_blocks"])):
        for i, blk in enumerate(params[f"stage{s}"]):
            st = 2 if s > 0 and i == 0 else 1
            h = jax.nn.relu(group_norm(_conv(mm, x, blk["conv1"], st),
                                       blk["norm1"]))
            h = group_norm(mm.conv(h, blk["conv2"]), blk["norm2"])
            sc = (group_norm(_conv(mm, x, blk["proj"], st), blk["proj_norm"])
                  if "proj" in blk else x)
            x = jax.nn.relu(h + sc)
    x = x.mean(axis=(1, 2))
    return mm.dot(x, params["fc"]["w"]) + params["fc"]["b"]


def forward_flops(w: dict, example_shape: tuple) -> int:
    """Forward FLOPs of one example: 2*Ho*Wo*kh*kw*cin*cout per convolution
    at its output size, 2*m*n for the output layer; GroupNorm, ReLU,
    residual adds and the pool not counted."""
    h, wd, cin = example_shape
    c = w["stage_widths"][0]
    total = 2 * h * wd * 9 * cin * c
    cin = c
    for s, (nb, cout) in enumerate(zip(w["stage_blocks"], w["stage_widths"])):
        for i in range(nb):
            if s > 0 and i == 0:
                h, wd = -(-h // 2), -(-wd // 2)
            total += 2 * h * wd * 9 * (cin + cout) * cout
            if cin != cout:
                total += 2 * h * wd * cin * cout
            cin = cout
    return total + 2 * cin * w["n_classes"]


WIDTHS = ("in_ch", "n_classes", "stage_blocks", "stage_widths", "gn_groups")


def make_data(cfg: dict):
    """Label-skewed 32x32x3 stand-in for CIFAR-10 (one class per client),
    from the configuration's fixed data seed."""
    from bench import data

    return data.image_classification(
        n_clients=cfg["n_clients"], n_classes=cfg["n_classes"],
        shape=[*cfg["in_hw"], cfg["in_ch"]],
        samples_per_client=cfg["samples_per_client"],
        classes_per_client=cfg["classes_per_client"], noise=cfg["noise"],
        test_samples=cfg["test_samples"], seed=cfg["data_seed"])
