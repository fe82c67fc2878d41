"""Reference model and FLOP counter of the `cnn_fmnist` configuration.

The paper's Fed-FashionMNIST client model (Sec. V-A.1): two 3x3 SAME
convolutions with 32 and 64 filters, each followed by ReLU and a 2x2
average pool, a 128-unit ReLU layer and a 10-way output, on 28x28x1 input
(421,546 parameters).  Plain `jax.numpy` in float32; products go through
the reference's `Matmuls`, so the precision is the caller's.

Initialisation follows the simulator's documented one (He-normal weights
from ``split(key, 4)`` in layer order, zero biases), so that the reference
starts from the model the seed defines.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape) * np.float32(np.sqrt(2.0 / fan_in))


def _flat(w: dict) -> int:
    h, wd = w["in_hw"]
    return (h // 4) * (wd // 4) * w["c2"]


def init(key, w: dict) -> dict:
    ks = jax.random.split(key, 4)
    flat = _flat(w)
    return {
        "conv1": _he(ks[0], (3, 3, w["in_ch"], w["c1"]), 9 * w["in_ch"]),
        "conv2": _he(ks[1], (3, 3, w["c1"], w["c2"]), 9 * w["c1"]),
        "fc1": {"w": _he(ks[2], (flat, w["fc"]), flat),
                "b": jnp.zeros((w["fc"],), jnp.float32)},
        "fc2": {"w": _he(ks[3], (w["fc"], w["n_classes"]), w["fc"]),
                "b": jnp.zeros((w["n_classes"],), jnp.float32)},
    }


def _pool(x):
    b, h, wd, c = x.shape
    return x.reshape(b, h // 2, 2, wd // 2, 2, c).mean(axis=(2, 4))


def apply(params, x, mm, w: dict):
    """x: (B, H, W, C) float32 -> logits (B, classes)."""
    x = _pool(jax.nn.relu(mm.conv(x, params["conv1"])))
    x = _pool(jax.nn.relu(mm.conv(x, params["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(mm.dot(x, params["fc1"]["w"]) + params["fc1"]["b"])
    return mm.dot(x, params["fc2"]["w"]) + params["fc2"]["b"]


def forward_flops(w: dict, example_shape: tuple) -> int:
    """Forward FLOPs of one example: 2*H*W*kh*kw*cin*cout per convolution,
    2*m*n per matrix-vector product; pooling, ReLU and biases not counted."""
    h, wd = w["in_hw"]
    conv1 = 2 * h * wd * 9 * w["in_ch"] * w["c1"]
    conv2 = 2 * (h // 2) * (wd // 2) * 9 * w["c1"] * w["c2"]
    fc = 2 * _flat(w) * w["fc"] + 2 * w["fc"] * w["n_classes"]
    return conv1 + conv2 + fc


WIDTHS = ("in_hw", "in_ch", "c1", "c2", "fc", "n_classes")


def make_data(cfg: dict):
    """Label-skewed 28x28x1 stand-in for Fashion-MNIST (one class per
    client), from the configuration's fixed data seed."""
    from bench import data

    return data.image_classification(
        n_clients=cfg["n_clients"], n_classes=cfg["n_classes"],
        shape=[*cfg["in_hw"], cfg["in_ch"]],
        samples_per_client=cfg["samples_per_client"],
        classes_per_client=cfg["classes_per_client"], noise=cfg["noise"],
        test_samples=cfg["test_samples"], seed=cfg["data_seed"])
