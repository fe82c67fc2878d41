"""Reference model and FLOP counter of the `charrnn_shakespeare` configuration.

The LEAF Shakespeare next-character model (Caldas et al., "LEAF: A
Benchmark for Federated Settings"): an 8-dimensional embedding, two LSTM
layers of 256 units and a dense output over the vocabulary, on sequences
of 80 characters (817,872 parameters at LEAF's 80-symbol
vocabulary).  Plain `jax.numpy` in float32; products go through the
reference's `Matmuls`.

Departures from a textbook LSTM, kept because the simulator's model has
them: the gates are one fused ``x @ wx + h @ wh + b`` split as (i, f, g,
o), and the forget gate carries a constant +1 bias.  Initialisation
follows the simulator's documented one (embedding N(0, 0.01), input and
recurrent weights N(0, 1/fan_in), zero biases, keys ``split(key, 4)`` for
embedding, layer 1, layer 2, output).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _cell(key, din, dh):
    ks = jax.random.split(key, 2)
    return {
        "wx": jax.random.normal(ks[0], (din, 4 * dh)) / np.float32(np.sqrt(din)),
        "wh": jax.random.normal(ks[1], (dh, 4 * dh)) / np.float32(np.sqrt(dh)),
        "b": jnp.zeros((4 * dh,), jnp.float32),
    }


def init(key, w: dict) -> dict:
    ks = jax.random.split(key, 4)
    h, v = w["hidden"], w["vocab"]
    return {
        "embed": jax.random.normal(ks[0], (v, w["embed"])) * 0.1,
        "lstm1": _cell(ks[1], w["embed"], h),
        "lstm2": _cell(ks[2], h, h),
        "fc": {"w": jax.random.normal(ks[3], (h, v))
                    * np.float32(np.sqrt(2.0 / h)),
               "b": jnp.zeros((v,), jnp.float32)},
    }


def _layer(p, seq, mm):
    """One LSTM layer over (B, S, D) -> (B, S, H)."""
    b = seq.shape[0]
    dh = p["wh"].shape[0]

    def step(carry, xt):
        h, c = carry
        z = mm.dot(xt, p["wx"]) + mm.dot(h, p["wh"]) + p["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zeros = jnp.zeros((b, dh), jnp.float32)
    _, hs = jax.lax.scan(step, (zeros, zeros), jnp.swapaxes(seq, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def apply(params, tokens, mm, w: dict):
    """tokens: (B, S) int32 -> logits (B, S, vocab)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    h = _layer(params["lstm2"], _layer(params["lstm1"], x, mm), mm)
    return mm.dot(h, params["fc"]["w"]) + params["fc"]["b"]


def forward_flops(w: dict, example_shape: tuple) -> int:
    """Forward FLOPs of one example, a sequence of ``example_shape[0]`` tokens:
    per token 2*(din + H)*4H per LSTM layer plus 2*H*vocab for the output;
    the embedding lookup and the gate nonlinearities are not counted."""
    e, h, v = w["embed"], w["hidden"], w["vocab"]
    per_token = 2 * (e + h) * 4 * h + 2 * (h + h) * 4 * h + 2 * h * v
    return per_token * int(example_shape[0])


WIDTHS = ("vocab", "embed", "hidden")


def make_data(cfg: dict):
    """Per-client Markov-chain character streams standing in for the
    plays' speaking roles, from the configuration's fixed data seed."""
    from bench import data

    return data.char_stream(
        n_clients=cfg["n_clients"], vocab=cfg["vocab"],
        seq_len=cfg["seq_len"],
        sequences_per_client=cfg["sequences_per_client"],
        test_sequences=cfg["test_sequences"], seed=cfg["data_seed"])
