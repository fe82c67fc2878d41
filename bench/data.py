"""Synthetic federated datasets of matched shape, built from a fixed seed.

Copies of the generators in `repro.data.synthetic` (label-skewed Gaussian
class prototypes for the image task, per-client Markov chains for the
character task), kept here so the benchmark's inputs do not change when the
program's generators do.  The character sampler draws a whole time step of
every sequence at once; it is not bit-identical to the program's per-symbol
loop, and need not be: both the program and the reference read these
arrays.

Every function returns host NumPy arrays: per-client train inputs and
labels, shared test inputs and labels.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class ClientData:
    train_x: list[np.ndarray]
    train_y: list[np.ndarray]
    test_x: np.ndarray
    test_y: np.ndarray

    @property
    def sizes(self) -> np.ndarray:
        return np.array([len(x) for x in self.train_x], np.float64)

    def weights(self) -> np.ndarray:
        """Aggregation weights p_n = |D_n| / sum |D_m|, as float32."""
        s = self.sizes
        return (s / s.sum()).astype(np.float32)

    def tiled(self) -> tuple[np.ndarray, np.ndarray]:
        """Client shards tiled to the largest one: (N, S_max, ...) arrays.

        The simulator trains full-batch on shards padded to a common size
        by repeating each client's own samples; the FLOP counters and the
        reference use the same tiled shards.
        """
        s_max = max(len(x) for x in self.train_x)

        def tile(a):
            reps = -(-s_max // len(a))
            return np.tile(a, (reps,) + (1,) * (a.ndim - 1))[:s_max]

        return (np.stack([tile(x) for x in self.train_x]),
                np.stack([tile(y) for y in self.train_y]))


def image_classification(*, n_clients: int, n_classes: int, shape: list[int],
                         samples_per_client: int, classes_per_client: int,
                         noise: float, test_samples: int,
                         seed: int) -> ClientData:
    """Label-skew classification: client n holds classes n, n+1, ... (mod
    the class count); class c is a Gaussian prototype plus noise.  Shard
    sizes are drawn from [samples/2, 3 samples/2) so the weights differ."""
    rng = np.random.default_rng(seed)
    d = int(np.prod(shape))
    protos = rng.normal(size=(n_classes, d)).astype(np.float32)
    sizes = rng.integers(samples_per_client // 2,
                         samples_per_client * 3 // 2, size=n_clients)

    def sample(cls, n):
        x = protos[cls] + noise * rng.normal(size=(n, d)).astype(np.float32)
        return x.reshape((n, *shape)), np.full(n, cls, np.int32)

    train_x, train_y = [], []
    for n in range(n_clients):
        classes = [(n + j) % n_classes for j in range(classes_per_client)]
        per = int(sizes[n]) // len(classes)
        xs, ys = zip(*(sample(c, per) for c in classes))
        train_x.append(np.concatenate(xs))
        train_y.append(np.concatenate(ys))
    per = test_samples // n_classes
    xs, ys = zip(*(sample(c, per) for c in range(n_classes)))
    return ClientData(train_x, train_y, np.concatenate(xs), np.concatenate(ys))


def _markov_chain(rng: np.random.Generator, vocab: int) -> np.ndarray:
    t = rng.gamma(0.3, size=(vocab, vocab))
    return t / t.sum(1, keepdims=True)


def _sample_sequences(rng: np.random.Generator, chain: np.ndarray,
                      n_seq: int, length: int) -> np.ndarray:
    """``n_seq`` walks of ``length`` symbols, one time step at a time."""
    vocab = chain.shape[0]
    cdf = np.cumsum(chain, axis=1)
    cdf[:, -1] = 1.0
    out = np.empty((n_seq, length), np.int32)
    out[:, 0] = rng.integers(vocab, size=n_seq)
    for j in range(1, length):
        u = rng.random(n_seq)
        nxt = (u[:, None] > cdf[out[:, j - 1]]).sum(axis=1)
        out[:, j] = np.minimum(nxt, vocab - 1)
    return out


def char_stream(*, n_clients: int, vocab: int, seq_len: int,
                sequences_per_client: int, test_sequences: int,
                seed: int) -> ClientData:
    """Next-character data, one Markov chain per client (the LEAF
    Shakespeare split is by speaking role, so clients differ); the test set
    comes from a shared chain.  Inputs are ``seq_len`` symbols, labels the
    same walk shifted by one."""
    rng = np.random.default_rng(seed)
    shared = _markov_chain(rng, vocab)
    train_x, train_y = [], []
    for n in range(n_clients):
        r = np.random.default_rng(seed + 1 + n)
        chain = _markov_chain(r, vocab)
        n_seq = int(r.integers(sequences_per_client // 2,
                               sequences_per_client * 3 // 2))
        seqs = _sample_sequences(r, chain, n_seq, seq_len + 1)
        train_x.append(seqs[:, :-1])
        train_y.append(seqs[:, 1:])
    r = np.random.default_rng(seed + 999)
    seqs = _sample_sequences(r, shared, test_sequences, seq_len + 1)
    return ClientData(train_x, train_y, seqs[:, :-1], seqs[:, 1:])
