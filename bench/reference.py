"""Plain float32 reference of one R&A D-FL scenario, and the comparison.

This module imports nothing of the program and takes nothing it made.  It
follows the semantics the simulator documents (paper Sec. III and V):

* every client starts from the same model, initialised from
  ``PRNGKey(seed)``; round t uses the second half of ``split(key)`` and
  carries the first half on;
* local training is full-batch gradient descent, ``local_epochs`` steps
  of ``w - lr * grad``, on the client's shard tiled to the largest shard;
* the model is a float32 vector in the leaf order of the parameter tree
  (sorted keys, row-major), cut into segments of ``seg_len`` values, the
  last one zero-padded;
* R&A: each segment of sender m reaches receiver n when a uniform draw
  ``U(k_round, (N, N, L))`` lies below the min-PER route's success
  probability rho[m, n] (Floyd-Warshall on -log link success; own model
  always present); receivers aggregate by eq. 6 (``ra_normalized``) or by
  substitution of their own segment;
* AaYG: one-hop mixes over the client block of the link matrix, each with
  its own key from ``split(k_round, mixes)``;
* ideal C-FL: the error-free weighted average;
* after each round: test accuracy of every client's model, its train loss
  on its tiled shard, and (R&A) the mean over segments of
  ||Lambda_l||_F^2 (NaN for AaYG, 0 for ideal C-FL).

Matrix products and convolutions run at float32 "highest" precision, the
precision the configuration states.  `Matmuls("bf16_3x")` is the control:
the same products from operands split into two bfloat16 parts, with the
low-times-low term dropped, in the forward and the backward pass — what
XLA's "high" precision computes on a TPU, written out so that it is the
same computation on any backend.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PROTOCOLS = ("ra", "aayg", "ideal_cfl")
MODES = ("ra_normalized", "substitution")
_EPS = 1e-12
BIAS_FLOOR = 1e-8      # below this the bias statistic is rounding noise
LOSS_FLOOR = 1e-2      # nats; a loss under it is compared by its absolute gap


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _three_pass(f, a, b):
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bilinear_3x(f, a, b):
    return _three_pass(f, a, b)


def _bilinear_3x_fwd(f, a, b):
    return _three_pass(f, a, b), (a, b)


def _bilinear_3x_bwd(f, res, g):
    a, b = res

    def grad_a(g_, b_):
        return jax.vjp(lambda a_: f(a_, b_), a)[1](g_)[0]

    def grad_b(a_, g_):
        return jax.vjp(lambda b_: f(a_, b_), b)[1](g_)[0]

    return _three_pass(grad_a, g, b), _three_pass(grad_b, a, g)


_bilinear_3x.defvjp(_bilinear_3x_fwd, _bilinear_3x_bwd)


def _matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _conv_same(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)


class Matmuls:
    """The reference's matrix products at one precision."""

    PRECISIONS = ("highest", "bf16_3x")

    def __init__(self, precision: str = "highest"):
        if precision not in self.PRECISIONS:
            raise ValueError(f"precision must be one of {self.PRECISIONS}")
        self.precision = precision

    def _run(self, f, a, b):
        if self.precision == "highest":
            return f(a, b)
        return _bilinear_3x(f, a, b)

    def dot(self, a, b):
        return self._run(_matmul, a, b)

    def conv(self, x, w):
        return self._run(_conv_same, x, w)


def ce_loss(logits, labels):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def accuracy(logits, labels):
    return jnp.mean(jnp.argmax(logits, -1) == labels)


def min_per_success(link_eps: np.ndarray) -> jnp.ndarray:
    """rho[m, n]: success probability of the min-E2E-PER route (eq. 5)."""
    eps = jnp.asarray(link_eps, jnp.float32)
    v = eps.shape[0]
    tiny = jnp.finfo(jnp.float32).tiny
    cost = jnp.where(eps > 0.0, -jnp.log(jnp.clip(eps, tiny, 1.0)), jnp.inf)
    dist = jnp.where(jnp.eye(v, dtype=bool), 0.0, cost)

    def relax(k, d):
        return jnp.minimum(d, d[:, k, None] + d[None, k, :])

    dist = jax.lax.fori_loop(0, v, relax, dist)
    return jnp.where(jnp.isfinite(dist), jnp.exp(-dist), 0.0)


class Reference:
    """One configuration's reference: model, data, network, statics.

    ``model`` is the configuration's module (`init(key, widths)`,
    `apply(params, x, mm, widths)`); ``sim`` holds seg_len, n_rounds,
    local_epochs, lr, aayg_mixes.  Local training and evaluation are each
    one jitted program shared by every protocol, with the data passed as
    arguments; only the small exchange step is compiled per (protocol,
    mode).
    """

    def __init__(self, model, widths: dict, sim: dict, data, link_eps,
                 precision: str = "highest"):
        self.mm = Matmuls(precision)
        self.widths = widths
        self.model = model
        self.n = len(data.train_x)
        self.rounds = int(sim["n_rounds"])
        self.epochs = int(sim["local_epochs"])
        self.lr = np.float32(sim["lr"])
        self.mixes = int(sim["aayg_mixes"])
        self.seg_len = int(sim["seg_len"])
        xs, ys = data.tiled()
        self.data = tuple(jnp.asarray(a) for a in
                          (xs, ys, data.test_x, data.test_y))
        self.p = jnp.asarray(data.weights())
        self.eps = jnp.asarray(link_eps, jnp.float32)[:self.n, :self.n]
        self.rho = jax.jit(min_per_success)(link_eps)[:self.n, :self.n]
        shapes = jax.eval_shape(lambda k: model.init(k, widths),
                                jax.random.PRNGKey(0))
        leaves, self.treedef = jax.tree_util.tree_flatten(shapes)
        self.shapes = [tuple(l.shape) for l in leaves]
        self.m = int(sum(np.prod(s) for s in self.shapes))
        self.segments = -(-self.m // self.seg_len)
        self._exchanges: dict[tuple[str, str], Callable] = {}
        self._init = jax.jit(self._init_stack)
        self._train_fn = jax.jit(self._train)
        self._metrics_fn = jax.jit(self._metrics)

    # -- pieces -------------------------------------------------------
    def _init_stack(self, key):
        params = self.model.init(key, self.widths)
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (self.n,) + l.shape), params)

    def _loss(self, params, x, y):
        return ce_loss(self.model.apply(params, x, self.mm, self.widths), y)

    def _train(self, stack, xs, ys):
        def one(params, x, y):
            for _ in range(self.epochs):
                g = jax.grad(self._loss)(params, x, y)
                params = jax.tree.map(lambda w, d: w - self.lr * d, params, g)
            return params

        return jax.vmap(one)(stack, xs, ys)

    def _metrics(self, stack, xs, ys, test_x, test_y):
        def one(params, x, y):
            logits = self.model.apply(params, test_x, self.mm, self.widths)
            return accuracy(logits, test_y), self._loss(params, x, y)

        return jax.vmap(one)(stack, xs, ys)

    def _to_segments(self, stack):
        flat = jnp.concatenate(
            [l.reshape(self.n, -1) for l in jax.tree_util.tree_leaves(stack)],
            axis=1)
        pad = self.segments * self.seg_len - self.m
        return jnp.pad(flat, ((0, 0), (0, pad))).reshape(
            self.n, self.segments, self.seg_len)

    def _from_segments(self, w):
        flat = w.reshape(self.n, -1)[:, :self.m]
        out, at = [], 0
        for s in self.shapes:
            size = int(np.prod(s))
            out.append(flat[:, at:at + size].reshape((self.n,) + s))
            at += size
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def _aggregate(self, mode, w, e):
        ef = e.astype(jnp.float32)                            # (m, n, l)
        coeff = self.p[:, None, None] * ef
        num = jnp.einsum("mnl,mlk->nlk", coeff, w, precision=HIGHEST)
        mass = coeff.sum(axis=0)                              # (n, l)
        if mode == "ra_normalized":
            return num / jnp.maximum(mass, _EPS)[:, :, None]
        return num + (self.p.sum() - mass)[:, :, None] * w

    def _bias(self, e):
        w = self.p[:, None, None] * e.astype(jnp.float32)
        coeff = w / jnp.maximum(w.sum(axis=0, keepdims=True), _EPS)
        lam = self.p[:, None, None] - coeff
        return jnp.mean(jnp.sum(lam * lam, axis=(0, 1)))

    def _own(self):
        return jnp.eye(self.n, dtype=bool)[:, :, None]

    def _exchange(self, protocol, mode, stack, k_round):
        """The round's exchange of the trained models: (stack, bias)."""
        w = self._to_segments(stack)
        shape = (self.n, self.n, self.segments)
        if protocol == "ra":
            u = jax.random.uniform(k_round, shape)
            e = (u < self.rho[:, :, None]) | self._own()
            bias = self._bias(e)
            w = self._aggregate(mode, w, e)
        elif protocol == "aayg":
            for k in jax.random.split(k_round, self.mixes):
                u = jax.random.uniform(k, shape)
                e = (u < self.eps[:, :, None]) | self._own()
                w = self._aggregate(mode, w, e)
            bias = jnp.float32(jnp.nan)
        else:
            g = jnp.einsum("m,mlk->lk", self.p, w, precision=HIGHEST)
            w = jnp.broadcast_to(g[None], w.shape)
            bias = jnp.float32(0.0)
        return self._from_segments(w), bias

    # -- entry --------------------------------------------------------
    def run(self, seed: int, protocol: str, mode: str) -> dict:
        """Per-round ``acc`` (R, N), ``loss`` (R, N) and ``bias`` (R,)."""
        if protocol not in PROTOCOLS or mode not in MODES:
            raise ValueError(f"no reference for {protocol}+{mode}")
        exchange = self._exchanges.get((protocol, mode))
        if exchange is None:
            exchange = self._exchanges[(protocol, mode)] = jax.jit(
                partial(self._exchange, protocol, mode))
        xs, ys, test_x, test_y = self.data
        key = jax.random.PRNGKey(np.int32(seed))
        stack = self._init(key)
        accs, losses, biases = [], [], []
        for _ in range(self.rounds):
            key, k_round = jax.random.split(key)
            stack, bias = exchange(self._train_fn(stack, xs, ys), k_round)
            acc, loss = self._metrics_fn(stack, xs, ys, test_x, test_y)
            accs.append(np.asarray(acc))
            losses.append(np.asarray(loss))
            biases.append(float(bias))
        return {"acc": np.stack(accs), "loss": np.stack(losses),
                "bias": np.asarray(biases, np.float32)}


def gaps(got: dict, want: dict) -> dict[str, float]:
    """The numbers compared for one scenario.

    ``loss_gap``: the largest gap of a client's train loss in any round,
    relative to the reference's loss or to `LOSS_FLOOR` where that is
    smaller (a client that has fitted its one-class shard reaches a loss
    of 0 in float32).  ``acc_gap``: the largest gap of a
    client's test accuracy in any round.  ``bias_gap``: the largest gap of
    the round's R&A bias statistic relative to the reference's, or to
    `BIAS_FLOOR` where the statistic is smaller (a round in which every
    segment arrived has bias 0, which each side computes to within
    rounding); 0 where the protocol defines none.
    """
    loss = (np.abs(got["loss"] - want["loss"])
            / np.maximum(np.abs(want["loss"]), LOSS_FLOOR))
    acc = np.abs(got["acc"] - want["acc"])
    b_got, b_want = np.asarray(got["bias"]), np.asarray(want["bias"])
    defined = np.isfinite(b_want)
    bias = (np.abs(b_got[defined] - b_want[defined])
            / np.maximum(np.abs(b_want[defined]), BIAS_FLOOR)
            if defined.any() else np.zeros(1))
    # A NaN on the program's side where the reference has a number (or the
    # reverse) is a gap of its own kind: report it as infinite.
    mismatch = np.isfinite(b_got) != np.isfinite(b_want)
    worst = [float(np.max(loss)), float(np.max(acc)), float(np.max(bias))]
    worst = [w if np.isfinite(w) else float("inf") for w in worst]
    return {"loss_gap": worst[0], "acc_gap": worst[1],
            "bias_gap": float("inf") if mismatch.any() else worst[2]}
