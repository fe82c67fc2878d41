"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,l,k", [
    (2, 1, 128), (4, 8, 128), (10, 7, 256), (16, 16, 128), (3, 5, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ra_aggregate_matches_ref(n, l, k, dtype):
    key = jax.random.PRNGKey(n * 100 + l)
    ks = jax.random.split(key, 3)
    w = jax.random.normal(ks[0], (n, l, k)).astype(dtype)
    p = jax.nn.softmax(jax.random.normal(ks[1], (n,)))
    e = (jax.random.uniform(ks[2], (n, n, l)) < 0.7).astype(jnp.float32)
    e = jnp.maximum(e, jnp.eye(n)[:, :, None])
    got = ops.ra_aggregate(w, p, e)
    want = ref.ra_aggregate_ref(w, p, e)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


def test_ra_aggregate_block_sweep():
    key = jax.random.PRNGKey(0)
    n, l, k = 8, 300, 128
    ks = jax.random.split(key, 3)
    w = jax.random.normal(ks[0], (n, l, k))
    p = jnp.ones((n,)) / n
    e = (jax.random.uniform(ks[2], (n, n, l)) < 0.5).astype(jnp.float32)
    e = jnp.maximum(e, jnp.eye(n)[:, :, None])
    want = ref.ra_aggregate_ref(w, p, e)
    for bl in (128, 256, 384):   # 3 and 2 padded tiles, then one full-L tile
        got = ops.ra_aggregate(w, p, e, block_l=bl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,l,k", [
    (7, 11, 100),   # nothing aligned: odd N, prime L, K not a lane multiple
    (5, 3, 96),     # L < default block_l
    (13, 9, 192),   # odd N, L coprime with block_l
    (6, 10, 130),   # K not a multiple of 128
    (3, 1, 36),     # single segment
])
def test_ra_aggregate_golden_odd_shapes(n, l, k):
    """Kernel vs pure-jnp oracle in interpret mode on CPU across shapes
    where (N, L, K) are NOT multiples of the block size."""
    key = jax.random.PRNGKey(n * 1000 + l * 10 + k)
    ks = jax.random.split(key, 3)
    w = jax.random.normal(ks[0], (n, l, k))
    p = jax.nn.softmax(jax.random.normal(ks[1], (n,)))
    e = (jax.random.uniform(ks[2], (n, n, l)) < 0.6).astype(jnp.float32)
    e = jnp.maximum(e, jnp.eye(n)[:, :, None])
    want = ref.ra_aggregate_ref(w, p, e)
    for bl in (128, 256):
        got = ops.ra_aggregate(w, p, e, block_l=bl)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5,
            err_msg=f"block_l={bl}",
        )


@pytest.mark.parametrize("b,s,h,d", [
    (1, 32, 1, 16), (2, 64, 2, 32), (1, 128, 4, 64), (2, 96, 3, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan_matches_ref(b, s, h, d, dtype):
    key = jax.random.PRNGKey(b * 17 + s)
    ks = jax.random.split(key, 5)
    r = (jax.random.normal(ks[0], (b, s, h, d)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (b, s, h, d)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (b, s, h, d)) * 0.5).astype(dtype)
    w = (-jnp.exp(jax.random.normal(ks[3], (b, s, h, d)) * 0.5 - 1.0)).astype(
        jnp.float32
    )
    u = jax.random.normal(ks[4], (h, d)) * 0.3
    got = ops.rwkv6_scan(r, k, v, w, u, chunk=32)
    want = ref.rwkv6_scan_ref(r, k, v, w, u)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_rwkv6_chunk_sweep():
    key = jax.random.PRNGKey(7)
    b, s, h, d = 1, 96, 2, 32
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (b, s, h, d)) * 0.5
    k = jax.random.normal(ks[1], (b, s, h, d)) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, d)) * 0.5
    w = -jnp.exp(jax.random.normal(ks[3], (b, s, h, d)) * 0.5 - 1.0)
    u = jax.random.normal(ks[4], (h, d)) * 0.3
    want = ref.rwkv6_scan_ref(r, k, v, w, u)
    for chunk in (8, 16, 32, 48, 96):
        got = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_model_kernel_integration():
    """rwkv6_seq(use_kernel=True) == jnp reference path inside the model."""
    from repro.models import ssm as S

    cfg = S.RWKV6Cfg(d_model=64, n_heads=2)
    key = jax.random.PRNGKey(0)
    params = S.init_rwkv6(key, cfg)
    x = jax.random.normal(key, (2, 64, 64))
    a = S.rwkv6_seq(params, cfg, x, use_kernel=False)
    b = S.rwkv6_seq(params, cfg, x, use_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


@pytest.mark.parametrize("b,s,h,kv,dh", [
    (2, 64, 4, 2, 32), (1, 128, 8, 8, 64), (2, 96, 6, 2, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel_matches_ref(b, s, h, kv, dh, dtype):
    key = jax.random.PRNGKey(s)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, dh)).astype(dtype)
    got = ops.flash_attention(q, k, v, scale=dh**-0.5, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, scale=dh**-0.5)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_flash_attention_kernel_block_sweep():
    key = jax.random.PRNGKey(3)
    b, s, h, kv, dh = 1, 96, 4, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, kv, dh))
    v = jax.random.normal(ks[2], (b, s, kv, dh))
    want = ref.flash_attention_ref(q, k, v, scale=dh**-0.5)
    for bq, bk in ((16, 16), (32, 48), (96, 96), (48, 16)):
        got = ops.flash_attention(q, k, v, scale=dh**-0.5,
                                  block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
