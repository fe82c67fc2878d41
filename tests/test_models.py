"""Model zoo: train/serve smoke + decode-vs-forward equivalence per family."""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import registry
from repro.models import transformer as T
from repro.models.transformer import _block, _norm, _scan_layers


def tiny(family, **kw):
    base = dict(name=f"tiny-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab=97)
    base.update(kw)
    return T.ModelCfg(**base)


FAMILIES = [
    tiny("dense", qkv_bias=True),
    tiny("moe", n_experts=4, top_k=2, capacity_factor=8.0),
    tiny("ssm", rwkv_heads=4),
    tiny("hybrid"),
    tiny("enc_dec", n_enc_layers=2, enc_seq=8, norm="layernorm", act="gelu"),
    tiny("vlm", n_layers=4, cross_attn_every=2, n_modal_tokens=8),
]


def _batch(cfg, key, B=2, S=12):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if registry.needs_modal(cfg):
        t = cfg.enc_seq if cfg.family == "enc_dec" else cfg.n_modal_tokens
        batch["modal_embeds"] = jax.random.normal(key, (B, t, cfg.d_model))
    return batch


@pytest.mark.parametrize("cfg", FAMILIES, ids=lambda c: c.family)
def test_train_step_no_nan(cfg):
    key = jax.random.PRNGKey(0)
    bundle = registry.build(cfg, lr=1e-3)
    state = registry.init_state(bundle, key)
    batch = _batch(cfg, key)
    state2, metrics = jax.jit(bundle.train_step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    for leaf in jax.tree.leaves(state2["params"]):
        assert bool(jnp.isfinite(leaf).all())


@pytest.mark.parametrize("cfg", FAMILIES, ids=lambda c: c.family)
def test_loss_decreases(cfg):
    key = jax.random.PRNGKey(0)
    bundle = registry.build(cfg, optimizer="adamw", lr=3e-3)
    state = registry.init_state(bundle, key)
    batch = _batch(cfg, key)
    step = jax.jit(bundle.train_step)
    losses = []
    for _ in range(10):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("cfg", FAMILIES, ids=lambda c: c.family)
def test_decode_matches_forward(cfg):
    """Sequential serve_step == full forward (prefill path also checked)."""
    key = jax.random.PRNGKey(0)
    B, S = 2, 12
    bundle = registry.build(cfg)
    params = bundle.init(key)
    batch = _batch(cfg, key, B, S)
    tokens = batch["tokens"]
    kwargs = (
        {"modal_embeds": batch["modal_embeds"]} if registry.needs_modal(cfg) else {}
    )
    full_logits, _ = T.forward(params, cfg, tokens, **kwargs)

    # Prefill S-1 tokens, then decode the last one.
    pre_batch = dict(batch, tokens=tokens[:, : S - 1])
    last_pre, cache = bundle.prefill_step(params, pre_batch)
    np.testing.assert_allclose(
        np.asarray(last_pre), np.asarray(full_logits[:, S - 2]),
        atol=2e-3, rtol=1e-3,
    )

    # The prefill cache is sized S-1; decode needs one more slot.
    cache = _grow_cache(cfg, cache, S)
    lg, cache = bundle.serve_step(params, cache, tokens[:, S - 1:], jnp.int32(S - 1))
    np.testing.assert_allclose(
        np.asarray(lg[:, 0]), np.asarray(full_logits[:, S - 1]),
        atol=2e-3, rtol=1e-3,
    )


def _grow_cache(cfg, cache, new_len):
    def grow(path_leaf):
        return path_leaf

    out = dict(cache)
    for name in ("k", "v"):
        if name in cache:
            c = cache[name]
            pad = new_len - c.shape[-3]
            if pad > 0:
                widths = [(0, 0)] * c.ndim
                widths[-3] = (0, pad)
                out[name] = jnp.pad(c, widths)
    return out


def test_sliding_window_masks_old_tokens():
    cfg = tiny("dense")
    key = jax.random.PRNGKey(0)
    bundle = registry.build(cfg)
    params = bundle.init(key)
    tokens = jax.random.randint(key, (1, 10), 0, cfg.vocab)
    lw, _ = T.forward(params, cfg, tokens, window=4)
    lf, _ = T.forward(params, cfg, tokens)
    # early positions agree (window not yet binding), later differ
    np.testing.assert_allclose(np.asarray(lw[:, 1]), np.asarray(lf[:, 1]), atol=1e-4)
    assert float(jnp.max(jnp.abs(lw[:, -1] - lf[:, -1]))) > 1e-6


def test_moe_capacity_drops_change_output():
    cfg_lo = tiny("moe", n_experts=4, top_k=2, capacity_factor=0.5)
    cfg_hi = dc.replace(cfg_lo, capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    bundle_lo = registry.build(cfg_lo)
    params = bundle_lo.init(key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg_lo.vocab)
    lo, _ = T.forward(params, cfg_lo, tokens)
    hi, _ = T.forward(params, cfg_hi, tokens)
    assert float(jnp.max(jnp.abs(lo - hi))) > 1e-6


def test_scan_unroll_equivalence():
    """Unrolled scans (dry-run cost path) must match the scanned forward."""
    for cfg in (tiny("dense"), tiny("ssm", rwkv_heads=4)):
        key = jax.random.PRNGKey(0)
        bundle = registry.build(cfg)
        params = bundle.init(key)
        tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab)
        a, _ = T.forward(params, cfg, tokens)
        b, _ = T.forward(params, dc.replace(cfg, scan_unroll=True), tokens)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid"])
def test_bf16_dtype_discipline(family):
    """bf16 configs must keep scan carries dtype-stable (hymba regression)."""
    kw = {"dtype": jnp.bfloat16}
    if family == "moe":
        kw.update(n_experts=4, top_k=2)
    if family == "ssm":
        kw.update(rwkv_heads=4)
    cfg = tiny(family, **kw)
    key = jax.random.PRNGKey(0)
    bundle = registry.build(cfg)
    params = bundle.init(key)
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab)
    logits, _ = T.forward(params, cfg, tokens)
    assert bool(jnp.isfinite(logits).all())
    cache = bundle.init_cache(2, 8)
    lg, new_cache = bundle.serve_step(params, cache, tokens[:, :1], jnp.int32(0))
    assert bool(jnp.isfinite(lg).all())
    for a, b in zip(jax.tree.leaves(new_cache), jax.tree.leaves(cache)):
        assert a.dtype == b.dtype, (a.dtype, b.dtype)


def test_chunked_attention_matches_naive():
    """§Perf: online-softmax chunked attention == naive attention."""
    cfg_n = tiny("dense")
    cfg_c = dc.replace(cfg_n, attn_impl="chunked", attn_chunk=4)
    key = jax.random.PRNGKey(0)
    bundle = registry.build(cfg_n)
    params = bundle.init(key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg_n.vocab)
    a, _ = T.forward(params, cfg_n, tokens)
    b, _ = T.forward(params, cfg_c, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                               rtol=1e-4)
    # with sliding window too
    aw, _ = T.forward(params, cfg_n, tokens, window=6)
    bw, _ = T.forward(params, cfg_c, tokens, window=6)
    np.testing.assert_allclose(np.asarray(aw), np.asarray(bw), atol=2e-4,
                               rtol=1e-4)


def test_chunked_loss_matches_full():
    """§Perf: vocab-chunked CE == full-logits CE (value and gradient)."""
    cfg_f = tiny("dense")
    cfg_c = dc.replace(cfg_f, loss_vocab_chunk=13)  # non-divisor of 97
    key = jax.random.PRNGKey(0)
    b_f = registry.build(cfg_f)
    b_c = registry.build(cfg_c)
    params = b_f.init(key)
    batch = {"tokens": jax.random.randint(key, (2, 12), 0, cfg_f.vocab)}
    lf, _ = b_f.loss_fn(params, batch)
    lc, _ = b_c.loss_fn(params, batch)
    np.testing.assert_allclose(float(lf), float(lc), rtol=1e-5)
    gf = jax.grad(lambda p: b_f.loss_fn(p, batch)[0])(params)
    gc = jax.grad(lambda p: b_c.loss_fn(p, batch)[0])(params)
    for x, y in zip(jax.tree.leaves(gf), jax.tree.leaves(gc)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-4)


def test_flash_attention_matches_naive():
    """§Perf: flash (custom-vjp) attention == naive, values AND grads."""
    cfg_n = tiny("dense")
    cfg_f = dc.replace(cfg_n, attn_impl="flash", attn_chunk=4)
    key = jax.random.PRNGKey(0)
    bundle = registry.build(cfg_n)
    params = bundle.init(key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg_n.vocab)

    def loss(p, c):
        logits, _ = T.forward(p, c, tokens)
        return registry.cross_entropy(logits[:, :-1], tokens[:, 1:])

    ln, gn = jax.value_and_grad(lambda p: loss(p, cfg_n))(params)
    lf, gf = jax.value_and_grad(lambda p: loss(p, cfg_f))(params)
    np.testing.assert_allclose(float(ln), float(lf), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gn), jax.tree.leaves(gf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    # windowed variant
    lwn = loss(params, dc.replace(cfg_n, sliding_window=None))
    for w in (None, 6):
        a, _ = T.forward(params, cfg_n, tokens, window=w)
        b, _ = T.forward(params, cfg_f, tokens, window=w)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-4)


def test_sim_registry_all_models():
    """Registry self-test: every registered sim model (smallnets + NWP
    transformers for every decoder-only configs/ arch) instantiates and
    runs one forward pass at tiny size, with a stable unique model_id."""
    key = jax.random.PRNGKey(0)
    inputs = {
        "cnn": np.zeros((2, 28, 28, 1), np.float32),
        "resnet": np.zeros((2, 8, 8, 3), np.float32),
        "mlp": np.zeros((2, 32), np.float32),
    }
    seen_ids = set()
    names = registry.sim_models()
    assert "transformer_nwp" in names
    assert any(n.startswith("nwp:") for n in names)
    for name in names:
        m = registry.sim_model(name, vocab=90)
        assert m.model_id not in seen_ids
        seen_ids.add(m.model_id)
        assert m.model_id == registry.SIM_MODEL_IDS[name]
        x = jnp.asarray(inputs.get(name, np.zeros((2, 8), np.int32)))
        out = m.apply_fn(m.init_fn(key), x)
        if name in inputs:
            assert out.shape == (2, 10)
        else:
            assert out.shape == (2, 8, 90)      # (B, S, vocab) logits
        assert np.isfinite(np.asarray(out, np.float32)).all()
    with pytest.raises(ValueError, match="unknown sim model"):
        registry.sim_model("not-a-model")
    with pytest.raises(ValueError, match="decoder-only"):
        registry.nwp_cfg("whisper_base")


_CONV_CASES = [  # hw, cin, cout, k, stride, atol
    (7, 2, 3, 3, 1, 1e-5),      # the CNN's 3x3 SAME convs
    (8, 3, 4, 3, 2, 1e-5),      # ResNet's strided stage entry
    (9, 2, 5, 1, 2, 1e-5),      # ResNet's 1x1 strided projection
    # The CNN's two layers at full width: gradients of up to ~60 summed
    # over 2 * 28 * 28 terms, so float32 summation order shows at 3e-5.
    (28, 1, 32, 3, 1, 1e-4),    # conv1: per-tap weight gradient
    (14, 32, 64, 3, 1, 1e-4),   # conv2: one-convolution weight gradient
]


@pytest.mark.parametrize("hw,cin,cout,k,stride,atol", _CONV_CASES,
                         ids=["-".join(map(str, c[:5])) for c in _CONV_CASES])
def test_conv2d_vjp_matches_native(hw, cin, cout, k, stride, atol):
    """The custom weight gradient (per tap where cin == 1, one convolution
    otherwise) equals JAX's transposed convolution."""
    from repro.models import smallnets

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (2, hw, hw, cin))
    w = jax.random.normal(kw, (k, k, cin, cout))

    def loss(conv):
        return lambda x_, w_: jnp.sum(jnp.sin(conv(x_, w_, stride)))

    got = jax.grad(loss(smallnets.conv2d), (0, 1))(x, w)
    want = jax.grad(loss(smallnets._conv), (0, 1))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=atol)


@pytest.mark.parametrize("cin", [1, 3])   # per-tap and convolution paths
def test_conv2d_grad_batches_under_shard_map(cin):
    """Scenarios x clients vmap of a conv weight gradient inside shard_map
    (the sharded scenario grid): JAX's own transposed convolution raises
    NotImplementedError there."""
    from jax.sharding import PartitionSpec as P

    from repro.models import smallnets

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("grid",))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 5, 5, cin))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 3, 3, cin, 2))

    def grid(conv):
        def loss(w_, x_):
            return jnp.sum(conv(x_, w_, 1) ** 2)
        return lambda ws: jax.vmap(
            lambda wg: jax.vmap(jax.grad(loss))(wg, x))(ws)

    sharded = jax.shard_map(grid(smallnets.conv2d), mesh=mesh,
                            in_specs=P("grid"), out_specs=P("grid"),
                            check_vma=False)
    got = jax.jit(sharded)(w)
    want = jax.jit(grid(smallnets._conv))(w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_cnn_weight_gradient_path_per_layer():
    """Which CNN layers take the one-convolution weight gradient, as the
    scenarios x clients program lowers it: conv2 (cin 32) one convolution
    and no per-tap contractions, conv1 (cin 1) its nine taps, and every
    convolution at float32 "highest"."""
    import re

    from repro.fl.simulator import _float32_matmuls
    from repro.models import smallnets

    n_scen, n_clients, batch = 2, 3, 4
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l, (n_scen, n_clients) + l.shape),
        smallnets.init_cnn(jax.random.PRNGKey(0)))
    x = jnp.zeros((n_scen, n_clients, batch, 28, 28, 1))

    def loss(p, x_):
        return jnp.sum(smallnets.apply_cnn(p, x_))

    grads = jax.vmap(jax.vmap(jax.grad(loss)))
    text = jax.jit(_float32_matmuls(grads)).lower(params, x).as_text()
    convs = [l for l in text.splitlines() if "stablehlo.convolution" in l]
    dots = [l for l in text.splitlines() if "stablehlo.dot_general" in l]
    # Samples as features of x and g, taps as the output's spatial dims.
    dw_convs = [l for l in convs if "[f, 0, 1, b]x[i, 0, 1, o]->[0, 1, b, f]" in l]
    assert len(dw_convs) == 1
    assert re.search(r"-> tensor<3x3x\d+x\d+xf32>", dw_convs[0])
    # conv2's taps would be (scenario, client, 32, 64) contractions.
    assert not [l for l in dots
                if f"-> tensor<{n_scen}x{n_clients}x32x64xf32>" in l]
    assert len([l for l in dots
                if f"-> tensor<{n_scen}x{n_clients}x1x32xf32>" in l]) == 9
    assert convs and all(
        "precision_config = [#stablehlo<precision HIGHEST>, "
        "#stablehlo<precision HIGHEST>]" in l for l in convs)


def test_resnet_default_tree_is_kept():
    """Without the stage, width and GroupNorm keywords `init_resnet`
    builds the tree it always has: three stages at width x (1, 2, 4), a
    projection where the width changes, no norms, and keys from one split
    of 4 + 2 * blocks + stages in layer order."""
    from repro.models import smallnets

    key = jax.random.PRNGKey(0)
    p = smallnets.init_resnet(key, depth=18, width=8)
    assert sorted(p) == ["fc", "stage0", "stage1", "stage2", "stem"]
    assert [sorted(b) for s in ("stage0", "stage1", "stage2")
            for b in p[s]] == [["conv1", "conv2"]] * 2 + [
                ["conv1", "conv2", "proj"], ["conv1", "conv2"]] * 2
    assert p["stage2"][0]["proj"].shape == (1, 1, 16, 32)
    assert sum(l.size for l in jax.tree.leaves(p)) == 43_810
    ks = jax.random.split(key, 4 + 2 * 6 + 3)
    np.testing.assert_array_equal(p["stem"], smallnets._conv_init(ks[0], 3, 3, 3, 8))
    np.testing.assert_array_equal(p["stage1"][0]["proj"],
                                  smallnets._conv_init(ks[7], 1, 1, 8, 16))
    np.testing.assert_array_equal(p["fc"]["w"],
                                  smallnets._fc_init(ks[15], 32, 10)["w"])


def test_resnet_scopes_name_stem_stages_and_group_norm():
    """The stem, each stage and each GroupNorm are named scopes in the
    compiled training step, so its time splits by stage offline."""
    import re

    from repro.models import smallnets

    p = smallnets.init_resnet(jax.random.PRNGKey(0), stage_blocks=(1, 1, 1, 1),
                              stage_widths=(4, 8, 8, 16), gn_groups=2)
    x = jnp.zeros((2, 8, 8, 3))
    step = jax.jit(jax.grad(lambda p_: jnp.sum(smallnets.apply_resnet(p_, x))))
    names = re.findall(r'op_name="([^"]*)"',
                       step.lower(p).compile().as_text())
    # A transformation wraps the outer scope: jvp(resnet)/stage0/...
    for part in ["stem", "stage0", "stage1", "stage2", "stage3"]:
        assert any(re.search(rf"(\w+\()*resnet\)*/{part}/", n)
                   for n in names), part
    assert any(re.search(r"resnet\)*/stage\d/group_norm/", n) for n in names)


def test_resnet_weight_gradient_path_per_layer():
    """Every ResNet-18-GN convolution takes the one-convolution weight
    gradient as the scenarios x clients program lowers it: the stem (cin 3;
    on a v5e 1.9 ms against 9.0 ms per tap for 20 models x 65 images at
    32x32), the sixteen 3x3 block convolutions (stride 2 at each later
    stage's entry, as rhs dilation 2) and the three 1x1 projections; no
    per-tap contractions, and every convolution at float32 "highest"."""
    import re

    from repro.fl.simulator import _float32_matmuls
    from repro.models import smallnets

    n_scen, n_clients, batch = 2, 3, 4
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l, (n_scen, n_clients) + l.shape),
        smallnets.init_resnet(jax.random.PRNGKey(0), stage_blocks=(2, 2, 2, 2),
                              stage_widths=(8, 16, 32, 64), gn_groups=2))
    x = jnp.zeros((n_scen, n_clients, batch, 8, 8, 3))

    def loss(p, x_):
        return jnp.sum(smallnets.apply_resnet(p, x_))

    grads = jax.vmap(jax.vmap(jax.grad(loss)))
    text = jax.jit(_float32_matmuls(grads)).lower(params, x).as_text()
    convs = [l for l in text.splitlines() if "stablehlo.convolution" in l]
    dots = [l for l in text.splitlines() if "stablehlo.dot_general" in l]
    dw = [l for l in convs if "[f, 0, 1, b]x[i, 0, 1, o]->[0, 1, b, f]" in l]
    m = n_scen * n_clients
    kernels = sorted((re.search(r"-> tensor<(\d+)x\d+x(\d+)x(\d+)xf32>", l).groups(),
                      re.search(r"rhs_dilate = \[(\d), \d\]", l).group(1))
                     for l in dw)
    want = ([(("1", str(c), str(2 * c * m)), "2") for c in (8, 16, 32)]
            + [(("3", "3", str(8 * m)), "1")]
            + [(("3", str(c), str(c * m)), "1") for c in (8, 16, 32, 64)
               for _ in range(4 if c == 8 else 3)]
            + [(("3", str(c), str(2 * c * m)), "2") for c in (8, 16, 32)])
    assert kernels == sorted(want)
    # No per-tap contractions: the only products are the output layer's
    # two gradients.
    assert len(dots) == 2
    assert convs and all(
        "precision_config = [#stablehlo<precision HIGHEST>, "
        "#stablehlo<precision HIGHEST>]" in l for l in convs)


@pytest.mark.parametrize("stem_width,remat", [(16, False), (64, True)])
def test_resnet_rematerialises_blocks_from_the_published_width(stem_width, remat):
    """Blocks are recomputed in the backward pass only from a stem of
    ResNet-18's 64 channels up; the default width-16 net keeps its
    activations and traces no rematerialisation."""
    from repro.models import smallnets

    widths = tuple(stem_width * 2**s for s in range(3))
    p = smallnets.init_resnet(jax.random.PRNGKey(0), stage_blocks=(1, 1, 1),
                              stage_widths=widths)
    x = jnp.zeros((2, 8, 8, 3))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p_: jnp.sum(smallnets.apply_resnet(p_, x))))(p)
    names = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    assert ("remat2" in names) == remat
