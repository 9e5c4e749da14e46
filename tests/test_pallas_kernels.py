"""Pallas kernel correctness vs reference math (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    from ray_tpu.ops.pallas import flash_attention_pallas
    from ray_tpu.ops.pallas.flash_attention import _reference

    q = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64), jnp.float32)
    out = flash_attention_pallas(q, k, v, None, causal, 64, 64)
    ref = _reference(q, k, v, 1.0 / 8.0, causal)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_flash_attention_grad():
    from ray_tpu.ops.pallas import flash_attention_pallas
    from ray_tpu.ops.pallas.flash_attention import _reference

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 32), jnp.float32)
    gp = jax.grad(lambda q: jnp.sum(flash_attention_pallas(q, k, v, None, True, 32, 32)))(q)
    gr = jax.grad(lambda q: jnp.sum(_reference(q, k, v, 1.0 / (32 ** 0.5), True)))(q)
    np.testing.assert_allclose(gp, gr, rtol=1e-4, atol=1e-4)


def test_int8_quant_roundtrip():
    from ray_tpu.ops.pallas import dequantize_int8, quantize_int8

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 256), jnp.float32)
    values, scales = quantize_int8(x)
    assert values.dtype == jnp.int8
    assert scales.shape == (4, 32, 1)
    back = dequantize_int8(values, scales, jnp.float32)
    # int8 roundtrip error bounded by scale/2 per element
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.asarray(scales) * 0.51
    assert (err <= bound).all()


def test_flash_attention_kv_cache_decode():
    """sq != sk: causal offset must align query window to end of keys."""
    from ray_tpu.ops.pallas import flash_attention_pallas
    from ray_tpu.ops.pallas.flash_attention import _reference

    q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 200, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 200, 32), jnp.float32)
    out = flash_attention_pallas(q, k, v, None, True, 4, 64)
    ref = _reference(q, k, v, 1.0 / (32 ** 0.5), True)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_flash_attention_ragged_key_tail():
    """sk not a multiple of block_k: padded key columns must be masked."""
    from ray_tpu.ops.pallas import flash_attention_pallas
    from ray_tpu.ops.pallas.flash_attention import _reference

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 50, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 50, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 50, 32), jnp.float32)
    out = flash_attention_pallas(q, k, v, None, False, 32, 32)
    ref = _reference(q, k, v, 1.0 / (32 ** 0.5), False)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_fused_backward_all_grads(causal):
    """The fused dq/dk/dv Pallas backward must match reference-math grads."""
    from ray_tpu.ops.pallas import flash_attention_pallas
    from ray_tpu.ops.pallas.flash_attention import _reference

    q = jax.random.normal(jax.random.PRNGKey(0), (2, 96, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 96, 64), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(3), (2, 96, 64), jnp.float32)
    scale = 1.0 / 8.0

    def loss_p(q, k, v):
        return jnp.sum(flash_attention_pallas(q, k, v, scale, causal, 32, 32) * g)

    def loss_r(q, k, v):
        return jnp.sum(_reference(q, k, v, scale, causal) * g)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_attention_backward_ragged_and_cache():
    """Backward with sq != sk (decode windows) and non-multiple-of-block
    key lengths: padded rows/cols must contribute zero gradient."""
    from ray_tpu.ops.pallas import flash_attention_pallas
    from ray_tpu.ops.pallas.flash_attention import _reference

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 150, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 150, 32), jnp.float32)
    scale = 1.0 / (32 ** 0.5)

    gp = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention_pallas(q, k, v, scale, True, 32, 64)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        _reference(q, k, v, scale, True)), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grouped_heads_ragged_tail_fwd_bwd(causal):
    """Grouped heads AND a key length that is no multiple of the block, in
    one call, as `ops.attention.attention` drives the kernel: K/V heads are
    repeated in front of it, so dk/dv of one K/V head are the sums over its
    group of query heads, and the padded tail gives nothing to either."""
    from ray_tpu.ops.attention import _repeat_kv, causal_attention_reference
    from ray_tpu.ops.pallas import flash_attention_pallas

    b, hq, hkv, s, d = 2, 4, 2, 50, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    g = jax.random.normal(ks[3], (b, hq, s, d), jnp.float32)
    scale = d ** -0.5

    def kernel(q, k, v):
        kr, vr = (_repeat_kv(t, hq // hkv).reshape(b * hq, s, d) for t in (k, v))
        out = flash_attention_pallas(q.reshape(b * hq, s, d), kr, vr, scale,
                                     causal, 32, 32)
        return out.reshape(b, hq, s, d)

    def reference(q, k, v):
        return causal_attention_reference(
            q, _repeat_kv(k, hq // hkv), _repeat_kv(v, hq // hkv),
            sm_scale=scale, causal=causal)

    np.testing.assert_allclose(kernel(q, k, v), reference(q, k, v),
                               rtol=1e-4, atol=1e-4)
    gp = jax.grad(lambda *a: jnp.sum(kernel(*a) * g), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(reference(*a) * g), argnums=(0, 1, 2))(q, k, v)
    for a, bb, name in zip(gp, gr, "qkv"):
        assert a.shape == bb.shape
        np.testing.assert_allclose(a, bb, rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name} mismatch")


def _ffn_ref_block(x, nw, wg, wu, wd, eps=1e-5):
    """The plain block `ffn_block` is compared with (autodiff backward)."""
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    h = (xf * rstd * nw.astype(jnp.float32)).astype(x.dtype)
    gate, up = h @ wg, h @ wu
    s = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return x + (s @ wd).astype(x.dtype)


def _ffn_operands(seed, T, d, dff):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (2, T // 2, d), jnp.float32),
            1 + 0.1 * jax.random.normal(ks[1], (d,), jnp.float32),
            jax.random.normal(ks[2], (d, dff), jnp.float32) * d ** -0.5,
            jax.random.normal(ks[3], (d, dff), jnp.float32) * d ** -0.5,
            jax.random.normal(ks[4], (dff, d), jnp.float32) * dff ** -0.5)


def test_fused_ffn_block_matches_reference():
    """ffn_block (hand-written backward) vs plain-jnp block: forward and
    every gradient leaf (interpret mode on CPU)."""
    from ray_tpu.ops.pallas.fused_ffn import ffn_block

    args = _ffn_operands(0, 512, 256, 512)
    np.testing.assert_allclose(ffn_block(*args), _ffn_ref_block(*args),
                               rtol=1e-5, atol=1e-5)

    def lp(*a):
        return jnp.sum(ffn_block(*a).astype(jnp.float32) ** 2)

    def lr(*a):
        return jnp.sum(_ffn_ref_block(*a).astype(jnp.float32) ** 2)

    gp = jax.grad(lp, argnums=(0, 1, 2, 3, 4))(*args)
    gr = jax.grad(lr, argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(["dx", "dnw", "dwg", "dwu", "dwd"], gp, gr):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("T,d,dff,kernel", [
    (1024, 256, 512, True),    # every dimension tiles by the 512 blocks
    (520, 64, 64, False),      # T leaves 8 modulo 512
    (8, 520, 64, False),       # d leaves 8
    (8, 64, 768, False),       # dff leaves 256 (ADVICE.md's widths)
])
def test_fused_ffn_backward_is_chosen_by_shape(T, d, dff, kernel):
    """The dW_gate/dW_up step is the Pallas kernel where (T, d, dff) tile
    by its blocks and its XLA expression where they do not (such shapes
    raised "must tile" before); either way all five gradients are the
    plain block's. Nothing is set: the shapes decide."""
    from ray_tpu.ops.pallas.fused_ffn import ffn_block

    args = _ffn_operands(1, T, d, dff)

    def grads(block):
        return jax.grad(lambda *a: jnp.sum(block(*a).astype(jnp.float32) ** 2),
                        argnums=(0, 1, 2, 3, 4))

    assert ("pallas_call" in str(jax.make_jaxpr(grads(ffn_block))(*args))) == kernel
    for name, a, b in zip(["dx", "dnw", "dwg", "dwu", "dwd"],
                          grads(ffn_block)(*args), grads(_ffn_ref_block)(*args)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


def test_fused_ffn_in_transformer_forward():
    """cfg.fused_ffn=True matches the stock layer path end to end (tiny
    shapes that satisfy the kernel's tiling divide the 512 blocks evenly
    via the min() clamps)."""
    import dataclasses

    from ray_tpu.models.transformer import ModelConfig, init_params, loss_fn

    cfg = ModelConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=256, max_seq_len=256,
                      dtype=jnp.float32, remat="dots")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 512)
    batch = {"tokens": tokens}

    loss_ref, _ = loss_fn(params, batch, cfg)
    cfg_f = dataclasses.replace(cfg, fused_ffn=True)
    loss_fused, _ = loss_fn(params, batch, cfg_f)
    np.testing.assert_allclose(float(loss_fused), float(loss_ref), rtol=1e-5)

    g_ref = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    g_fused = jax.grad(lambda p: loss_fn(p, batch, cfg_f)[0])(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4),
        g_ref, g_fused)


def test_fused_attn_block_in_transformer():
    """cfg.fused_attn=True (+fused_ffn) matches the stock layer end to end,
    loss and every gradient leaf (reference einsum path on CPU)."""
    import dataclasses

    from ray_tpu.models.transformer import ModelConfig, init_params, loss_fn

    cfg = ModelConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=256, max_seq_len=256,
                      dtype=jnp.float32, remat="dots")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, 512)
    batch = {"tokens": tokens}

    cfg_f = dataclasses.replace(cfg, fused_ffn=True, fused_attn=True)
    loss_ref, _ = loss_fn(params, batch, cfg)
    loss_fused, _ = loss_fn(params, batch, cfg_f)
    np.testing.assert_allclose(float(loss_fused), float(loss_ref), rtol=1e-5)

    g_ref = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    g_fused = jax.grad(lambda p: loss_fn(p, batch, cfg_f)[0])(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4),
        g_ref, g_fused)


def test_fused_attn_requires_fused_ffn():
    import dataclasses

    from ray_tpu.models.transformer import ModelConfig, init_params, loss_fn

    cfg = dataclasses.replace(ModelConfig.tiny(), fused_attn=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="requires fused_ffn"):
        loss_fn(params, {"tokens": jnp.zeros((1, 9), jnp.int32)}, cfg)


def test_fused_adamw_matches_optax_chain():
    """FusedAdamW (Pallas one-pass update; jnp fallback on CPU) must match
    optax.chain(clip_by_global_norm, adamw) step for step."""
    import optax

    from ray_tpu.ops.pallas.adamw import FusedAdamW

    lr = 3e-3
    params = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32),
        "b": jax.random.normal(jax.random.PRNGKey(1), (5,), jnp.float32),
    }
    ref_opt = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=0.1,
                    mu_dtype=jnp.float32))
    fused = FusedAdamW(lr, b1=0.9, b2=0.95, weight_decay=0.1, clip_norm=1.0)

    ref_state = ref_opt.init(params)
    f_state = fused.init(params)
    ref_params = params
    f_params = params
    for i in range(3):
        grads = jax.tree_util.tree_map(
            lambda p: jax.random.normal(jax.random.PRNGKey(10 + i), p.shape)
            * (3.0 if i == 0 else 0.1),  # step 0 exercises real clipping
            ref_params)
        updates, ref_state = ref_opt.update(grads, ref_state, ref_params)
        ref_params = optax.apply_updates(ref_params, updates)
        f_params, f_state = fused.apply(grads, f_state, f_params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5,
                                                    atol=2e-6),
            ref_params, f_params)


def test_fused_blocks_on_sharded_mesh():
    """fused_ffn+fused_attn under dp/fsdp/tp shardings: the custom-vjp
    blocks (with their one Pallas kernel) must compile and step on a
    GSPMD-partitioned mesh, matching the stock path's loss."""
    import dataclasses

    from ray_tpu.models import ModelConfig
    from ray_tpu.parallel import MeshConfig, make_virtual_mesh
    from ray_tpu.train import batch_sharding, make_train_step
    from ray_tpu.train.step import default_optimizer

    mesh = make_virtual_mesh(8, MeshConfig(dp=2, fsdp=2, tp=2))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0, 512)
    losses = {}
    for name, kw in [("stock", {}),
                     ("fused", dict(fused_ffn=True, fused_attn=True))]:
        cfg = dataclasses.replace(ModelConfig.tiny(), **kw)
        step_fn, init_fn, _ = make_train_step(cfg, mesh,
                                              default_optimizer(1e-3))
        state = init_fn(jax.random.PRNGKey(0))
        b = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
        sh = batch_sharding(mesh)
        b = {k: jax.device_put(v, sh[k]) for k, v in b.items()}
        state, m = step_fn(state, b)
        losses[name] = float(jax.device_get(m["loss"]))
    np.testing.assert_allclose(losses["fused"], losses["stock"], rtol=1e-5)
