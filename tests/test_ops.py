"""Kernel/op correctness tests on the virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention, causal_attention_reference, rms_norm
from ray_tpu.ops.attention import attention_sharded
from ray_tpu.ops.layers import apply_rotary, rotary_embedding, swiglu
from ray_tpu.parallel import MeshConfig, make_virtual_mesh


def test_rms_norm_matches_manual():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.1 + 1.0
    out = rms_norm(x, w)
    expected = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-5) * np.asarray(w)
    np.testing.assert_allclose(out, expected, rtol=1e-5)


def test_rotary_is_norm_preserving():
    pos = jnp.arange(16)
    cos, sin = rotary_embedding(pos, 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 32))
    out = apply_rotary(x, cos[None], sin[None])
    np.testing.assert_allclose(
        jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # position 0 is identity
    np.testing.assert_allclose(out[:, 0], x[:, 0], atol=1e-6)


def test_swiglu():
    g = jnp.array([1.0, -1.0])
    u = jnp.array([2.0, 2.0])
    out = swiglu(g, u)
    np.testing.assert_allclose(out, jax.nn.silu(g) * u)


def test_attention_matches_reference():
    rng = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(r, (2, 4, 32, 16), jnp.float32)
               for r in jax.random.split(rng, 3))
    out = attention(q, k, v, causal=True)
    ref = causal_attention_reference(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_attention_gqa():
    rng = jax.random.PRNGKey(1)
    q = jax.random.normal(rng, (2, 8, 16, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 16, 16))
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 2, 16, 16))
    out = attention(q, k, v, causal=True)
    # reference with explicit repeat
    kr = jnp.repeat(k, 4, axis=1)
    vr = jnp.repeat(v, 4, axis=1)
    ref = causal_attention_reference(q, kr, vr)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


# the four-chip cell's attention: each device its own (batch, head) block
SHARDED_MESHES = [MeshConfig(dp=-1, fsdp=2, tp=2), MeshConfig(dp=-1, fsdp=1, tp=4)]
_mesh_id = lambda m: f"fsdp{m.fsdp}-tp{m.tp}"


@pytest.mark.parametrize("mesh_cfg", SHARDED_MESHES, ids=_mesh_id)
@pytest.mark.parametrize("causal", [True, False])
def test_attention_sharded_matches_reference(causal, mesh_cfg):
    mesh = make_virtual_mesh(8, mesh_cfg)
    q, k, v = (jax.random.normal(r, (4, 8, 64, 16), jnp.float32)
               for r in jax.random.split(jax.random.PRNGKey(0), 3))
    out = attention_sharded(mesh, q, k, v, causal=causal)
    ref = causal_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("mesh_cfg", SHARDED_MESHES, ids=_mesh_id)
def test_attention_sharded_gqa(mesh_cfg):
    """4 : 1 grouped heads as Mistral's: q and kv heads split contiguously
    over tp, so each rank's q heads meet their own kv heads."""
    mesh = make_virtual_mesh(8, mesh_cfg)
    b, hq, hkv, s, d = 4, 16, 4, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, hq, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d), jnp.float32)
    out = attention_sharded(mesh, q, k, v, causal=True)
    ref = causal_attention_reference(q, jnp.repeat(k, hq // hkv, axis=1),
                                     jnp.repeat(v, hq // hkv, axis=1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("remat", ["none", "full", "dots"])
def test_remat_modes_agree(remat):
    """Every value `remat` takes gives the loss and the gradients of the
    plain forward (logits, then `token_nll`, no checkpoint anywhere): the
    policies only trade recompute for saved-activation memory."""
    import dataclasses

    import numpy as np

    from ray_tpu.models.transformer import (ModelConfig, forward, init_params,
                                            loss_fn, token_nll)

    base = ModelConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), base)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0,
                                base.vocab_size)

    def plain(p):
        return token_nll(forward(p, tokens[:, :-1], base), tokens[:, 1:])

    cfg = dataclasses.replace(base, remat=remat)
    loss0, g0 = jax.value_and_grad(plain)(params)
    loss1, g1 = jax.value_and_grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg, None)[0])(params)
    np.testing.assert_allclose(loss0, loss1, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("remat", ["dots_plus_attn", "selective", ""])
def test_unknown_remat_value_raises(remat):
    """`remat` takes three values; a policy that lost its measurement and
    left with its code is an unknown string like any other."""
    import dataclasses

    from ray_tpu.models.transformer import ModelConfig, init_params, loss_fn

    cfg = dataclasses.replace(ModelConfig.tiny(), remat=remat)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 9), jnp.int32)
    with pytest.raises(ValueError, match="unknown remat mode"):
        loss_fn(params, {"tokens": tokens}, cfg)
