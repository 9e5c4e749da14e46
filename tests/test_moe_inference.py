"""MoE layer + expert parallelism; KV-cache inference correctness."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import ModelConfig, forward, init_params, loss_fn
from ray_tpu.models.inference import decode_step, generate, prefill
from ray_tpu.ops.moe import moe_ffn, top2_gating
from ray_tpu.parallel import MeshConfig, make_virtual_mesh
from ray_tpu.train import batch_sharding, make_train_step
from ray_tpu.train.step import default_optimizer


def test_top2_gating_capacity_and_weights():
    logits = jnp.array([[5.0, 0.0, 0.0], [0.0, 5.0, 0.0],
                        [5.0, 0.0, 0.0], [0.0, 0.0, 5.0]])
    dispatch, combine, aux = top2_gating(logits, capacity=4)
    assert dispatch.shape == (4, 3, 4)
    # each token's combine weights sum to ~1 (top-2 renormalized)
    sums = combine.sum(axis=(1, 2))
    np.testing.assert_allclose(sums, np.ones(4), atol=1e-5)
    assert float(aux) > 0


def test_moe_ffn_shapes_and_grads():
    rng = jax.random.PRNGKey(0)
    B, S, d, E, ff = 2, 8, 16, 4, 32
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (B, S, d))
    router = jax.random.normal(ks[1], (d, E)) * 0.1
    wg = jax.random.normal(ks[2], (E, d, ff)) * 0.1
    wu = jax.random.normal(ks[3], (E, d, ff)) * 0.1
    wd = jax.random.normal(ks[4], (E, ff, d)) * 0.1
    out, aux = moe_ffn(x, router, wg, wu, wd, capacity_factor=2.0)
    assert out.shape == (B, S, d)

    def loss(x, router, wg, wu, wd):
        out, aux = moe_ffn(x, router, wg, wu, wd, capacity_factor=2.0)
        return jnp.sum(out ** 2) + aux

    grads = jax.grad(loss, argnums=(1, 2))(x, router, wg, wu, wd)
    assert float(jnp.abs(grads[0]).sum()) > 0  # router receives gradient


@pytest.mark.slow
def test_moe_model_trains_sharded():
    cfg = ModelConfig.tiny_moe()
    mesh = make_virtual_mesh(8, MeshConfig(dp=2, fsdp=2, tp=2))
    step_fn, init_fn, _ = make_train_step(cfg, mesh, default_optimizer(1e-3))
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0, cfg.vocab_size)
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    b_sh = batch_sharding(mesh)
    batch = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
    losses = []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("cfg", [
    ModelConfig.tiny(),
    dataclasses.replace(ModelConfig.tiny(), n_kv_heads=1),         # 4:1 grouped
    dataclasses.replace(ModelConfig.tiny(), tie_embeddings=True),
    # capacity = tokens: nothing is dropped, whatever the number of tokens
    dataclasses.replace(ModelConfig.tiny_moe(), capacity_factor=4.0),
], ids=["tiny", "heads_4to1", "tied_embeddings", "tiny_moe"])
def test_prefill_decode_matches_full_forward(cfg):
    """Greedy decode via KV cache must match argmax over full forward: the
    one spelling of the projections and the FFN (`transformer._project_qkv`,
    `_mlp`), seen from the training forward, prefill and the decode step."""
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab_size)

    # full-forward next token
    logits_full = forward(params, prompt, cfg)
    next_full = jnp.argmax(logits_full[:, -1], axis=-1)

    logits_pre, cache = prefill(params, prompt, cfg, max_len=32)
    next_cache = jnp.argmax(logits_pre, axis=-1)
    np.testing.assert_allclose(np.asarray(logits_pre), np.asarray(logits_full[:, -1]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(next_full), np.asarray(next_cache))

    # one decode step == full forward on prompt+token
    logits_step, cache = decode_step(params, cache, next_cache.astype(jnp.int32), cfg)
    extended = jnp.concatenate([prompt, next_cache[:, None]], axis=1)
    logits_full2 = forward(params, extended, cfg)
    np.testing.assert_allclose(np.asarray(logits_step),
                               np.asarray(logits_full2[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_generate_greedy_deterministic():
    cfg = ModelConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 5), 0, cfg.vocab_size)
    out1 = generate(params, prompt, cfg, max_new_tokens=8, max_len=32)
    out2 = generate(params, prompt, cfg, max_new_tokens=8, max_len=32)
    assert out1.shape == (1, 13)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :5]), np.asarray(prompt))


def test_generate_sampled_with_temperature():
    cfg = ModelConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((1, 4), jnp.int32)
    out = generate(params, prompt, cfg, max_new_tokens=6, max_len=32,
                   temperature=1.0, rng=jax.random.PRNGKey(7))
    assert out.shape == (1, 10)
