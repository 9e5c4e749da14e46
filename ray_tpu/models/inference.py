"""Inference: KV-cache prefill/decode and a jitted generate loop.

The serving-side compute path (used by Serve model replicas — the
reference delegates this to torch; here it is native): prefill builds the
stacked per-layer KV cache in one pass, decode steps are single-token
forward passes attending over the cache (static max_len shapes, masked by
position, so the whole generate loop is one compiled `lax.scan`).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.transformer import (ModelConfig, _deq_tree,
                                        _embed_lookup, _mlp, _project_qkv,
                                        lm_head_weights)
from ray_tpu.ops.layers import rms_norm, rotary_embedding


def _gqa_decode_attention(q, k_cache, v_cache, k_cur, v_cur, mask, sm_scale=0.0):
    """Single-token grouped-query attention over a cache window plus the
    current token's (not-yet-written) K/V row.

    q [b,h,1,hd]; k_cache/v_cache [b,kvh,Lw,hd] (a prefix window of the
    slot cache); k_cur/v_cur [b,kvh,hd]; mask [b,Lw] with True = attend
    (STRICT: the current position is not in the cache — it contributes via
    the separate k_cur/v_cur term). Unlike `_masked_attention` this never
    materializes GQA-repeated K/V (those copies are cache-sized, per layer,
    per step): queries are grouped [b,kvh,rep,hd] and contracted against
    the shared K/V heads directly. `sm_scale` 0: 1 / sqrt(hd).
    """
    b, h, _, hd = q.shape
    kvh = k_cache.shape[1]
    qg = q[:, :, 0].reshape(b, kvh, h // kvh, hd)
    scale = sm_scale or hd ** -0.5
    lg = jnp.einsum("bgrd,bgld->bgrl", qg, k_cache).astype(jnp.float32) * scale
    lg = jnp.where(mask[:, None, None, :], lg, -1e30)
    self_lg = jnp.einsum("bgrd,bgd->bgr", qg, k_cur).astype(jnp.float32) * scale
    lg = jnp.concatenate([lg, self_lg[..., None]], axis=-1)
    probs = jax.nn.softmax(lg, axis=-1).astype(q.dtype)
    win = k_cache.shape[2]
    attn = jnp.einsum("bgrl,bgld->bgrd", probs[..., :win], v_cache) \
        + probs[..., win:] * v_cur[:, :, None]
    return attn.reshape(b, h, hd)


def _masked_attention(q, k, v, mask):
    """q [b,h,sq,hd] over cached k/v [b,kvh,L,hd] with bool mask [sq,L]."""
    n_rep = q.shape[1] // k.shape[1]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=1)
        v = jnp.repeat(v, n_rep, axis=1)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def prefill(params: Dict, tokens: jax.Array, cfg: ModelConfig,
            max_len: int, logits_index: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict]:
    """Process the prompt; returns (logits [b, vocab], cache).

    Logits come from the last position, or from `logits_index` [b] when the
    prompt is right-padded (the causal mask keeps positions < index exact).
    cache = {"k": [L,b,kvh,max_len,hd], "v": ..., "length": scalar}.
    """
    b, s = tokens.shape
    hd = cfg.head_dim
    positions = jnp.arange(s)
    cos, sin = rotary_embedding(positions, hd, cfg.rope_theta)
    cos, sin = cos[None], sin[None]
    x = _embed_lookup(params["embed"], tokens, cfg.dtype)
    causal = jnp.tril(jnp.ones((s, s), bool))
    pad = jnp.zeros((s, max_len - s), bool)
    mask = jnp.concatenate([causal, pad], axis=1)

    # named scopes are metadata only: they name the phases of the program
    # in a device trace (`cache_write`, `attention`, `mlp`, `head`)
    def body(x, lp):
        lp = _deq_tree(lp, cfg.dtype)
        with jax.named_scope("attention"):
            q, k, v = _project_qkv(cfg, lp, x, cos, sin)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        with jax.named_scope("cache_write"):
            k_cache = jnp.zeros((b, cfg.n_kv_heads, max_len, hd), cfg.dtype)
            v_cache = jnp.zeros((b, cfg.n_kv_heads, max_len, hd), cfg.dtype)
            k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(cfg.dtype), (0, 0, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(cfg.dtype), (0, 0, 0, 0))
        with jax.named_scope("attention"):
            attn = _masked_attention(q, k_cache, v_cache, mask)
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * hd)
            x = x + (attn @ lp["wo"]).astype(x.dtype)
        with jax.named_scope("mlp"):
            x = x + _mlp(cfg, lp, x)[0].astype(x.dtype)
        return x, (k_cache, v_cache)

    x, (k_all, v_all) = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = lm_head_weights(params, cfg)
        if logits_index is None:
            sel = x[:, -1]
        else:
            sel = jnp.take_along_axis(
                x, logits_index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = (sel @ head.astype(cfg.dtype)).astype(jnp.float32)
    cache = {"k": k_all, "v": v_all, "length": jnp.asarray(s, jnp.int32)}
    return logits, cache


def decode_step(params: Dict, cache: Dict, token: jax.Array,
                cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """One token for each batch row; returns (logits [b, vocab], cache)."""
    b = token.shape[0]
    hd = cfg.head_dim
    pos = cache["length"]
    max_len = cache["k"].shape[-2]
    cos, sin = rotary_embedding(pos[None], hd, cfg.rope_theta)
    cos, sin = cos[None], sin[None]
    x = _embed_lookup(params["embed"], token[:, None], cfg.dtype)  # [b,1,d]
    mask = (jnp.arange(max_len) <= pos)[None, :]  # [1, max_len]

    def body(x, inputs):
        lp, k_cache, v_cache = inputs
        lp = _deq_tree(lp, cfg.dtype)
        q, k, v = _project_qkv(cfg, lp, x, cos, sin)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(cfg.dtype), (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(cfg.dtype), (0, 0, pos, 0))
        attn = _masked_attention(q, k_cache, v_cache, mask)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * hd)
        x = x + (attn @ lp["wo"]).astype(x.dtype)
        x = x + _mlp(cfg, lp, x)[0].astype(x.dtype)
        return x, (k_cache, v_cache)

    x, (k_all, v_all) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = lm_head_weights(params, cfg)
    logits = (x[:, 0] @ head.astype(cfg.dtype)).astype(jnp.float32)
    new_cache = {"k": k_all, "v": v_all, "length": pos + 1}
    return logits, new_cache


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "max_len",
                                             "temperature"))
def generate(params: Dict, prompt: jax.Array, cfg: ModelConfig, *,
             max_new_tokens: int = 32, max_len: int = 512,
             temperature: float = 0.0,
             rng: Optional[jax.Array] = None) -> jax.Array:
    """Autoregressive generation; returns [b, prompt_len + max_new_tokens].

    temperature 0 = greedy; otherwise categorical sampling with `rng`.
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    logits, cache = prefill(params, prompt, cfg, max_len)

    def sample(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)

    first = sample(logits, rng)

    def step(carry, key):
        cache, token = carry
        logits, cache = decode_step(params, cache, token, cfg)
        nxt = sample(logits, key)
        return (cache, nxt), token

    keys = jax.random.split(rng, max_new_tokens)
    # each scan step emits its *input* token, so ys = exactly the
    # max_new_tokens sampled tokens (the final step's sample is unused)
    (_, _last), tokens = jax.lax.scan(step, (cache, first), keys)
    return jnp.concatenate([prompt, tokens.T], axis=1)
