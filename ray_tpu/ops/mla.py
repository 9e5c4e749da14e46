"""Multi-head latent attention (MLA), with or without positions.

A token leaves one latent row `[c, k_r]` (kv_lora_rank + qk_rope_head_dim
values, stored in `W` lanes: a cache may pad the row to whole lanes) in the
cache instead of every head's keys and values: `[k_n, v]_h = W_kvb,h c`,
`k_h = [k_n,h, k_r]` with `k_r` shared by all heads. Whether `k_r` and the
query's `q_r` are rotated (`rotate`) is the configuration's: the row in the
cache holds `k_r` as the scores need it, so neither form below knows.

`mla_expand`            every head's keys and values from the latent rows:
                        the form prefill uses (`mla_prefill_attention`,
                        with `ops.attention.causal_attention_blocked`).
`mla_decode_absorbed`   Q new positions a slot (1, or a draft's 2) against
                        the latent cache without expanding it: the key half
                        of `W_kvb` is folded into the query (q_n W_kb^T
                        lives in latent space), the scores are taken against
                        the latent rows themselves, and the value half is
                        applied once, after the sum over positions. One
                        algorithm, two executions (`decode_walk`): on a TPU
                        at shapes that tile, a kernel that reads each slot's
                        live rows (`ops/pallas/mla_decode.py`); elsewhere
                        einsums over the window of every slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import causal_attention_blocked
from ray_tpu.ops.layers import apply_rotary, rotary_embedding
from ray_tpu.ops.pallas import mla_decode

F32 = jnp.float32
# the most bytes of float32 scores the prompt pass holds at once: all 128
# heads x 512 query rows x 8191 keys would be 2.1 GB, and every head's keys
# and values beside them 0.7 GB
_SCORE_BYTES = 256 * 2**20


def rotate(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """RoPE (half-rotation layout, no scaling) on x [..., s, heads, d] at
    positions [..., s]; float32 inside, x's type back."""
    cos, sin = rotary_embedding(positions, x.shape[-1], theta)
    return apply_rotary(x, cos, sin)


def mla_expand(latent: jax.Array, w_kvb: jax.Array, n_heads: int, rank: int,
               d_nope: int, d_rope: int, d_v: int):
    """latent [b, s, W >= rank + d_rope], w_kvb [rank, H (d_nope + d_v)] ->
    (k [b, s, H, d_nope + d_rope], v [b, s, H, d_v])."""
    b, s, _ = latent.shape
    kv = (latent[..., :rank] @ w_kvb).reshape(b, s, n_heads, d_nope + d_v)
    k_r = jnp.broadcast_to(latent[:, :, None, rank:rank + d_rope],
                           (b, s, n_heads, d_rope))
    return jnp.concatenate([kv[..., :d_nope], k_r], axis=-1), kv[..., d_nope:]


def mla_prefill_attention(q: jax.Array, latent: jax.Array, w_kvb: jax.Array,
                          rank: int, d_nope: int, d_v: int) -> jax.Array:
    """The prompt pass: q [b, s, H, d_nope + d_rope] against the keys and
    values expanded from latent [b, s, W], causal -> [b, s, H, d_v]. Where
    the scores of all heads pass `_SCORE_BYTES`, a group of heads at a time,
    one group after another: its keys and values are expanded, used and
    gone before the next group's."""
    b, s, H, dq = q.shape
    scale = dq ** -0.5
    groups = 1
    while (b * (H // groups) * min(512, s) * s * 4 > _SCORE_BYTES
           and H % (2 * groups) == 0):
        groups *= 2
    if groups == 1:
        k, v = mla_expand(latent, w_kvb, H, rank, d_nope, dq - d_nope, d_v)
        return causal_attention_blocked(q, k, v, sm_scale=scale)
    hg = H // groups
    w = w_kvb.reshape(rank, groups, hg * (d_nope + d_v))

    def one(i):
        k, v = mla_expand(latent, jax.lax.dynamic_index_in_dim(w, i, 1, False),
                          hg, rank, d_nope, dq - d_nope, d_v)
        return causal_attention_blocked(
            jax.lax.dynamic_slice_in_dim(q, i * hg, hg, axis=2), k, v, sm_scale=scale)

    out = jax.lax.map(one, jnp.arange(groups))             # [g, b, s, hg, d_v]
    return jnp.moveaxis(out, 0, 2).reshape(b, s, H, d_v)


def decode_walk(cache: jax.Array, lengths: jax.Array, attn_len: int,
                n_heads: int):
    """The kernel's walk over `cache` [L, B, 1, max_len, W] for this step
    (`mla_decode.live_blocks`; the same for every layer: computed once a
    step), or None where the decode runs as einsums."""
    if not mla_decode.uses_kernel(cache, attn_len, n_heads):
        return None
    return mla_decode.live_blocks(lengths, attn_len)


def mla_decode_absorbed(q: jax.Array, cache: jax.Array, layer,
                        cur: jax.Array, lengths: jax.Array, attn_len: int,
                        w_kvb: jax.Array, rank: int, d_nope: int, d_v: int,
                        walk=None) -> jax.Array:
    """q [B, Q, H, d_nope + d_rope]: Q new positions a slot, the slot's
    `lengths[b] + 0 .. Q-1`; cache [L, B, 1, max_len, W] the latent rows of
    every layer, of which `layer`'s (a scalar, traced or not) first
    `attn_len` are read (with the cache's one "kv head"); cur [B, Q, W] the new positions' own rows (not
    written yet) -> [B, Q, H, d_v]. Position a attends the rows
    [0, lengths[b]) of the cache (STRICT) and cur[:, :a + 1]: causal among
    the new ones. `walk` = `decode_walk(...)`."""
    B, Q, H, dq = q.shape
    W = cache.shape[-1]
    w = w_kvb.reshape(rank, H, d_nope + d_v)
    # fold W_kb into the query: [B, Q, H, rank], the shared part behind it,
    # zeros over the lanes a padded row does not use
    q_lat = jnp.concatenate(
        [jnp.einsum("bqhn,rhn->bqhr", q[..., :d_nope], w[..., :d_nope]),
         q[..., d_nope:],
         jnp.zeros((B, Q, H, W - rank - (dq - d_nope)), q.dtype)],
        axis=-1).astype(cache.dtype)
    scale = dq ** -0.5
    if walk is not None:
        ctx = mla_decode.latent_decode_attention(
            q_lat.reshape(B, Q * H, W), cur, cache, layer, walk, attn_len,
            rank, scale).reshape(B, Q, H, rank)
        return jnp.einsum("bqhr,rhv->bqhv", ctx, w[..., d_nope:])
    window = jax.lax.dynamic_slice(
        cache, (layer, 0, 0, 0, 0), (1, B, 1, attn_len, W))[0]   # [B, 1, Lw, W]
    mask = jnp.arange(attn_len)[None, :] < lengths[:, None]
    # the einsum forms of `_gqa_decode_attention` (one shared "kv head" g,
    # the Q x H query rows as its group r): XLA:TPU reads the window in place
    qg = q_lat.reshape(B, 1, Q * H, W)
    lg = jnp.einsum("bgrc,bglc->bgrl", qg, window).astype(F32) * scale
    lg = jnp.where(mask[:, None, None, :], lg, -1e30)
    self_lg = jnp.einsum("bqhc,bpc->bqhp", q_lat, cur).astype(F32) * scale
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    self_lg = jnp.where(causal[None, :, None, :], self_lg, -1e30)
    pr = jax.nn.softmax(
        jnp.concatenate([lg, self_lg.reshape(B, 1, Q * H, Q)], -1), axis=-1)
    pr = pr.astype(cache.dtype)
    ctx = jnp.einsum("bgrl,bglc->bgrc", pr[..., :attn_len], window)[..., :rank] \
        + jnp.einsum("bgrp,bpc->bgrc", pr[..., attn_len:], cur[..., :rank])
    return jnp.einsum("bqhr,rhv->bqhv", ctx.reshape(B, Q, H, rank),
                      w[..., d_nope:])
