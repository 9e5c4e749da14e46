"""Multi-head latent attention (MLA) without positions.

A token leaves one latent row `[c, k_r]` (kv_lora_rank + qk_rope_head_dim
values) in the cache instead of every head's keys and values:
`[k_n, v]_h = W_kvb,h c`, `k_h = [k_n,h, k_r]` with `k_r` shared by all
heads. No rotation is applied (`mla_use_nope`); the "rope" part is just
the shared part of the key.

`mla_expand`            every head's keys and values from the latent rows:
                        the form prefill uses (with
                        `ops.attention.causal_attention_blocked`).
`mla_decode_absorbed`   one token against the latent cache without
                        expanding it: the key half of `W_kvb` is folded into
                        the query (q_n W_kb^T lives in latent space), the
                        scores are taken against the latent rows themselves,
                        and the value half is applied once, after the sum
                        over positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def mla_expand(latent: jax.Array, w_kvb: jax.Array, n_heads: int, rank: int,
               d_nope: int, d_v: int):
    """latent [b, s, rank + d_rope], w_kvb [rank, H (d_nope + d_v)] ->
    (k [b, s, H, d_nope + d_rope], v [b, s, H, d_v])."""
    b, s, _ = latent.shape
    kv = (latent[..., :rank] @ w_kvb).reshape(b, s, n_heads, d_nope + d_v)
    k_r = jnp.broadcast_to(latent[:, :, None, rank:],
                           (b, s, n_heads, latent.shape[-1] - rank))
    return jnp.concatenate([kv[..., :d_nope], k_r], axis=-1), kv[..., d_nope:]


def mla_decode_absorbed(q: jax.Array, window: jax.Array, cur: jax.Array,
                        mask: jax.Array, w_kvb: jax.Array, rank: int,
                        d_nope: int, d_v: int) -> jax.Array:
    """q [B, H, d_nope + d_rope] (one token a slot); window [B, 1, Lw, rank +
    d_rope] a prefix of the slot's latent rows (with the cache's one "kv
    head"); cur [B, rank + d_rope] the
    current token's own row (not written yet); mask [B, Lw], True = attend
    (STRICT: the current position comes in through `cur`) -> [B, H, d_v]."""
    B, H, dq = q.shape
    w = w_kvb.reshape(rank, H, d_nope + d_v)
    # fold W_kb into the query: [B, H, rank], then the shared part behind it
    q_lat = jnp.concatenate(
        [jnp.einsum("bhn,rhn->bhr", q[..., :d_nope], w[..., :d_nope]),
         q[..., d_nope:]], axis=-1).astype(window.dtype)
    scale = dq ** -0.5
    # the einsum forms of `_gqa_decode_attention` (one shared "kv head" g,
    # the H query heads as its group r): XLA:TPU reads the window in place
    qg, cur_g = q_lat[:, None], cur[:, None]
    lg = jnp.einsum("bgrc,bglc->bgrl", qg, window).astype(F32) * scale
    lg = jnp.where(mask[:, None, None, :], lg, -1e30)
    self_lg = jnp.einsum("bgrc,bgc->bgr", qg, cur_g).astype(F32) * scale
    pr = jax.nn.softmax(jnp.concatenate([lg, self_lg[..., None]], -1), axis=-1)
    pr = pr.astype(window.dtype)
    Lw = window.shape[2]
    ctx = jnp.einsum("bgrl,bglc->bgrc", pr[..., :Lw], window)[..., :rank] \
        + pr[..., Lw:] * cur_g[:, :, None, :rank]
    return jnp.einsum("bhr,rhv->bhv", ctx[:, 0], w[..., d_nope:])
