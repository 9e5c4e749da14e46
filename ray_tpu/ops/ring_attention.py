"""Ring attention: sequence/context parallelism over an ICI ring.

Green-field relative to the reference (SURVEY §5.7: no sequence parallelism
exists anywhere in it). Design follows blockwise ring attention (Liu et al.):
the sequence is sharded over the `sp` mesh axis; each device keeps its Q
shard resident while K/V shards rotate around the ring via
`jax.lax.ppermute` (compiled to ICI neighbor exchanges), merging each
block's contribution with an online-softmax (log-sum-exp) accumulator in
fp32. Peak memory is O(seq/sp) per device instead of O(seq), and the
K/V rotation overlaps with the block attention compute (XLA schedules the
ppermute DMA concurrently with the einsums).

Causality is enforced blockwise from global offsets: a K block entirely in
the future contributes nothing (its contribution is masked to -inf), the
diagonal block gets a triangular mask, past blocks attend fully.

Differentiable end-to-end (ppermute has a transpose rule), so the backward
pass is generated by autodiff; each ring step is `jax.checkpoint`ed so the
backward recomputes block attention instead of storing seq^2 intermediates.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attend(q, k, v, q_off, k_off, causal, scale):
    """One Q-shard x K-block contribution with fp32 stats.

    Returns (o_unnorm [b,h,sq,d] fp32, lse-parts m [b,h,sq], l [b,h,sq]).
    """
    sq, sk = q.shape[-2], k.shape[-2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        logits = jnp.where(q_pos >= k_pos, logits, -1e30)
    m = jnp.max(logits, axis=-1)                        # [b,h,sq]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return o, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "sp", causal: bool = True,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Per-shard ring attention; call inside shard_map over `axis_name`.

    Shapes are the local shards: [batch, heads, seq/sp, head_dim]. Supports
    GQA by repeating KV heads outside (or equal heads).
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    sq = q.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    q_off = my_idx * sq

    # accumulators: unnormalized output + running (max, sum) in fp32
    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:-1], -1e30, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    @jax.checkpoint
    def step(carry, s):
        o, m, l, kb, vb = carry
        # K block currently held started life on device (my_idx - s) % size
        k_off = ((my_idx - s) % axis_size) * sq
        ob, mb, lb = _block_attend(q, kb, vb, q_off, k_off, causal, scale)
        m_new = jnp.maximum(m, mb)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(mb - m_new)
        o = o * alpha[..., None] + ob * beta[..., None]
        l = l * alpha + lb * beta
        # rotate K/V to the next device; XLA overlaps this DMA with compute
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (o, m_new, l, kb, vb), None

    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(axis_size))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def ring_attention_sharded(mesh: Mesh, q, k, v, *, causal: bool = True,
                           axis_name: str = "sp",
                           sm_scale: Optional[float] = None):
    """shard_map wrapper: [batch, heads, seq, head_dim] global arrays with
    seq sharded over `axis_name`; batch over (dp, fsdp); heads over tp."""
    spec = P(("dp", "fsdp"), "tp", axis_name, None)
    fn = functools.partial(
        ring_attention, axis_name=axis_name, causal=causal, sm_scale=sm_scale)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
