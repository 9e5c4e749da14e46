"""Attention: in-repo Pallas flash kernel on TPU, reference einsum elsewhere.

The TPU path uses this repo's Pallas flash-attention kernels
(`ray_tpu.ops.pallas.flash_attention`) for BOTH forward and backward —
tiled onto the MXU with online softmax and a fused FlashAttention-2
recompute backward, O(seq) memory in each direction. The reference path is
a plain einsum attention used on CPU (tests / virtual meshes) and as the
ground truth the kernels are checked against.

GQA (fewer KV heads than Q heads) is handled by repeating KV heads before
the kernel; XLA turns the repeat into a broadcast so no HBM copy occurs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.pallas import _util


def causal_attention_reference(q, k, v, sm_scale: Optional[float] = None,
                               causal: bool = True) -> jax.Array:
    """Ground-truth attention. [batch, heads, seq, head_dim] layout."""
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, h, s, d = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, h, n_rep, s, d)).reshape(b, h * n_rep, s, d)


def uses_flash_kernel(q: jax.Array) -> bool:
    """Whether `attention` runs the Pallas flash kernel for this q
    ([batch, heads, seq, head_dim]): on a TPU, at shapes that tile."""
    return _util.on_tpu() and q.shape[-1] >= 128 and q.shape[-2] >= 128


def attention_sharded(mesh, q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True) -> jax.Array:
    """`attention` on a mesh of several chips. A Mosaic kernel cannot be
    partitioned by the compiler ("wrap the call in a shard_map"), so each
    device runs the kernel on its own block: batch over (dp, fsdp), heads
    over tp — attention is independent per (batch, head), so no collective.
    GQA stays aligned because q and kv heads split contiguously."""
    spec = P(("dp", "fsdp"), "tp", None, None)
    return jax.shard_map(
        functools.partial(attention, causal=causal), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale"))
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, sm_scale: Optional[float] = None) -> jax.Array:
    """Multi-head attention, [batch, heads, seq, head_dim]; supports GQA.

    Dispatches to the TPU pallas flash kernel when running on TPU and the
    shapes satisfy its tiling constraints; otherwise falls back to the
    reference einsum (which XLA still fuses reasonably on TPU).
    """
    n_rep = q.shape[1] // k.shape[1]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if uses_flash_kernel(q):
        from ray_tpu.ops.pallas.flash_attention import flash_attention_pallas

        b, h, sq, d = q.shape
        sk = k.shape[-2]
        # Measured on v5e at seq 2048 / head_dim 128 (see flash kernel
        # docstring): fwd peaks at (1024, 1024) blocks — 95% of bf16 peak vs
        # 43% at (512, 1024); the bwd pair peaks at (1024, 512) — the dkv
        # kernel carries two k-block f32 accumulators, so a smaller k block
        # keeps its VMEM footprint down while a big q block amortizes the
        # sequential-axis revisits.
        out = flash_attention_pallas(
            q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
            v.reshape(b * h, sk, d), scale, causal,
            min(1024, sq), min(1024, sk),
            min(1024, sq), min(512, sk))
        return out.reshape(b, h, sq, d)
    return causal_attention_reference(q, k, v, sm_scale=scale, causal=causal)


def causal_attention_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             sm_scale: float, q_block: int = 512) -> jax.Array:
    """Causal attention whose query/key width differs from its value width
    (latent attention: 192 against 128; the flash kernel and `attention`
    above assume one `d`). q, k [b, s, h, dk], v [b, s, h, dv] ->
    [b, s, h, dv]. Query rows go `q_block` at a time, each block against the
    keys up to its own end only, so the scores never exceed
    [b, h, q_block, s] and the causal half above the diagonal blocks is
    never computed. Softmax statistics in float32."""
    s = q.shape[1]
    blk = min(q_block, s)
    outs = []
    for start in range(0, s, blk):
        end = min(start + blk, s)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:end], k[:, :end]
                        ).astype(jnp.float32) * sm_scale
        ok = (start + jnp.arange(end - start))[:, None] >= jnp.arange(end)[None, :]
        pr = jax.nn.softmax(jnp.where(ok[None, None], sc, -1e30), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", pr.astype(v.dtype), v[:, :end]))
    return jnp.concatenate(outs, axis=1)


def banded_attention(q: jax.Array, k: jax.Array, v: jax.Array, layer, q_start,
                     k_lo, *, window: Optional[int], sm_scale: float) -> jax.Array:
    """Causal grouped-query attention of a chunk's queries against one layer
    of a row cache, under a band: q [b, H, sq, d]; k, v [layers, b, kvh, sk,
    d]; `layer`, `q_start`, `k_lo` int32 scalars (data: one program serves
    every chunk of a prompt). Query row r stands at key column c = r +
    q_start and attends columns max(k_lo, c - window + 1) .. c of `layer`
    (`window` None: from k_lo; columns below k_lo hold nothing). -> [b, H,
    sq, d]. On a TPU at shapes that tile, the flash kernel
    (`flash_attention_banded`: blocks outside the band are skipped, grouped
    heads read through the index map); elsewhere a masked einsum over the
    grouped heads. Neither copies K or V to the query heads."""
    b, H, sq, d = q.shape
    layers, _, kvh, sk, _ = k.shape
    if uses_flash_kernel(q):
        from ray_tpu.ops.pallas.flash_attention import flash_attention_banded

        bounds = jnp.stack([jnp.asarray(a, jnp.int32) for a in (q_start, k_lo, layer)])
        out = flash_attention_banded(
            q.reshape(b * H, sq, d), k.reshape(layers, b * kvh, sk, d),
            v.reshape(layers, b * kvh, sk, d), bounds, sm_scale, window)
        return out.reshape(b, H, sq, d)
    kl = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
    vl = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
    qg = q.reshape(b, kvh, H // kvh, sq, d)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kl).astype(jnp.float32) * sm_scale
    at = jnp.arange(sq)[:, None] + q_start
    cols = jnp.arange(sk)[None, :]
    ok = (cols <= at) & (cols >= k_lo)
    if window is not None:
        ok &= cols > at - window
    p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bgkd->bgrqd", p, vl).reshape(b, H, sq, d)
