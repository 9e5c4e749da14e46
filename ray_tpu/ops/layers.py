"""Elementwise/normalization building blocks, XLA-fusion-friendly.

These are deliberately thin: on TPU the win is letting XLA fuse them into
surrounding matmuls, not hand-scheduling (a Pallas RMSNorm measured 3%
slower end to end, BASELINE.md, and is gone).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5,
             unit_offset: bool = False) -> jax.Array:
    """RMSNorm: x * w / sqrt(mean(x^2)). Computed in fp32, cast back.
    `unit_offset`: the scale is (1 + w), the weight held around zero."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    scale = weight.astype(jnp.float32)
    if unit_offset:
        scale = 1.0 + scale
    return (out * scale).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """LayerNorm without a bias: (x - mean(x)) / sqrt(var(x) + eps) * w over
    the last axis. Computed in fp32, cast back."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)


def rotary_embedding(positions: jax.Array, head_dim: int,
                     theta: float = 500000.0) -> tuple[jax.Array, jax.Array]:
    """RoPE cos/sin tables for given positions. Llama-3 default theta."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., seq, hd/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply RoPE to [..., seq, heads, head_dim] given [..., seq, hd/2] tables."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    # broadcast tables over the heads axis
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rotate_interleaved(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """RoPE over INTERLEAVED pairs (GPT-J's layout: lane 2i turns with lane
    2i + 1, at frequency theta^(-2i/d)) on x [..., s, heads, d] at positions
    [..., s]; float32 inside, x's type back. `apply_rotary` pairs lane i with
    lane i + d/2: the same rotation under a permutation of the lanes, so a
    model published in one layout scores differently in the other. Each lane
    reads its partner by a roll of one lane (no strided split of the lanes)."""
    cos, sin = rotary_embedding(positions, x.shape[-1], theta)
    cos = jnp.repeat(cos, 2, axis=-1)[..., :, None, :]   # [..., s, 1, d]
    sin = jnp.repeat(sin, 2, axis=-1)[..., :, None, :]
    xf = x.astype(jnp.float32)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


def swiglu(x_gate: jax.Array, x_up: jax.Array) -> jax.Array:
    """SwiGLU activation: silu(gate) * up."""
    return jax.nn.silu(x_gate) * x_up
