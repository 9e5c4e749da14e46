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


def swiglu(x_gate: jax.Array, x_up: jax.Array) -> jax.Array:
    """SwiGLU activation: silu(gate) * up."""
    return jax.nn.silu(x_gate) * x_up
