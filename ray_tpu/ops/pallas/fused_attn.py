"""Fused attention block (rmsnorm -> qkv -> rotary -> flash attention -> Wo
+ residual) as a custom_vjp — the attention-half twin of
`ops/pallas/fused_ffn.py`.

The win, as measured for the FFN half (BASELINE.md r05 note), is SAVING
instead of RECOMPUTING: under dots remat the backward re-runs the fp32
rotary, the [b,s,h,d]<->[b,h,s,d] transposes, and the whole flash forward
kernel to regenerate the attention output and softmax statistics. Here the
forward saves the post-rotary q/k (bf16), v, the attention output and the
flash kernel's logsumexp rows, so the backward goes straight to the flash
backward kernels (dq/dk/dv), un-rotates with the transposed rotation, and
finishes with plain XLA dW/dx matmuls + the rmsnorm VJP. Residual cost vs
the dots policy: ~+16 MB/layer at b1 shapes (covered by what fused_ffn
freed).

K/V are saved UNREPEATED ([b, kv_heads, s, hd]); GQA expansion happens at
kernel entry in both directions (XLA lowers the repeat to a broadcast), and
dk/dv are summed back over the repeat groups.

No reference counterpart: hellofinch/ray ships no kernels (SURVEY.md §2.4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import apply_rotary
from ray_tpu.ops.pallas import _util


def _repeat_kv(t: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return t
    b, h, s, d = t.shape
    return jnp.broadcast_to(t[:, :, None], (b, h, n_rep, s, d)).reshape(
        b, h * n_rep, s, d)


def _use_kernel(s: int, hd: int) -> bool:
    return _util.on_tpu() and hd >= 128 and s >= 128


def _core_fwd(q4, kr, vr, scale):
    """[b, h, s, hd] (k/v already GQA-repeated) -> (out [b,h,s,hd],
    lse [bh, 8, s] f32 or None on the reference path)."""
    from ray_tpu.ops.pallas.flash_attention import _flash_fwd

    b, h, s, hd = q4.shape
    if _use_kernel(s, hd):
        out, lse = _flash_fwd(q4.reshape(b * h, s, hd),
                              kr.reshape(b * h, s, hd),
                              vr.reshape(b * h, s, hd),
                              scale, True, min(1024, s), min(1024, s))
        return out.reshape(b, h, s, hd), lse
    from ray_tpu.ops.attention import causal_attention_reference

    out = causal_attention_reference(q4, kr, vr, sm_scale=scale, causal=True)
    return out, None


def _core_bwd(q4, kr, vr, out, lse, do4, scale):
    """Returns (dq4, dkr, dvr) in [b, h, s, hd]."""
    b, h, s, hd = q4.shape
    if lse is not None:
        from ray_tpu.ops.pallas.flash_attention import _flash_bwd

        dq, dk, dv = _flash_bwd(
            q4.reshape(b * h, s, hd), kr.reshape(b * h, s, hd),
            vr.reshape(b * h, s, hd), out.reshape(b * h, s, hd), lse,
            do4.reshape(b * h, s, hd), scale, True,
            min(1024, s), min(512, s))
        return (dq.reshape(b, h, s, hd), dk.reshape(b, h, s, hd),
                dv.reshape(b, h, s, hd))
    from ray_tpu.ops.attention import causal_attention_reference

    _, vjp = jax.vjp(
        lambda q, k, v: causal_attention_reference(q, k, v, sm_scale=scale,
                                                   causal=True), q4, kr, vr)
    return vjp(do4)


def _fwd_impl(x, nw, wq, wk, wv, wo, cos, sin, n_heads, n_kv_heads, eps):
    b, s, d = x.shape
    hd = d // n_heads
    xf = x.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    h = (xf * rstd * nw.astype(jnp.float32)).astype(x.dtype)
    q = (h @ wq).reshape(b, s, n_heads, hd)
    k = (h @ wk).reshape(b, s, n_kv_heads, hd)
    v = (h @ wv).reshape(b, s, n_kv_heads, hd)
    q = apply_rotary(q, cos, sin).transpose(0, 2, 1, 3)   # [b, h, s, hd]
    k = apply_rotary(k, cos, sin).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    n_rep = n_heads // n_kv_heads
    scale = hd ** -0.5
    out, lse = _core_fwd(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep), scale)
    attn_flat = out.transpose(0, 2, 1, 3).reshape(b, s, n_heads * hd)
    y = x + (attn_flat @ wo).astype(x.dtype)
    return y, (rstd, q, k, v, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def attn_block(x: jax.Array, norm_w: jax.Array, wq: jax.Array, wk: jax.Array,
               wv: jax.Array, wo: jax.Array, cos: jax.Array, sin: jax.Array,
               n_heads: int, n_kv_heads: int, eps: float = 1e-5) -> jax.Array:
    """x [b, s, d] -> x + Wo(flash_attn(rotary(qkv(rmsnorm(x)))))."""
    y, _ = _fwd_impl(x, norm_w, wq, wk, wv, wo, cos, sin,
                     n_heads, n_kv_heads, eps)
    return y


def _vjp_fwd(x, norm_w, wq, wk, wv, wo, cos, sin, n_heads, n_kv_heads, eps):
    y, (rstd, q, k, v, out, lse) = _fwd_impl(
        x, norm_w, wq, wk, wv, wo, cos, sin, n_heads, n_kv_heads, eps)
    return y, (x, rstd, q, k, v, out, lse, norm_w, wq, wk, wv, wo, cos, sin)


def _vjp_bwd(n_heads, n_kv_heads, eps, res, dy):
    x, rstd, q, k, v, out, lse, nw, wq, wk, wv, wo, cos, sin = res
    b, s, d = x.shape
    hd = d // n_heads
    n_rep = n_heads // n_kv_heads
    scale = hd ** -0.5
    dy2d = dy.reshape(b * s, d)

    # output projection
    attn_flat = out.transpose(0, 2, 1, 3).reshape(b * s, n_heads * hd)
    dwo = jax.lax.dot_general(attn_flat, dy2d, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32).astype(wo.dtype)
    do4 = (dy2d @ wo.T).reshape(b, s, n_heads, hd).transpose(0, 2, 1, 3)
    do4 = do4.astype(out.dtype)

    # flash backward on saved tensors (no forward re-run)
    dq4, dkr, dvr = _core_bwd(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                              out, lse, do4, scale)
    if n_rep > 1:
        dkr = dkr.reshape(b, n_kv_heads, n_rep, s, hd).sum(axis=2)
        dvr = dvr.reshape(b, n_kv_heads, n_rep, s, hd).sum(axis=2)

    # un-rotate: the rotation is orthogonal, so the VJP is rotation by -θ
    dq_pre = apply_rotary(dq4.transpose(0, 2, 1, 3), cos, -sin)
    dk_pre = apply_rotary(dkr.transpose(0, 2, 1, 3), cos, -sin)
    dv_pre = dvr.transpose(0, 2, 1, 3)
    dq2d = dq_pre.reshape(b * s, n_heads * hd).astype(x.dtype)
    dk2d = dk_pre.reshape(b * s, n_kv_heads * hd).astype(x.dtype)
    dv2d = dv_pre.reshape(b * s, n_kv_heads * hd).astype(x.dtype)

    # dW for the three projections; h recomputed elementwise (one pass)
    x2d = x.reshape(b * s, d)
    rstd2d = rstd.reshape(b * s, 1)
    h2d = (x2d.astype(jnp.float32) * rstd2d
           * nw.astype(jnp.float32)).astype(x.dtype)
    ct = (((0,), (0,)), ((), ()))
    dwq = jax.lax.dot_general(h2d, dq2d, ct,
                              preferred_element_type=jnp.float32).astype(wq.dtype)
    dwk = jax.lax.dot_general(h2d, dk2d, ct,
                              preferred_element_type=jnp.float32).astype(wk.dtype)
    dwv = jax.lax.dot_general(h2d, dv2d, ct,
                              preferred_element_type=jnp.float32).astype(wv.dtype)

    # dh back through the projections, then the rmsnorm VJP
    dh = (jax.lax.dot_general(dq2d, wq, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
          + jax.lax.dot_general(dk2d, wk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
          + jax.lax.dot_general(dv2d, wv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32))
    xf = x2d.astype(jnp.float32)
    wdh = dh * nw.astype(jnp.float32)
    proj = jnp.sum(wdh * xf, axis=-1, keepdims=True) / d
    dx = (rstd2d * (wdh - xf * rstd2d * rstd2d * proj)
          + dy2d.astype(jnp.float32)).astype(x.dtype).reshape(b, s, d)
    dnw = jnp.sum(dh * xf * rstd2d, axis=0).astype(nw.dtype)
    return (dx, dnw, dwq, dwk, dwv, dwo,
            jnp.zeros_like(cos), jnp.zeros_like(sin))


attn_block.defvjp(_vjp_fwd, _vjp_bwd)
