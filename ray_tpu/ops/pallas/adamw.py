"""Fused AdamW + global-norm-clip update kernel.

The optimizer phase is HBM-bound: optax's chain (clip scale -> mu/nu
update -> bias correction -> weight decay -> apply) reads and writes the
full fp32 moment state plus params and grads. One Pallas pass per leaf does
the whole update — read p (bf16), g, mu, nu (f32); write p', mu', nu' —
the roofline minimum of 22 bytes/param. The global grad norm is computed
outside (one fused XLA reduction) and enters as a scalar.

Matches optax.chain(clip_by_global_norm, adamw) semantics (bias-corrected
moments, decoupled weight decay, mu_dtype=f32); equality is unit-tested
against optax. Leaves whose size does not tile by (8, 128) fall back to
the jnp expression of the same math.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas._util import interpret_mode

_LANES = 128
_ROWS = 512  # rows per grid block: (512, 128) f32 blocks, ~0.75 MB x 7 bufs
# How many of the largest leaves take the Pallas path (the rest use the jnp
# expression). Not what limits b1: it has 12 leaves, so every one takes the
# kernel, and the v5e compiler accepts them all inside the full train step
# (PR 21, described v5e:2x2). What it refuses is HBM: this optimizer keeps nu
# in f32 (10.62G of state) where the optax chain keeps it in the params'
# bf16 (8.50G), and the b1 step at 2 x 2048 then needs 16.29G of 15.75G.
PALLAS_LEAVES = 16


def _adamw_kernel(scal_ref, p_ref, g_ref, mu_ref, nu_ref,
                  po_ref, muo_ref, nuo_ref, *, b1, b2, eps, wd):
    # scalars ride a (1, 4) SMEM ref: 2-D scalar blocks are the layout
    # Mosaic's SMEM path expects
    lr = scal_ref[0, 0]
    clip = scal_ref[0, 1]
    c1 = scal_ref[0, 2]       # 1 - b1^t
    c2 = scal_ref[0, 3]       # 1 - b2^t
    g = g_ref[:].astype(jnp.float32) * clip
    mu = b1 * mu_ref[:] + (1.0 - b1) * g
    nu = b2 * nu_ref[:] + (1.0 - b2) * g * g
    p = p_ref[:].astype(jnp.float32)
    update = lr * ((mu / c1) / (jnp.sqrt(nu / c2) + eps) + wd * p)
    po_ref[:] = (p - update).astype(po_ref.dtype)
    muo_ref[:] = mu
    nuo_ref[:] = nu


def _leaf_update(p, g, mu, nu, scalars, *, b1, b2, eps, wd,
                 use_pallas=True):
    n = p.size
    if use_pallas and n % (8 * _LANES) == 0 and not interpret_mode():
        rows = n // _LANES
        br = min(_ROWS, rows)
        if rows % br:
            br = 8  # rows is a multiple of 8 by the check above
        shape2d = (rows, _LANES)
        grid = (rows // br,)
        spec = lambda dt: pl.BlockSpec((br, _LANES), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)
        po, muo, nuo = pl.pallas_call(
            functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                spec(p.dtype), spec(g.dtype),
                spec(jnp.float32), spec(jnp.float32),
            ],
            out_specs=[spec(p.dtype), spec(jnp.float32), spec(jnp.float32)],
            out_shape=[
                jax.ShapeDtypeStruct(shape2d, p.dtype),
                jax.ShapeDtypeStruct(shape2d, jnp.float32),
                jax.ShapeDtypeStruct(shape2d, jnp.float32),
            ],
            interpret=interpret_mode(),
        )(scalars, p.reshape(shape2d), g.reshape(shape2d),
          mu.reshape(shape2d), nu.reshape(shape2d))
        return (po.reshape(p.shape), muo.reshape(p.shape),
                nuo.reshape(p.shape))
    # jnp fallback: same math (odd-shaped leaves, CPU tests)
    lr, clip, c1, c2 = (scalars[0, 0], scalars[0, 1], scalars[0, 2],
                        scalars[0, 3])
    gf = g.astype(jnp.float32) * clip
    mu2 = b1 * mu + (1.0 - b1) * gf
    nu2 = b2 * nu + (1.0 - b2) * gf * gf
    pf = p.astype(jnp.float32)
    update = lr * ((mu2 / c1) / (jnp.sqrt(nu2 / c2) + eps) + wd * pf)
    return (pf - update).astype(p.dtype), mu2, nu2


class FusedAdamWState(NamedTuple):
    count: jax.Array
    mu: Any
    nu: Any


class FusedAdamW:
    """Drop-in for `optax.chain(clip_by_global_norm, adamw)` with a fused
    apply: `apply(grads, state, params) -> (new_params, new_state)` updates
    params directly (one memory pass) instead of returning deltas.
    `make_train_step` detects this interface."""

    def __init__(self, learning_rate: Union[float, Callable[[jax.Array], jax.Array]],
                 b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, clip_norm: float = 1.0):
        self.learning_rate = learning_rate
        self.b1, self.b2, self.eps = b1, b2, eps
        self.weight_decay = weight_decay
        self.clip_norm = clip_norm

    def init(self, params: Any) -> FusedAdamWState:
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return FusedAdamWState(count=jnp.zeros((), jnp.int32),
                               mu=zeros,
                               nu=jax.tree_util.tree_map(jnp.copy, zeros))

    def apply(self, grads: Any, state: FusedAdamWState, params: Any):
        import optax

        gnorm = optax.global_norm(grads)
        clip = jnp.minimum(1.0, self.clip_norm / jnp.maximum(gnorm, 1e-12))
        count = state.count + 1
        lr = (self.learning_rate(state.count)
              if callable(self.learning_rate) else self.learning_rate)
        t = count.astype(jnp.float32)
        scalars = jnp.stack([
            jnp.asarray(lr, jnp.float32),
            clip.astype(jnp.float32),
            1.0 - self.b1 ** t,
            1.0 - self.b2 ** t,
        ]).reshape(1, 4)
        leaves_p, tdef = jax.tree_util.tree_flatten(params)
        leaves_g = tdef.flatten_up_to(grads)
        leaves_mu = tdef.flatten_up_to(state.mu)
        leaves_nu = tdef.flatten_up_to(state.nu)
        big = set(sorted(range(len(leaves_p)),
                         key=lambda i: leaves_p[i].size,
                         reverse=True)[:PALLAS_LEAVES])
        out_p, out_mu, out_nu = [], [], []
        for i, (p, g, mu, nu) in enumerate(
                zip(leaves_p, leaves_g, leaves_mu, leaves_nu)):
            po, muo, nuo = _leaf_update(
                p, g, mu, nu, scalars, b1=self.b1, b2=self.b2, eps=self.eps,
                wd=self.weight_decay, use_pallas=i in big)
            out_p.append(po)
            out_mu.append(muo)
            out_nu.append(nuo)
        return (jax.tree_util.tree_unflatten(tdef, out_p),
                FusedAdamWState(count=count,
                                mu=jax.tree_util.tree_unflatten(tdef, out_mu),
                                nu=jax.tree_util.tree_unflatten(tdef, out_nu)))
