"""Fused FFN block (rmsnorm -> gate/up -> swiglu -> down + residual) with a
hand-written backward.

Why: BASELINE.md's r04 decomposition pinned the b1 MFU gap on backward-pass
elementwise HBM traffic under dots remat — XLA's backward materializes the
swiglu recompute, d_swiglu, and the re-normed hidden states as separate HBM
round-trips between the dW/dx matmuls. The backward here is written out by
hand in four steps; each was measured as a Pallas kernel against its XLA
expression (v5e, b1 shapes: batch 2 x 2048, d=2048, dff=8192; step time
against the all-XLA custom backward's 243.2 ms), and each is what won:

  1  dW_down = swiglu(gate, up)^T @ dy                              XLA
     (Pallas, swiglu as the prologue: +16.0 ms at 512^3 tiles, +18.6 ms
      with full-d N blocks: the retile removed the gate/up panel re-reads
      and multiplied the dy panel's)
  2  d_s = dy @ W_down^T; dgate = d_s * up * silu'(gate),
     dup = d_s * silu(gate)                                         XLA
     (Pallas, d_s never in HBM: +8.9 ms)
  3  dW_gate = h^T @ dgate, dW_up = h^T @ dup                       Pallas
     (h = x*rstd*nw recomputed as the prologue and never stored, two dots
      sharing one operand panel: -6.3 ms against XLA's
      materialize-then-matmul)
  4  dh = dgate @ Wg^T + dup @ Wu^T and the rmsnorm VJP             XLA
     (Pallas re-read the weight panels per row block and lost more than
      its fusion saved; see the note at the call site)

The custom_vjp itself is the main win: saving gate/up and hand-writing the
backward beats autodiff under dots remat by ~7 ms with every step on XLA.

Step 3's kernel runs where (T, d, dff) tile by its blocks; where they do not
(a width that leaves 256 modulo 512) the same step is its XLA expression,
which is also what the tests compare the kernel with. The code chooses from
the shapes; nothing is set.

The forward stays plain XLA (it already runs at ~93% of ideal). Residuals
saved — x, rstd, gate, up — are the same set the `dots` remat policy keeps,
so memory is unchanged; the block must sit OUTSIDE any jax.checkpoint
region (a custom_vjp inside remat would have its forward replayed to
regenerate residuals, re-running all three matmuls).

No reference counterpart: hellofinch/ray ships no kernels (SURVEY.md §2.4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas._util import interpret_mode

# Tile size of every block dimension: 512 keeps the MXU busy with full
# 128-lane tiles while the double-buffered operands and the two f32
# accumulators stay inside the ~16 MB VMEM budget.
_BLOCK = 512


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _dsilu(x):
    s = jax.nn.sigmoid(x)
    return s * (1.0 + x * (1.0 - s))


# ------------------------------------------------------------------ kernels


def _dw_gateup_kernel(x_ref, rstd_ref, nw_ref, dgate_ref, dup_ref,
                      dwg_ref, dwu_ref, accg_ref, accu_ref):
    """dW_gate/dW_up = h^T @ dgate/dup with h = (x * rstd * nw) recomputed
    per tile (the normed hidden state is never stored)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        accg_ref[:] = jnp.zeros_like(accg_ref)
        accu_ref[:] = jnp.zeros_like(accu_ref)

    h = (x_ref[:].astype(jnp.float32) * rstd_ref[:]
         * nw_ref[:].astype(jnp.float32)).astype(dgate_ref.dtype)  # [bk, bm]
    accg_ref[:] += jax.lax.dot_general(
        h, dgate_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    accu_ref[:] += jax.lax.dot_general(
        h, dup_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        dwg_ref[:] = accg_ref[:].astype(dwg_ref.dtype)
        dwu_ref[:] = accu_ref[:].astype(dwu_ref.dtype)


# ------------------------------------------------------------- entry points


def _fwd_impl(x2d, nw, wg, wu, wd, eps):
    xf = x2d.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    h = (xf * rstd * nw.astype(jnp.float32)).astype(x2d.dtype)
    gate = h @ wg
    up = h @ wu
    out = (_silu(gate.astype(jnp.float32)).astype(x2d.dtype) * up) @ wd
    return x2d + out.astype(x2d.dtype), rstd, gate, up


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ffn_block(x: jax.Array, norm_w: jax.Array, w_gate: jax.Array,
              w_up: jax.Array, w_down: jax.Array, eps: float = 1e-5) -> jax.Array:
    """x [..., d] -> x + W_down(swiglu(Wg(rmsnorm(x)), Wu(rmsnorm(x))))."""
    shape = x.shape
    y, _, _, _ = _fwd_impl(x.reshape(-1, shape[-1]), norm_w, w_gate, w_up,
                           w_down, eps)
    return y.reshape(shape)


def _vjp_fwd(x, norm_w, w_gate, w_up, w_down, eps):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    y, rstd, gate, up = _fwd_impl(x2d, norm_w, w_gate, w_up, w_down, eps)
    return y.reshape(shape), (x2d, rstd, gate, up, norm_w, w_gate, w_up,
                              w_down, shape)


def _vjp_bwd(eps, res, dy):
    x2d, rstd, gate, up, nw, wg, wu, wd, shape = res
    d = shape[-1]
    dy2d = dy.reshape(-1, d)
    T = x2d.shape[0]
    dff = wg.shape[1]

    # 1: dW_down [dff, d]
    s_act = (_silu(gate.astype(jnp.float32))
             * up.astype(jnp.float32)).astype(gate.dtype)
    dwd = jax.lax.dot_general(
        s_act, dy2d, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(wd.dtype)

    # 2: dgate/dup [T, dff]
    ds = jax.lax.dot_general(dy2d, wd, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    gf = gate.astype(jnp.float32)
    uf = up.astype(jnp.float32)
    dgate = (ds * uf * _dsilu(gf)).astype(gate.dtype)
    dup = (ds * _silu(gf)).astype(up.dtype)

    # 3: dW_gate/dW_up [d, dff]: the kernel where the shapes tile by its
    # blocks, the same step in XLA where they do not
    bm, bn, bk = min(_BLOCK, d), min(_BLOCK, dff), min(_BLOCK, T)
    if d % bm or dff % bn or T % bk:
        h = (x2d.astype(jnp.float32) * rstd
             * nw.astype(jnp.float32)).astype(x2d.dtype)
        dwg = jax.lax.dot_general(h, dgate, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32).astype(wg.dtype)
        dwu = jax.lax.dot_general(h, dup, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32).astype(wu.dtype)
    else:
        dwg, dwu = pl.pallas_call(
            _dw_gateup_kernel,
            grid=(d // bm, dff // bn, T // bk),
            in_specs=[
                pl.BlockSpec((bk, bm), lambda i, j, k: (k, i), memory_space=pltpu.VMEM),
                pl.BlockSpec((bk, 1), lambda i, j, k: (k, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bm), lambda i, j, k: (0, i), memory_space=pltpu.VMEM),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j), memory_space=pltpu.VMEM),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j), memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda i, j, k: (i, j), memory_space=pltpu.VMEM),
                pl.BlockSpec((bm, bn), lambda i, j, k: (i, j), memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((d, dff), wg.dtype),
                jax.ShapeDtypeStruct((d, dff), wu.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                            pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret_mode(),
        )(x2d, rstd, nw.reshape(1, -1), dgate, dup)

    # 4: dh matmuls + rmsnorm VJP stay XLA: a measured Pallas
    # variant (full-d N blocks so the VJP row-reduction fits one tile) had
    # to re-read the [d, dff] weight panels once per 128-row block, ~2 GB
    # of extra HBM traffic per layer, and lost more than the elementwise
    # fusion saved. XLA tiles the matmul properly and fuses the elementwise
    # VJP chain into one pass over dh.
    dh = (jax.lax.dot_general(dgate, wg, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
          + jax.lax.dot_general(dup, wu, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32))
    xf = x2d.astype(jnp.float32)
    wdh = dh * nw.astype(jnp.float32)
    proj = jnp.sum(wdh * xf, axis=-1, keepdims=True) / d
    dx = (rstd * (wdh - xf * rstd * rstd * proj)
          + dy2d.astype(jnp.float32)).astype(x2d.dtype)
    dnw = jnp.sum(dh * xf * rstd, axis=0).astype(nw.dtype)
    return dx.reshape(shape), dnw, dwg, dwu, dwd


ffn_block.defvjp(_vjp_fwd, _vjp_bwd)
