"""The selective scan of `ops/mamba.py` as one Pallas call: a block of
channels' state h [d_state, block] stays in vector registers / VMEM while
the positions of the sequence stream past, so HBM sees u and dt read, y
written and the state once, instead of the [positions, d_state, d_inner]
products an XLA scan materialises (320 KB a position at the published
widths).

Grid (batch, channel block, chunk of T positions); the chunk axis is
sequential and carries h in the resident output block. Inside a chunk the
positions go eight at a time (one aligned [8, block] load of u and of dt, one
aligned store of y); per position the work is elementwise over
[d_state, block] (channels on the lanes, state columns on the sublanes) and
one sublane reduction. B_t and C_t arrive as [d_state, 1] columns
(`[.., s, d_state, 1]` arrays), which broadcast along the lanes.

Everything is float32: the decay exp(dt A) and the running state must be.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util

KERNEL_NAME = "selective_scan"
_BLOCKS = (1024, 512, 256, 128)     # channels a grid step holds, widest first


def block_channels(di: int) -> int:
    return next((b for b in _BLOCKS if di % b == 0), 0)


def fits(di: int) -> bool:
    """On a TPU, at a channel count that tiles the lanes."""
    return _util.on_tpu() and block_channels(di) > 0


def _kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, h_ref, *, T: int):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = h0_ref[...]

    A = a_ref[...]                                            # [n, block]

    def eight(g, h):
        t0 = pl.multiple_of(g * 8, 8)
        u8 = u_ref[pl.ds(t0, 8), :]                           # [8, block]
        dt8 = dt_ref[pl.ds(t0, 8), :]
        rows = []
        for r in range(8):
            dt = dt8[r:r + 1]                                 # [1, block]
            h = jnp.exp(dt * A) * h + (dt * u8[r:r + 1]) * b_ref[t0 + r]
            rows.append(jnp.sum(h * c_ref[t0 + r], axis=0, keepdims=True))
        y_ref[pl.ds(t0, 8), :] = jnp.concatenate(rows, axis=0)
        return h

    h_ref[...] = jax.lax.fori_loop(0, T // 8, eight, h_ref[...])


def selective_scan_pallas(u: jax.Array, dt: jax.Array, A: jax.Array,
                          B: jax.Array, C: jax.Array, h0: jax.Array, T: int):
    """u, dt [b, s, di]; A [n, di]; B, C [b, s, n]; h0 [b, n, di], all
    float32, s a multiple of T, T of 8 -> (y [b, s, di] without the skip
    term, h after the last position [b, n, di])."""
    b, s, di = u.shape
    n = A.shape[0]
    bd = block_channels(di) or di
    seq = pl.BlockSpec((None, T, bd), lambda i, g, k: (i, k, g))
    col = pl.BlockSpec((None, T, n, 1), lambda i, g, k: (i, k, 0, 0))
    state = pl.BlockSpec((None, n, bd), lambda i, g, k: (i, 0, g))
    return pl.pallas_call(
        functools.partial(_kernel, T=T),
        grid=(b, di // bd, s // T),
        in_specs=[seq, seq, pl.BlockSpec((n, bd), lambda i, g, k: (0, g)),
                  col, col, state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((b, s, di), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, di), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=KERNEL_NAME,
        interpret=_util.interpret_mode(),
    )(u, dt, A, B[..., None], C[..., None], h0)
