"""The chunked (SSD) form of `ops/ssd.py`'s recurrence as one Pallas call:
the state h [N, channels of a head group] stays in the resident output
block while the chunks of Q positions stream past, and inside a chunk the
recurrence is four matrix products a head, so HBM sees x read, y written
and the state once; the [Q, Q] decay matrix of a head and chunk never
leaves VMEM (an XLA scan over chunks writes and reads it, 33 MB a chunk and
layer at the published widths).

Grid (batch, group of `_HEADS` heads, chunk); the chunk axis is sequential.
x and C are read IN PLACE out of the convolved [x | B | C] array (blocks of
its columns: a sliced copy of x is 0.4 GB at 12288 positions). A grid step
holds the chunk's C [Q, N] and B^T [N, Q] (shared by every head: one group),
G = C B^T [Q, Q] once, and for each head of the group its rows of step sizes
`dt` and of decay sums `cum` [1, Q] (the inclusive sum of dt A inside the
chunk, made outside: a cumulative sum over 256 positions is XLA's). Per
head, with L[i, j] = exp(cum_i - cum_j) for j <= i, else 0:

    y     = (G * L * dt) x  +  exp(cum) * (C h)  +  D x          [Q, P]
    h_new = exp(cum_Q) h  +  (B^T * exp(cum_Q - cum) * dt) x     [N, P]

(dt_j weighs COLUMN j of the decay matrix, a row broadcast along the
sublanes, so dt x is never written down.) The column form of `cum` ([Q, 1],
broadcast along the lanes) is the transpose of its row form broadcast along
the sublanes: one aligned [Q, Q] transpose a head. Heads are P = 64 channels wide, half a lane tile:
two heads share a [., 128] block, and each head's products run over the
pair's block with the other head's lanes zeroed, which costs the MXU what a
product 64 wide costs it and needs no slice inside a tile.

Everything is float32: the decays compound over the prompt, the state is
carried, and the products' operands are the scan's own inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util

KERNEL_NAME = "ssd_scan"
_HEADS = 8           # heads a grid step holds (their rows of `cum`: a sublane tile)


def fits(chunk: int, n_heads: int, head_dim: int, d_state: int) -> bool:
    """On a TPU, at a chunk and a state width that tile the lanes, heads of
    half a lane tile in whole groups."""
    return (_util.on_tpu() and chunk % 128 == 0 and d_state % 128 == 0
            and head_dim == 64 and n_heads % _HEADS == 0)


def _kernel(x_ref, c_ref, dt_ref, cum_ref, bt_ref, d_ref, h0_ref, y_ref, h_ref, *,
            P: int):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = h0_ref[...]

    Q, N = c_ref.shape
    C, Bt = c_ref[...], bt_ref[...]                            # [Q, N], [N, Q]
    G = jnp.dot(C, Bt, preferred_element_type=jnp.float32)     # [Q, Q]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    width = 2 * P                                              # a pair of heads
    first = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) < P
    for pair in range(cum_ref.shape[0] // 2):
        at = slice(pair * width, (pair + 1) * width)
        x, h = x_ref[:, at], h_ref[:, at]                      # [Q, 2P], [N, 2P]
        carried = jnp.dot(C, h, preferred_element_type=jnp.float32)
        y = d_ref[:, at] * x
        h_new = jnp.zeros(h.shape, jnp.float32)
        for r in range(2):
            mine = first if r == 0 else jnp.logical_not(first)
            head = slice(2 * pair + r, 2 * pair + r + 1)
            cum, dt = cum_ref[head, :], dt_ref[head, :]        # [1, Q]
            row = jnp.broadcast_to(cum, (Q, Q))
            col = row.T                                        # col[i, j] = cum_i
            L = jnp.where(lower, jnp.exp(jnp.minimum(col - row, 0.0)), 0.0) * dt
            xm = jnp.where(mine, x, 0.0)
            here = col[:, :width] if width <= Q else \
                jnp.broadcast_to(col[:, :1], (Q, width))
            y = y + jnp.dot(G * L, xm, preferred_element_type=jnp.float32) \
                + jnp.where(mine, jnp.exp(here) * carried, 0.0)
            end = cum[:, Q - 1:Q]                              # [1, 1]: cum_Q
            into = Bt * (jnp.exp(end - cum) * dt)              # [N, Q]
            h_new = h_new + jnp.where(mine, jnp.exp(end) * h, 0.0) \
                + jnp.dot(into, xm, preferred_element_type=jnp.float32)
        y_ref[:, at] = y
        h_ref[:, at] = h_new


def ssd_scan_pallas(xbc: jax.Array, dt: jax.Array, cum: jax.Array, D: jax.Array,
                    h0: jax.Array, Q: int, P: int):
    """xbc [b, s, HP + 2N]: the convolved x, B and C side by side; dt, cum
    [b, H, s] (the step sizes, 0 at a position that leaves the state alone,
    and the inclusive sum of dt A inside each chunk of Q positions); D [1, HP]
    (the skip weight of each channel); h0 [b, N, HP], all float32, s a
    multiple of Q, HP and N of 128 -> (y [b, s, HP], h after the last
    position [b, N, HP])."""
    b, s, _ = xbc.shape
    n, hp = h0.shape[1:]
    heads = _HEADS if (hp // P) % _HEADS == 0 else hp // P     # even, or pairs break
    bd = heads * P
    seq = pl.BlockSpec((None, Q, bd), lambda i, g, k: (i, k, g))
    rows = pl.BlockSpec((None, heads, Q), lambda i, g, k: (i, g, k))
    state = pl.BlockSpec((None, n, bd), lambda i, g, k: (i, 0, g))
    Bt = jnp.swapaxes(xbc[:, :, hp:hp + n], 1, 2)
    return pl.pallas_call(
        functools.partial(_kernel, P=P),
        grid=(b, hp // bd, s // Q),
        in_specs=[seq,                                          # x: columns [0, HP)
                  pl.BlockSpec((None, Q, n), lambda i, g, k: (i, k, hp // n + 1)),  # C
                  rows, rows,
                  pl.BlockSpec((None, n, Q), lambda i, g, k: (i, 0, k)),
                  pl.BlockSpec((1, bd), lambda i, g, k: (0, g)), state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((b, s, hp), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, hp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2**20),
        name=KERNEL_NAME,
        interpret=_util.interpret_mode(),
    )(xbc, xbc, dt, cum, Bt, D, h0)
