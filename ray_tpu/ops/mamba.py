"""Mamba-1's selective scan: a diagonal state-space recurrence whose step
size and input / output maps depend on the token.

Per channel c of `d_inner` and state column j of `d_state`, with A < 0:

    h_t[j, c] = exp(dt_t[c] A[j, c]) h_(t-1)[j, c] + dt_t[c] u_t[c] B_t[j]
    y_t[c]    = sum_j h_t[j, c] C_t[j] + D[c] u_t[c]

The state is laid out [d_state, d_inner], channels minor: 16 state columns
on the lanes would pad every HBM tile eightfold (a [.., 5120, 16] float32
array takes the room of [.., 5120, 128]); [.., 16, 5120] tiles exactly.

Two forms of the same recurrence:

`selective_step`   one token from the state (decode).
`selective_step_slots`  the same over ONE layer of a stacked slot cache
                   [layers, slots, d_state, d_inner], in place: on a TPU a
                   Pallas kernel that reads and writes only the busy slots'
                   state, once (`ops/pallas/selective_step.py`); elsewhere
                   `selective_step` over the layer's slice.
`selective_scan`   a whole right-padded sequence (prefill), by chunks of
                   positions with h carried from chunk to chunk. One
                   algorithm, two executions chosen from platform and shape
                   (`uses_scan_kernel`): on a TPU a Pallas kernel that keeps
                   a block of channels' h in VMEM while the positions stream
                   past (`ops/pallas/selective_scan.py`); elsewhere an
                   associative scan inside each chunk. Neither ever holds
                   more than one chunk's [chunk, d_state, d_inner] products:
                   an associative scan over a whole prompt would write 320 KB
                   a position and layer at the published widths.

A position with dt = 0 leaves h as it was (exp(0) = 1, nothing added): that
is how the padding of a prompt bucket is made harmless (`valid`).

The depthwise causal convolution in front of the scan is `ops/kda.py`'s
(`short_conv`, `short_conv_step`, `conv_tail`) plus a bias.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import selective_scan as _kernel
from ray_tpu.ops.pallas import selective_step as _step_kernel

F32 = jnp.float32


def selective_step(h: jax.Array, u: jax.Array, dt: jax.Array, A: jax.Array,
                   B: jax.Array, C: jax.Array,
                   D: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """h [b, n, di] float32; u, dt [b, di]; A [n, di]; B, C [b, n]; D [di]
    -> (h_t, y_t [b, di] float32)."""
    u, dt = u.astype(F32), dt.astype(F32)
    h = jnp.exp(dt[:, None] * A) * h + (dt * u)[:, None] * B.astype(F32)[..., None]
    y = jnp.sum(h * C.astype(F32)[..., None], axis=1) + D.astype(F32) * u
    return h, y


def selective_step_slots(state: jax.Array, layer: jax.Array, slots, busy: jax.Array,
                         u: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                         C: jax.Array, D: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """state [L, S, n, di] float32; `layer` a scalar; `slots` =
    `ops.pallas.selective_step.live_slots(lengths)` or None where the kernel
    does not run; busy [S] bool; u, dt [S, di]; A [n, di]; B, C [S, n];
    D [di] -> (state with `layer` advanced, y [S, di] float32). The kernel
    leaves an idle slot's state alone and gives it y = 0; the XLA form
    advances every slot (static shapes; an idle slot's state is replaced at
    admission either way)."""
    if slots is not None:
        state, y = _step_kernel.selective_step_pallas(
            state, layer, slots, u.astype(F32), dt.astype(F32), A.astype(F32),
            B.astype(F32), C.astype(F32))
        y = jnp.where(busy[:, None], y + D.astype(F32) * u.astype(F32), 0.0)
        return state, y
    h, y = selective_step(jax.lax.dynamic_index_in_dim(state, layer, 0, False),
                          u, dt, A, B, C, D)
    return jax.lax.dynamic_update_index_in_dim(state, h, layer, 0), y


def uses_step_kernel(state: jax.Array) -> bool:
    """Whether `selective_step_slots` runs the Pallas kernel over `state`."""
    return _step_kernel.fits(state)


def live_slots(lengths: jax.Array):
    return _step_kernel.live_slots(lengths)


def _scan_chunks(u, dt, A, B, C, h0, chunk: int):
    """The XLA execution: `lax.scan` over chunks, an associative scan over
    the positions of one chunk. u, dt [b, s, di], B, C [b, s, n] (s a
    multiple of `chunk`), h0 [b, n, di] -> (y [b, s, di] without the skip
    term, h after the last position)."""
    b, s, di = u.shape

    def chunks(a):  # [b, s, w] -> [s / chunk, b, chunk, w]
        return jnp.moveaxis(a.reshape(b, s // chunk, chunk, a.shape[-1]), 1, 0)

    def combine(left, right):  # h -> a2 (a1 h + x1) + x2
        return left[0] * right[0], right[0] * left[1] + right[1]

    def one_chunk(h, xs):
        u_c, dt_c, B_c, C_c = xs
        a = jnp.exp(dt_c[:, :, None] * A)                     # [b, T, n, di]
        x = (dt_c * u_c)[:, :, None] * B_c[..., None]
        a_run, x_run = jax.lax.associative_scan(combine, (a, x), axis=1)
        hs = a_run * h[:, None] + x_run
        return hs[:, -1], jnp.einsum("btnd,btn->btd", hs, C_c)

    h, y = jax.lax.scan(one_chunk, h0, tuple(chunks(a) for a in (u, dt, B, C)))
    return jnp.moveaxis(y, 0, 1).reshape(b, s, di), h


def uses_scan_kernel(u: jax.Array) -> bool:
    """Whether `selective_scan` runs the Pallas kernel for u [b, s, di]."""
    return _kernel.fits(u.shape[-1])


def selective_scan(u: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, D: jax.Array, h0: Optional[jax.Array] = None,
                   valid: Optional[jax.Array] = None,
                   chunk: int = 128) -> Tuple[jax.Array, jax.Array]:
    """u, dt [b, s, di] (dt after its softplus); A [n, di] (negative);
    B, C [b, s, n]; D [di]; h0 [b, n, di] or None (zeros); valid [b, s] bool
    or None -> (y [b, s, di] float32, h after the last valid position
    [b, n, di] float32). State, decay and sums in float32. A sequence that is
    no multiple of the chunk is padded with positions that leave h alone."""
    b, s, di = u.shape
    n = A.shape[0]
    u, dt, A, B, C = (a.astype(F32) for a in (u, dt, A, B, C))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    T = min(chunk, -(-s // 8) * 8)
    pad = -s % T
    if pad:
        u, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (u, dt, B, C))
    if h0 is None:
        h0 = jnp.zeros((b, n, di), F32)
    if uses_scan_kernel(u):
        y, h = _kernel.selective_scan_pallas(u, dt, A, B, C, h0, T)
    else:
        y, h = _scan_chunks(u, dt, A, B, C, h0, T)
    return y[:, :s] + D.astype(F32) * u[:, :s], h
