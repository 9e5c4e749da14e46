"""Mamba-2's state-space recurrence (SSD): a state-space layer whose decay
is a SCALAR a head, so that a prompt's recurrence is matrix products over
chunks of positions.

Per head h of H, channel p of the head's P, state column n of N, with
A[h] < 0 and B_t, C_t [N] shared by every head (one group):

    S_t[n, h, p] = exp(dt_t[h] A[h]) S_(t-1)[n, h, p] + dt_t[h] x_t[h, p] B_t[n]
    y_t[h, p]    = sum_n S_t[n, h, p] C_t[n] + D[h] x_t[h, p]

The state is laid out [N, H P], channels minor, as `ops/mamba.py` lays its
own out and for its reason read the other way: a head's 64 channels on the
lanes would pad every HBM tile twofold; [.., 128, 8192] float32 tiles
exactly (4.19 MB a slot and layer at the published widths, thirteen times
Mamba-1's), the per-channel rows (decay, dt x) broadcast along the sublanes
and B_t, C_t along the lanes.

Two forms of the same recurrence:

`ssd_step`        one token from the state (decode).
`ssd_step_slots`  the same over ONE layer of a stacked slot cache
                  [layers, slots, N, H P], in place: on a TPU a Pallas
                  kernel that reads and writes only the busy slots' state,
                  once (`ops/pallas/ssd_step.py`); elsewhere `ssd_step` over
                  the layer's slice.
`ssd_scan`        a whole right-padded sequence (prefill) by chunks of Q
                  positions, S carried from chunk to chunk in float32. Inside
                  a chunk, with cum_i the sum of dt A up to position i and
                  L[i, j] = exp(cum_i - cum_j) for j <= i, else 0:

                      Y = ((C B^T) * L) (dt x)  +  exp(cum) * (C S_in)
                      S_out = exp(cum_Q) S_in + (B * exp(cum_Q - cum))^T (dt x)

                  One algorithm, two executions chosen from platform and
                  shape (`uses_scan_kernel`): on a TPU a Pallas kernel that
                  keeps a head group's S and each [Q, Q] decay matrix in
                  VMEM (`ops/pallas/ssd_scan.py`); elsewhere a `lax.scan`
                  over the chunks of einsums. Neither ever holds anything of
                  size [positions, H, P, N].

A position with dt = 0 leaves S as it was (exp(0) = 1, nothing added): that
is how the padding of a prompt bucket is made harmless (`valid`).

The depthwise causal convolution in front of the scan is `ops/kda.py`'s
(`short_conv`, `short_conv_step`, `conv_tail`) plus a bias, over x, B and C
together.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import ssd_scan as _kernel
from ray_tpu.ops.pallas import ssd_step as _step_kernel

F32 = jnp.float32


def _per_channel(a: jax.Array, P: int) -> jax.Array:
    """[.., H] -> [.., H P]: a head's value for each of its channels."""
    return jnp.repeat(a, P, axis=-1)


def ssd_step(S: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
             B: jax.Array, C: jax.Array, D: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """S [b, N, HP] float32; x [b, H, P]; dt [b, H] (after its softplus);
    A, D [H]; B, C [b, N] -> (S_t, y_t [b, HP] float32)."""
    b, H, P = x.shape
    x, dt = x.astype(F32), dt.astype(F32)
    decay = _per_channel(jnp.exp(dt * A.astype(F32)), P)              # [b, HP]
    dtx = (dt[..., None] * x).reshape(b, H * P)
    S = decay[:, None] * S + dtx[:, None] * B.astype(F32)[..., None]
    y = jnp.sum(S * C.astype(F32)[..., None], axis=1)
    return S, y + _per_channel(D.astype(F32), P) * x.reshape(b, H * P)


def ssd_step_slots(state: jax.Array, layer: jax.Array, slots, busy: jax.Array,
                   x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, D: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """state [L, S, N, HP] float32; `layer` a scalar; `slots` =
    `live_slots(lengths)` or None where the kernel does not run; busy [S]
    bool; x [S, H, P]; dt [S, H]; A, D [H]; B, C [S, N] -> (state with
    `layer` advanced, y [S, HP] float32). The kernel leaves an idle slot's
    state alone and gives it y = 0; the XLA form advances every slot (static
    shapes; an idle slot's state is replaced at admission either way)."""
    if slots is not None:
        S_, H, P = x.shape
        x, dt = x.astype(F32), dt.astype(F32)
        state, y = _step_kernel.ssd_step_pallas(
            state, layer, slots, _per_channel(jnp.exp(dt * A.astype(F32)), P),
            (dt[..., None] * x).reshape(S_, H * P), B.astype(F32), C.astype(F32))
        skip = _per_channel(D.astype(F32), P) * x.reshape(S_, H * P)
        return state, jnp.where(busy[:, None], y + skip, 0.0)
    S, y = ssd_step(jax.lax.dynamic_index_in_dim(state, layer, 0, False),
                    x, dt, A, B, C, D)
    return jax.lax.dynamic_update_index_in_dim(state, S, layer, 0), y


def uses_step_kernel(state: jax.Array) -> bool:
    """Whether `ssd_step_slots` runs the Pallas kernel over `state`."""
    return _step_kernel.fits(state)


def live_slots(lengths: jax.Array):
    return _step_kernel.live_slots(lengths)


def _scan_chunks(x, dt, cum, B, C, D, h0, Q: int, P: int):
    """The XLA execution: `lax.scan` over chunks of einsums. x [b, s, HP];
    dt, cum [b, H, s] (cum inclusive inside each chunk); B, C [b, s, N] (s a
    multiple of Q); D [HP]; h0 [b, N, HP] -> (y [b, s, HP], S after the last
    position)."""
    b, s, hp = x.shape
    H, n = hp // P, B.shape[-1]

    def chunks(a):  # [b, s, ...] -> [s / Q, b, Q, ...]
        return jnp.moveaxis(a.reshape((b, s // Q, Q) + a.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((Q, Q), bool))

    def one_chunk(S, xs):
        x_c, dt_c, cum_c, B_c, C_c = xs            # dt_c, cum_c [b, Q, H]
        dtx = dt_c[..., None] * x_c.reshape(b, Q, H, P)
        S = S.reshape(b, n, H, P)
        diff = cum_c[:, :, None, :] - cum_c[:, None, :, :]           # [b, i, j, H]
        L = jnp.where(lower[None, :, :, None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        G = jnp.einsum("bin,bjn->bij", C_c, B_c)
        y = jnp.einsum("bij,bijh,bjhp->bihp", G, L, dtx) \
            + jnp.exp(cum_c)[..., None] * jnp.einsum("bin,bnhp->bihp", C_c, S)
        end = cum_c[:, -1:]                                          # [b, 1, H]
        S = jnp.exp(end)[:, :, :, None] * S + jnp.einsum(
            "bjn,bjh,bjhp->bnhp", B_c, jnp.exp(end - cum_c), dtx)
        return S.reshape(b, n, hp), y.reshape(b, Q, hp) + D * x_c

    per_pos = lambda a: chunks(jnp.moveaxis(a, 1, 2))
    S, y = jax.lax.scan(one_chunk, h0, (chunks(x), per_pos(dt), per_pos(cum),
                                        chunks(B), chunks(C)))
    return jnp.moveaxis(y, 0, 1).reshape(b, s, hp), S


def uses_scan_kernel(chunk: int, n_heads: int, head_dim: int, d_state: int) -> bool:
    """Whether `ssd_scan` runs the Pallas kernel at this chunk and widths."""
    return _kernel.fits(chunk, n_heads, head_dim, d_state)


def ssd_scan(xbc: jax.Array, dt: jax.Array, A: jax.Array, D: jax.Array,
             d_state: int, h0: Optional[jax.Array] = None,
             valid: Optional[jax.Array] = None,
             chunk: int = 256) -> Tuple[jax.Array, jax.Array]:
    """xbc [b, s, HP + 2N]: x [H, P], B [N] and C [N] of every position
    side by side, as the convolution over the three leaves them (the kernel
    reads x and C in place); dt [b, s, H] (after its softplus); A, D [H] (A
    negative); h0 [b, N, HP] or None (zeros); valid [b, s] bool or None ->
    (y [b, s, HP] float32, S after the last valid position [b, N, HP]
    float32). State, decays and sums in float32. A sequence that is no
    multiple of the chunk is padded with positions that leave S alone."""
    b, s, H = dt.shape
    n, hp = d_state, xbc.shape[-1] - 2 * d_state
    P = hp // H
    xbc, dt, A = (a.astype(F32) for a in (xbc, dt, A))
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    Q = min(chunk, -(-s // 8) * 8)
    pad = -s % Q
    if pad:
        xbc, dt = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (xbc, dt))
    # the decay sums inside each chunk; they and dt a row a head: [b, H, s]
    cum = jnp.cumsum((dt * A).reshape(b, -1, Q, H), axis=2).reshape(b, -1, H)
    dt, cum = jnp.moveaxis(dt, 1, 2), jnp.moveaxis(cum, 1, 2)
    if h0 is None:
        h0 = jnp.zeros((b, n, hp), F32)
    skip = _per_channel(D.astype(F32), P)
    if uses_scan_kernel(Q, H, P, n):
        y, S = _kernel.ssd_scan_pallas(xbc, dt, cum, skip[None], h0, Q, P)
    else:
        x, B, C = jnp.split(xbc, (hp, hp + n), axis=-1)
        y, S = _scan_chunks(x, dt, cum, B, C, skip, h0, Q, P)
    return y[:, :s], S
