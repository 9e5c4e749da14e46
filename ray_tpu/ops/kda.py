"""Kimi Delta Attention (KDA): the gated delta rule with a per-channel decay.

Per head, with state S in R^(dk x dv), decay alpha_t = exp(g_t) in (0,1)^dk
and write strength beta_t in (0,1):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

Two forms of the same recurrence:

`kda_step`      one token from the state (decode). Two passes over S: one
                reads S for the two products S^T (alpha*k) and S^T (alpha*q),
                one rewrites it; o_t follows algebraically, so the new state
                is never read back.
`kda_chunked`   a whole sequence in chunks of C (prefill), the state carried
                from chunk to chunk. With u_t = beta_t (v_t - k_t^T Diag(alpha_t)
                S_(t-1)) the recurrence unrolls to S_t = Diag(G_t) S_0 +
                sum_(i<=t) Diag(G_t / G_i) k_i u_i^T (G the running product of
                alpha inside the chunk), which makes the u of one chunk the
                solution of a unit lower-triangular system (I + A) U =
                beta (V - K+ S_0). (I + A)^-1 does not depend on the state,
                so it and all it multiplies are computed for every chunk at
                once and only three products a chunk stay in the scan.
                Every decay ratio is formed as exp(log G_t - log G_i) with
                i <= t, so nothing overflows however fast a channel
                forgets.

A position with beta = 0 and g = 0 leaves S as it was: that is how the
padding of a prompt bucket is made harmless (`models/hybrid.py`).

`short_conv` / `short_conv_step` are the depthwise causal convolution in
front of q, k and v, in the same two forms; the step form carries the last
K-1 inputs.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def l2_norm(x: jax.Array) -> jax.Array:
    xf = x.astype(F32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + 1e-6)


def short_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """x [b, s, ch], w [K, ch]: y_t = sum_j w[j] * x_(t-K+1+j), zeros
    before the sequence."""
    K, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[j] for j in range(K))


def conv_tail(x: jax.Array, true_len: jax.Array, K: int) -> jax.Array:
    """The last K-1 inputs of each row's true sequence, [b, K-1, ch]
    (zeros where the sequence is shorter than that)."""
    idx = true_len[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]   # [b, K-1]
    rows = jnp.take_along_axis(x, jnp.maximum(idx, 0)[..., None], axis=1)
    return jnp.where((idx >= 0)[..., None], rows, jnp.zeros((), x.dtype))


def short_conv_step(x: jax.Array, tail: jax.Array,
                    w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x [b, ch] the new input, tail [b, K-1, ch] -> (y [b, ch], new tail)."""
    window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    y = jnp.sum(window.astype(F32) * w.astype(F32)[None], axis=1)
    return y, window[:, 1:]


def kda_step(S: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
             g: jax.Array, beta: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """S [b, H, dk, dv] float32; q, k, g [b, H, dk]; v [b, H, dv];
    beta [b, H] -> (S_t, o_t [b, H, dv])."""
    alpha = jnp.exp(g.astype(F32))
    ka, qa = k * alpha, q * alpha
    # one pass over S for both S'^T k and S'^T q (S' = Diag(alpha) S)
    both = jnp.einsum("bhnk,bhkv->bhnv", jnp.stack([ka, qa], axis=2), S,
                      precision="highest")
    u = beta[..., None] * (v - both[:, :, 0])
    o = both[:, :, 1] + u * jnp.sum(k * q, axis=-1, keepdims=True)
    S = S * alpha[..., None] + k[..., None] * u[..., None, :]
    return S, o


def _decayed_grams(q: jax.Array, k: jax.Array, G: jax.Array, sub: int):
    """kk[t, i] = sum_c k[t,c] k[i,c] exp(G[t,c] - G[i,c]) and the same with
    q[t] for k[t], for i <= t inside one chunk (zero above the diagonal);
    q, k, G [..., C, dk] with G the running log decay (non-increasing in t).

    Formed in sub-blocks of `sub` positions so that no exponent is ever
    positive and no [C, C, dk] tensor is built: inside a block the decay
    ratios are taken directly ([sub, sub, dk]); between a block I and an
    earlier block J they factor through the first position r of I,
    exp(G_t - G_r) * exp(G_r - G_i), both factors <= 1, which makes the
    block a matrix product over the channels."""
    lead, (C, dk) = G.shape[:-2], G.shape[-2:]
    nb = C // sub
    q5, k5, G5 = (a.reshape(lead + (nb, sub, dk)) for a in (q, k, G))
    rows = jnp.concatenate([k5, q5], axis=-2)                   # [.., nb, 2 sub, dk]
    Gt = jnp.concatenate([G5, G5], axis=-2)
    # inside a block: D[t, i, c] = exp(G_t - G_i), masked BEFORE the exp
    inside = jnp.tril(jnp.ones((sub, sub), bool))
    inside = jnp.concatenate([inside, inside], axis=0)          # [2 sub, sub]
    D = jnp.exp(jnp.where(inside[..., None],
                          Gt[..., :, None, :] - G5[..., None, :, :], -jnp.inf))
    diag = jnp.sum(rows[..., :, None, :] * k5[..., None, :, :] * D, axis=-1)
    eye = jnp.eye(nb, dtype=F32)
    full = diag[..., :, :, None, :] * eye[:, None, :, None]     # [.., I, t, J, i]
    if nb > 1:
        first = G5[..., :1, :]                                  # G at each block's start
        left = rows * jnp.exp(Gt - first)
        earlier = jnp.tril(jnp.ones((nb, nb), bool), -1)[:, :, None, None]
        right = k5[..., None, :, :, :] * jnp.exp(jnp.where(
            earlier, first[..., :, None, :, :] - G5[..., None, :, :, :], -jnp.inf))
        full = full + jnp.einsum("...Itc,...IJic->...ItJi", left, right,
                                 precision="highest")
    full = full.reshape(lead + (nb, 2, sub, C))
    kk, qk = (full[..., j, :, :].reshape(lead + (C, C)) for j in (0, 1))
    return kk, qk


def _inv_unit_lower(A: jax.Array, base: int = 16) -> jax.Array:
    """(I + A)^-1 for strictly lower-triangular A [..., n, n]: forward
    substitution by rows inside the diagonal blocks of `base` (all blocks at
    once, `base` small steps), then pairs of blocks merged level by level:
    [[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]."""
    lead, n = A.shape[:-2], A.shape[-1]
    if n % base or (n // base) & (n // base - 1):
        base = n                                                # one block
    nb = n // base

    def diagonal_blocks(size, lower_left):
        m = n // size
        A4 = A.reshape(lead + (m, size, m, size))
        if lower_left:      # the lower-left quarter of every diagonal block
            return jnp.stack([A4[..., i, size // 2:, i, :size // 2]
                              for i in range(m)], axis=-3)
        return jnp.stack([A4[..., i, :, i, :] for i in range(m)], axis=-3)

    blocks = diagonal_blocks(base, False)                       # [.., nb, base, base]
    T = jnp.broadcast_to(jnp.eye(base, dtype=A.dtype), blocks.shape)
    for t in range(1, base):
        T = T.at[..., t, :].add(-jnp.einsum(
            "...i,...ij->...j", blocks[..., t, :t], T[..., :t, :],
            precision="highest"))
    size = base
    while size < n:
        size *= 2
        T = T.reshape(lead + (n // size, 2, size // 2, size // 2))
        P, Q = T[..., 0, :, :], T[..., 1, :, :]
        low = -jnp.einsum("...ij,...jk,...kl->...il", Q,
                          diagonal_blocks(size, True), P, precision="highest")
        T = jnp.concatenate(
            [jnp.concatenate([P, jnp.zeros_like(P)], axis=-1),
             jnp.concatenate([low, Q], axis=-1)], axis=-2)
    return T.reshape(lead + (n, n))


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, S0: Optional[jax.Array] = None,
                chunk: int = 64) -> Tuple[jax.Array, jax.Array]:
    """q, k, g [b, s, H, dk]; v [b, s, H, dv]; beta [b, s, H] (all
    float32) -> (o [b, s, H, dv], S after the last position [b, H, dk, dv]).
    A sequence that is no multiple of the chunk is padded with positions
    that leave the state alone.

    Everything that does not need the state is computed for ALL chunks at
    once, outside the scan: the decayed products, T = (I + A)^-1, and with
    it W = T beta K+ and U0 = T beta V, so that U = U0 - W S_0. The scan
    over chunks carries S through three matrix products a chunk."""
    b, s, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, s)
    pad = -s % C
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // C

    def chunks(a):  # [b, n*C, H, ...] -> [n, b, H, C, ...]
        a = a.reshape((b, n, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)                # log of the running decay, <= 0
    eG = jnp.exp(G)
    kk, qk = _decayed_grams(q, k, G, 16 if C % 16 == 0 else C)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    T = _inv_unit_lower(jnp.where(strict, beta[..., None] * kk, 0.0))
    W = jnp.einsum("...ti,...ic->...tc", T, beta[..., None] * k * eG,
                   precision="highest")
    U0 = jnp.einsum("...ti,...iv->...tv", T, beta[..., None] * v,
                    precision="highest")
    # [W; q eG] meet the state in one product
    WQ = jnp.concatenate([W, q * eG], axis=3)                    # [n, b, H, 2C, dk]
    to_end = k * jnp.exp(G[..., -1:, :] - G)  # decay from each position to the end
    last = eG[..., -1, :]                                        # [n, b, H, dk]

    def one_chunk(S, xs):
        WQc, U0c, qkc, kc, ec = xs
        both = jnp.einsum("bhck,bhkv->bhcv", WQc, S, precision="highest")
        U = U0c - both[:, :, :C]
        o = both[:, :, C:] + jnp.einsum("bhci,bhiv->bhcv", qkc, U,
                                        precision="highest")
        S = S * ec[..., None] + jnp.einsum("bhck,bhcv->bhkv", kc, U,
                                           precision="highest")
        return S, o

    if S0 is None:
        S0 = jnp.zeros((b, H, dk, dv), F32)
    S, o = jax.lax.scan(one_chunk, S0, (WQ, U0, qk, to_end, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * C, H, dv)
    return o[:, :s], S
