"""Plain reference of the EvaByte block stack (EvaByte 6.5B, byte level):
EVA attention with rotary positions, dense SwiGLU, RMSNorms that scale by
(1 + w) (`norm_add_unit_offset`), a float32 residual stream
(`fp32_skip_add`), an untied head of `num_pred_heads` x `vocab_size` columns
with float32 logits (`fp32_logits`).

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the equations below and not from the program: no cache, no
kernels, no batching tricks; for each query an explicit mask over positions
and one over chunk summaries. Only so that 28,672 positions at the
published widths fit a chip, the queries are taken a window at a time (the
keys they can see are then the window's own positions and every summary).
Weights arrive as the benchmark's initialiser made them (`params["runs"][0]`:
every leaf stacked on a leading layer axis, in the type they are served in)
and are raised to float32 one layer at a time. `c` is the configuration
file's dict. Nothing here imports the program.

Per head (d = head width, s = d^-1/2, positions from 0, C = `chunk_size`,
W = `window_size`):

    q, k, v = h W_q, h W_k, h W_v; q and k rotated (theta `rope_theta`)
    chunk c = positions [C c, C c + C); from the ROTATED keys
        a_m    = softmax over m in the chunk of (s phi . k_m)
        kbar_c = sum_m a_m k_m + mu        vbar_c = sum_m a_m v_m
    query n, window w = n // W, ONE softmax over
        E_n = {m : m // W == w, m <= n}         scores s q_n . k_m
        R_n = {c : (C c) // W < w}              scores s q_n . kbar_c
    o_n = sum_E p_m v_m + sum_R p_c vbar_c;  then W_o
    head j (0..num_pred_heads-1) at position i scores the byte at i + 1 + j

Departures from the published description, and what is `assumed` (the
configuration file lists the same, each with its reason): the summary's form
(`phi`, `mu` a head; softmax over the chunk of s phi . k; `mu` added to the
pooled key; the rotated keys pooled) is written from memory of the published
modeling code, with no network to check it; windows do not slide; rotary
positions in the half-rotation layout over the whole head; the head's
columns lie [j x vocab_size + byte]; `phi` and `mu` are seeded uniform and
non-zero. A partial last chunk has no summary (nobody could see it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rotate(x, positions, theta):
    """x [b, s, H, d] at positions [s]: pairs (i, i + d/2) turned by
    position x theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = positions.astype(F32)[:, None] * freq[None, :]          # [s, d/2]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _eva(x, p, c):
    """x [b, s, d_model] (already normed) -> [b, s, d_model]."""
    b, s, _ = x.shape
    H, W, C = c["num_attention_heads"], c["window_size"], c["chunk_size"]
    d = c["hidden_size"] // H
    scale = d ** -0.5
    pos = jnp.arange(s)
    q = _rotate((x @ p["wq"]).reshape(b, s, H, d), pos, float(c["rope_theta"]))
    k = _rotate((x @ p["wk"]).reshape(b, s, H, d), pos, float(c["rope_theta"]))
    v = (x @ p["wv"]).reshape(b, s, H, d)

    n_chunks = s // C                              # whole chunks only
    kc = k[:, :n_chunks * C].reshape(b, n_chunks, C, H, d)
    vc = v[:, :n_chunks * C].reshape(b, n_chunks, C, H, d)
    a = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, p["phi"]) * scale, axis=2)
    kbar = jnp.einsum("bnch,bnchd->bnhd", a, kc) + p["mu"][None, None]
    vbar = jnp.einsum("bnch,bnchd->bnhd", a, vc)
    chunk_window = (jnp.arange(n_chunks) * C) // W                   # [n_chunks]

    outs = []
    for lo in range(0, s, W):                      # the queries, a window at a time
        hi = min(lo + W, s)
        n, m = pos[lo:hi], pos[lo:hi]              # this window's own positions
        exact_ok = (m[None, :] // W == n[:, None] // W) & (m[None, :] <= n[:, None])
        pooled_ok = chunk_window[None, :] < (n[:, None] // W)
        exact = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, lo:hi]) * scale
        pooled = jnp.einsum("bqhd,bnhd->bhqn", q[:, lo:hi], kbar) * scale
        sc = jnp.concatenate([jnp.where(exact_ok, exact, -jnp.inf),
                              jnp.where(pooled_ok, pooled, -jnp.inf)], axis=-1)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", pr[..., :hi - lo], v[:, lo:hi])
                    + jnp.einsum("bhqn,bnhd->bqhd", pr[..., hi - lo:], vbar))
    return jnp.concatenate(outs, axis=1).reshape(b, s, H * d) @ p["wo"]


def _block(x, p, c):
    eps = float(c["rms_norm_eps"])
    x = x + _eva(_rms_norm(x, p["mixer_norm"], eps), p["eva"], c)
    h = _rms_norm(x, p["ffn_norm"], eps)
    f = p["ffn"]
    return x + (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]


def logits(params, tokens, c):
    """tokens [b, s] -> logits [b, s, num_pred_heads, vocab], float32: head j
    at position i scores the byte at i + 1 + j."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]

        def layer(x, p):           # one layer raised to float32 at a time
            return _block(x, _f32(p), c), None

        x, _ = jax.lax.scan(layer, x, params["runs"][0])
        x = _rms_norm(x, params["final_norm"].astype(F32), float(c["rms_norm_eps"]))
        out = x @ params["lm_head"].astype(F32)
        return out.reshape(out.shape[:-1] + (c["num_pred_heads"], c["vocab_size"]))


def lower_precision(params, how: str):
    """The control: the same weights after a round trip through the next
    precision below bf16 (`int8`: per-row absmax, as weight-only int8
    serving stores them), for every matrix a token is multiplied with: every
    leaf named `w*`, the embedding and the head; the norms, `phi` and `mu`
    stay."""
    if how != "int8":
        raise ValueError(f"no control precision {how!r}")

    def rt(path, w):
        name = path[-1].key
        if not (name.startswith("w") or name in ("embed", "lm_head")):
            return w
        wf = w.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1, keepdims=True), 1e-30) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    return jax.tree_util.tree_map_with_path(rt, params)


def rel_err(got, want) -> jax.Array:
    got, want = got.astype(F32), want.astype(F32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())
