"""Plain reference of the GraniteMoeHybrid block stack (Granite-4.0-H-Small):
Mamba-2 (SSD) mixers 9 : 1 with grouped-query attention without positions,
and in EVERY layer a softmax-routed top-k expert layer beside a shared MLP;
four scalars a dense GQA model does not have (`embedding_multiplier`,
`attention_multiplier`, `residual_multiplier`, `logits_scaling`); the head
tied to the embedding.

    x_0 = emb * E[token]
    x  += res * Mixer(RMSNorm_1(x));  h = RMSNorm_2(x)
    x  += res * (Experts(h) + Shared(h))
    logits = RMSNorm(x_L) E^T / lsc

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the equations: the Mamba-2 mixer is the literal recurrence, one
position at a time (`lax.scan` over the positions; state S [H, P, N] a
sequence), the convolution a sum of four shifted copies, the expert layer a
loop over the experts it is given with a mask, attention the explicit causal
mask a block of query rows at a time (the same arithmetic; 12288 positions
at the published widths then fit the chip). No cache, no chunks, no kernels,
no sorting. Weights arrive as the benchmark's initialiser made them
(`params["runs"]`: one dict per run of like layers, every leaf stacked on a
leading axis, in the type they are served in) and are raised to float32 one
layer (one expert) at a time. `c` is the configuration file's dict. Nothing
here imports the program.

The share: `c["experts_held"]` names the experts this chip holds out of `of`
(the router's width); what the absent experts would add is left out, as in
the program. The vocabulary is the slice `vocab_size`.

Departures from the published description, marked `DEPARTURE` below: the
in-projection `[z | xBC | dt]` arrives as two matrices (`w_in` for z and
xBC, `w_dt` for dt's H columns) and is joined here; an expert's input
projection `[u_1 | u_2]` arrives as its two halves (`w_gate`, `w_up`).

`assumed` (the configuration file lists them with their reasons): softmax
over the ten chosen logits and the `[u_1 | u_2]` halves (the public
GraniteMoeHybrid implementation); the split order `[z | xBC | dt]` and the
convolution over x, B and C together (the public Mamba-2 / Bamba
implementation); the gated norm's one group, the gate applied BEFORE the
norm; seeded `A_log`, `dt_bias`, `D` and convolution bias in Mamba's
published initial ranges; the expert width read from `intermediate_size`;
dtypes (state, dt, decay and sums float32 here as everything is); the
eight-chip deployment.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def held_experts(c) -> list:
    h = c["experts_held"]
    return list(range(h["first"], h["first"] + h["count"]))


def runs(c) -> list:
    """[(kind, count)]: the layers grouped as the weights are stacked;
    "mamba2" or "attn" from `layer_types`."""
    out = []
    for t in c["layer_types"][:c["num_hidden_layers"]]:
        kind = {"mamba": "mamba2", "attention": "attn"}[t]
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [tuple(r) for r in out]


def ssd_recurrence(x, dt, A, B, C, D, S0=None):
    """x [b, s, H, P]; dt [b, s, H] (after its softplus); A, D [H]; B, C
    [b, s, N] -> (y [b, s, H, P], S after the last position [b, H, P, N]):
    S_t[h] = exp(dt_t[h] A[h]) S_(t-1)[h] + dt_t[h] x_t[h] (outer) B_t,
    y_t[h] = S_t[h] C_t + D[h] x_t[h], one position at a time from S_0."""
    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = jnp.exp(dt_t * A)[:, :, None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        return S, jnp.einsum("bhpn,bn->bhp", S, C_t) + D[:, None] * x_t

    b, _, H, P = x.shape
    if S0 is None:
        S0 = jnp.zeros((b, H, P, B.shape[-1]), F32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C))
    S, y = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(y, 0, 1), S


def _mamba2(x, p, c):
    """x [b, s, d] (already normed) -> [b, s, d]."""
    H, P, N, K = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                  c["mamba_d_conv"])
    assert c["mamba_n_groups"] == 1 and H * P == c["mamba_expand"] * c["hidden_size"]
    b, s, _ = x.shape
    di = H * P
    # DEPARTURE: W_in [d, 2 di + 2 N + H] arrives as [z | xBC] and dt's columns
    zxbcdt = x @ jnp.concatenate([p["w_in"], p["w_dt"]], axis=-1)
    z, xbc, dt = jnp.split(zxbcdt, (di, 2 * di + 2 * N), axis=-1)
    # depthwise causal convolution over x, B and C together, kernel K, a
    # bias, then SiLU: y_t = sum_j conv[j] * u_(t-K+1+j)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + s] * p["conv"][j] for j in range(K)) + p["conv_bias"]
    u, B, C = jnp.split(jax.nn.silu(xbc), (di, di + N), axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                            # [b, s, H]
    y, _ = ssd_recurrence(u.reshape(b, s, H, P), dt, -jnp.exp(p["A_log"]), B, C,
                          p["D"])
    # the gate first, then ONE norm over all H P channels (one group)
    g = _rms_norm(y.reshape(b, s, di) * jax.nn.silu(z), p["norm"],
                  float(c["rms_norm_eps"]))
    return g @ p["w_out"]


def _attention(x, p, c, q_block=256):
    """Grouped-query attention WITHOUT positions: causal softmax of
    att * q . k (`attention_multiplier` in 1 / sqrt(head width)'s place).
    Scores are computed for `q_block` query rows at a time."""
    H, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    assert c["position_embedding_type"] == "nope"
    b, s, d = x.shape
    hd = d // H
    q = (x @ p["wq"]).reshape(b, s, H, hd)
    k = jnp.repeat((x @ p["wk"]).reshape(b, s, kvh, hd), H // kvh, axis=2)
    v = jnp.repeat((x @ p["wv"]).reshape(b, s, kvh, hd), H // kvh, axis=2)
    blk = min(q_block, s)
    n_blk = -(-s // blk)
    q = jnp.pad(q, ((0, 0), (0, n_blk * blk - s), (0, 0), (0, 0)))

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * float(c["attention_multiplier"])
        ok = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        pr = jax.nn.softmax(jnp.where(ok[None, None], sc, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, v)

    o = jax.lax.map(rows, jnp.arange(n_blk))               # [n_blk, b, blk, H, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(b, n_blk * blk, H * hd)[:, :s]
    return o @ p["wo"]


def _swiglu(h, p):
    p = _f32(p)
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def moe_weights(h, router, c, forced=None):
    """([T, E] float32: the weight of every expert for every token, 0 where
    the expert was not chosen; the worst routing violation). The k largest
    LOGITS are chosen and the weights are a softmax over THOSE k logits.

    `forced` [T, k] int32, if given, names the experts the PROGRAM chose for
    each token (-1 in a row: free choice). The k-th and (k+1)-th of 72
    logits lie hundredths apart, so bf16 rounding upstream turns the choice
    for some tokens, and a turned choice moves that token's hidden state: a
    discrete event, not an error of arithmetic. Under `forced` the reference
    follows the program's choice (weights still from its own logits), and
    reports how far the worst forced expert's logit lies UNDER its own k-th
    best: a near-tie is hundredths, a router computed wrongly is tenths."""
    k = c["num_experts_per_tok"]
    logits = h @ router
    best, idx = jax.lax.top_k(logits, k)
    violation = jnp.zeros((), F32)
    if forced is not None:
        use = forced[:, :1] >= 0
        want = jnp.maximum(forced, 0)
        under = best[:, -1] - jnp.min(jnp.take_along_axis(logits, want, -1), -1)
        violation = jnp.max(jnp.where(use[:, 0], jnp.maximum(under, 0.0), 0.0))
        idx = jnp.where(use, want, idx)
    w = jax.nn.softmax(jnp.take_along_axis(logits, idx, axis=-1), axis=-1)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(w), violation


def moe(h, p, c, held=None, shared=True, forced=None):
    """h [T, d] -> ([T, d]: the shared MLP plus the part of the experts
    `held` (global ids; `p`'s stacked expert weights are theirs, in order),
    the worst routing violation under `forced`)."""
    held = held_experts(c) if held is None else held
    W, violation = moe_weights(h, p["router"].astype(F32), c, forced)
    y = _swiglu(h, p["shared"]) if shared else jnp.zeros_like(h)

    def one_expert(y, e):            # a loop over the experts it is given
        gate, up, down, eid = e
        # DEPARTURE: W_in_e = [u_1 | u_2] arrives as its halves
        out = _swiglu(h, {"w_gate": gate, "w_up": up, "w_down": down})
        return y + jnp.take(W, eid, axis=1)[:, None] * out, None

    y, _ = jax.lax.scan(one_expert, y, (p["w_gate"], p["w_up"], p["w_down"],
                                        jnp.asarray(held, jnp.int32)))
    return y, violation


def block(x, p, kind, c, forced=None):
    """One layer: x [b, s, d] float32 -> (x, the routing violation)."""
    eps, res = float(c["rms_norm_eps"]), float(c["residual_multiplier"])
    h = _rms_norm(x, p["mixer_norm"].astype(F32), eps)
    mixer = _mamba2 if kind == "mamba2" else _attention
    x = x + res * mixer(h, _f32(p[kind]), c)
    h = _rms_norm(x, p["ffn_norm"].astype(F32), eps)
    b, s, d = h.shape
    y, violation = moe(h.reshape(b * s, d), p["moe"], c, forced=forced)
    return x + res * y.reshape(b, s, d), violation


def features_routed(params, tokens, c, routing=None):
    """tokens [b, s] -> (the last layer's hidden rows after the final norm
    [b, s, d] float32, the worst routing violation). `routing`
    [layers, b, s, k] int32 forces the experts each position uses
    (`moe_weights`); None: the reference's own choice."""
    with jax.default_matmul_precision("highest"):
        x = float(c["embedding_multiplier"]) * params["embed"].astype(F32)[tokens]
        worst, layer = jnp.zeros((), F32), 0
        for rp, (kind, k) in zip(params["runs"], runs(c)):
            for i in range(k):
                p = jax.tree_util.tree_map(lambda a: a[i], rp)
                forced = None if routing is None else \
                    routing[layer].reshape(-1, routing.shape[-1])
                x, violation = block(x, p, kind, c, forced)
                worst = jnp.maximum(worst, violation)
                layer += 1
        return _rms_norm(x, params["final_norm"].astype(F32),
                         float(c["rms_norm_eps"])), worst


def head(params, feats, c):
    """Rows of `features_routed` [..., d] -> logits [..., vocab] float32:
    the tied head, DIVIDED by `logits_scaling`. Apart from the stack so that
    a caller who needs a few rows of 13056 does not make 13056 x 50176."""
    with jax.default_matmul_precision("highest"):
        return feats @ params["embed"].astype(F32).T / float(c["logits_scaling"])


def logits_routed(params, tokens, c, routing=None):
    """tokens [b, s] -> (logits [b, s, vocab] float32, the worst routing
    violation)."""
    feats, worst = features_routed(params, tokens, c, routing)
    return head(params, feats, c), worst


def logits(params, tokens, c):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    return logits_routed(params, tokens, c)[0]


def lower_precision(params, how: str):
    """The control: the same weights after a round trip through the next
    precision below bf16 (`int8`: per-row absmax, as weight-only int8
    serving stores them), for every matrix a token is multiplied with:
    every leaf whose last two dimensions are a matrix, but the depthwise
    convolution and the per-layer vectors (stacked, they have two
    dimensions too)."""
    if how != "int8":
        raise ValueError(f"no control precision {how!r}")
    vectors = ("conv", "conv_bias", "dt_bias", "A_log", "D", "norm", "mixer_norm",
               "ffn_norm", "final_norm")

    def rt(path, w):
        if w.ndim < 2 or path[-1].key in vectors:
            return w
        wf = w.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1, keepdims=True), 1e-30) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    return jax.tree_util.tree_map_with_path(rt, params)


def rel_err(got, want) -> jax.Array:
    got, want = got.astype(F32), want.astype(F32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())
