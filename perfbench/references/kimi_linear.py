"""Plain reference of the Kimi-Linear block stack (Kimi-Linear-48B-A3B):
KDA (gated delta rule with a per-channel decay) and MLA without positions
as mixers, a leading dense SwiGLU layer, then a sigmoid-routed top-k expert
layer with one shared expert, an untied head.

Straightforward `jax.numpy` in float32 at `highest` matmul precision: KDA
is the token-by-token recurrence, MLA is its expanded form (keys and values
of every head materialised from the latent), the expert layer is a loop
over the experts it is given. No cache, no chunked algorithm, no sorting.
Weights arrive as the benchmark's initialiser made them (a list of
per-layer dicts, in the type they are served in) and are raised to float32
one layer (one expert) at a time. `c` is the configuration file's dict.
Nothing here imports the program.

The share: `c["experts_held"]` names the experts this chip holds out of
`of` (the router's width); what the absent experts would add is left out,
as in the program. The vocabulary is the slice `vocab_size`.

Departures from the published description are marked `DEPARTURE`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _l2_norm(x):
    # fla's l2norm: x / sqrt(sum x^2 + 1e-6)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def held_experts(c) -> list:
    h = c["experts_held"]
    return list(range(h["first"], h["first"] + h["count"]))


def layer_kinds(c) -> list:
    """[(mixer, ffn)] per layer, layers counted from 1 as the config does."""
    la = c["linear_attn_config"]
    out = []
    for i in range(1, c["num_hidden_layers"] + 1):
        mixer = "kda" if i in la["kda_layers"] else "mla"
        assert (i in la["full_attn_layers"]) == (mixer == "mla"), i
        out.append((mixer, "dense" if i <= c["first_k_dense_replace"] else "moe"))
    return out


def kda_recurrence(q, k, v, g, beta):
    """q, k, g [b, s, H, dk], v [b, s, H, dv], beta [b, s, H] -> o
    [b, s, H, dv]: S_t = (I - beta k k^T) Diag(exp g) S_(t-1) + beta k v^T,
    o_t = S_t^T q_t, one token at a time from S_0 = 0."""
    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[..., None]                    # Diag(alpha) S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    b, _, H, dk = q.shape
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, H, dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(o, 0, 1)


def _kda(x, p, c):
    """x [b, s, d] (already normed) -> [b, s, d]."""
    la = c["linear_attn_config"]
    H, dk, K = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    b, s, _ = x.shape
    eps = float(c["rms_norm_eps"])
    qkv = x @ p["w_qkv"]                                   # [b, s, 3 H dk]
    # depthwise causal convolution over time, kernel K, then SiLU:
    # y_t = sum_j conv[j] * x_(t-K+1+j)
    padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s] * p["conv"][j] for j in range(K))
    q, k, v = jnp.split(jax.nn.silu(y), 3, axis=-1)
    q = _l2_norm(q.reshape(b, s, H, dk)) * dk ** -0.5
    k = _l2_norm(k.reshape(b, s, H, dk))
    v = v.reshape(b, s, H, dk)
    # DEPARTURE: no projection carries a bias except dt_bias (fla's second
    # gate linear has one); the low ranks are head_dim (`assumed`)
    f = ((x @ p["w_f1"]) @ p["w_f2"] + p["dt_bias"]).reshape(b, s, H, dk)
    g = -jnp.exp(p["A_log"])[None, None, :, None] * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(x @ p["w_b"])                    # [b, s, H]
    o = _rms_norm(kda_recurrence(q, k, v, g, beta), p["o_norm"], eps)
    gate = jax.nn.sigmoid((x @ p["w_g1"]) @ p["w_g2"]).reshape(b, s, H, dk)
    return (o * gate).reshape(b, s, H * dk) @ p["wo"]


def _mla(x, p, c, q_block=256):
    """MLA without positions, expanded: every head's keys and values are
    made from the latent; causal softmax of q.k / sqrt(192). Scores are
    computed for `q_block` query rows at a time so that a long sequence
    fits (the same arithmetic, row block by row block)."""
    H = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r = c["kv_lora_rank"]
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, H, dn + dr)
    ckr = x @ p["w_kva"]
    lat = _rms_norm(ckr[..., :r], p["kv_norm"], float(c["rms_norm_eps"]))
    kv = (lat @ p["w_kvb"]).reshape(b, s, H, dn + dv)
    # the rope part of the key is shared by all heads; mla_use_nope: no
    # rotation is applied to any part
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(ckr[:, :, None, r:], (b, s, H, dr))], -1)
    v = kv[..., dn:]
    blk = min(q_block, s)
    assert s % blk == 0, (s, blk)

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * (dn + dr) ** -0.5
        ok = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(s)[None, :]
        pr = jax.nn.softmax(jnp.where(ok[None, None], sc, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, v)

    o = jax.lax.map(rows, jnp.arange(s // blk))            # [nblk, b, blk, H, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, H * dv)
    return o @ p["wo"]


def _swiglu(h, p):
    p = _f32(p)
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def moe_weights(h, router, bias, c, forced=None):
    """([T, E] float32: the weight of every expert for every token, 0 where
    the expert was not chosen; the worst routing violation). sigmoid
    scores; the top k of score + bias (the bias chooses only); weights
    renormalised over the chosen and scaled by the routing factor.

    `forced` [T, k] int32, if given, names the experts the PROGRAM chose
    for each token (-1 in a row: free choice). The k-th and (k+1)-th of 256
    scores lie a few thousandths apart, so bf16 rounding upstream turns the
    choice for one token in ten, and a turned choice moves that token's
    hidden state by a tenth: a discrete event, not an error of arithmetic.
    Under `forced` the reference follows the program's choice (weights still
    from its own scores), and reports how far the worst forced expert's
    score + bias lies UNDER its own k-th best: a near-tie is a few
    thousandths, a router computed wrongly (no bias, lower precision) is
    tenths."""
    k = c["num_experts_per_token"]
    s = jax.nn.sigmoid(h @ router)
    best, idx = jax.lax.top_k(s + bias, k)
    violation = jnp.zeros((), F32)
    if forced is not None:
        use = forced[:, :1] >= 0
        want = jnp.maximum(forced, 0)
        under = best[:, -1] - jnp.min(jnp.take_along_axis(s + bias, want, -1), -1)
        violation = jnp.max(jnp.where(use[:, 0], jnp.maximum(under, 0.0), 0.0))
        idx = jnp.where(use, want, idx)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if c["moe_renormalize"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * float(c["routed_scaling_factor"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(w), violation


def _moe_routed(h, p, c, held=None, shared=True, forced=None):
    """h [T, d] -> ([T, d]: the shared expert plus the held experts' part,
    the worst routing violation under `forced`)."""
    held = held_experts(c) if held is None else held
    W, violation = moe_weights(h, p["router"].astype(F32), p["bias"].astype(F32),
                               c, forced)
    y = _swiglu(h, p["shared"]) if shared else jnp.zeros_like(h)

    def one_expert(y, e):            # a loop over the experts it is given
        gate, up, down, eid = e
        out = _swiglu(h, {"w_gate": gate, "w_up": up, "w_down": down})
        return y + jnp.take(W, eid, axis=1)[:, None] * out, None

    y, _ = jax.lax.scan(one_expert, y, (p["w_gate"], p["w_up"], p["w_down"],
                                        jnp.asarray(held, jnp.int32)))
    return y, violation


def _moe(h, p, c, held=None, shared=True):
    return _moe_routed(h, p, c, held, shared)[0]


def _block(x, p, kind, c, forced=None):
    mixer, ffn = kind
    eps = float(c["rms_norm_eps"])
    h = _rms_norm(x, p["mixer_norm"].astype(F32), eps)
    x = x + (_kda if mixer == "kda" else _mla)(h, _f32(p[mixer]), c)
    h = _rms_norm(x, p["ffn_norm"].astype(F32), eps)
    b, s, d = h.shape
    if ffn == "dense":
        return x + _swiglu(h, p["ffn"]), jnp.zeros((), F32)
    y, violation = _moe_routed(h.reshape(b * s, d), p["moe"], c, forced=forced)
    return x + y.reshape(b, s, d), violation


def logits_routed(params, tokens, c, routing=None):
    """tokens [b, s] -> (logits [b, s, vocab] float32, the worst routing
    violation). `routing` [expert layers, b, s, k] int32 forces the experts
    each position uses (`moe_weights`); None: the reference's own choice."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        worst, layer = jnp.zeros((), F32), 0
        for p, kind in zip(params["layers"], layer_kinds(c)):
            forced = None
            if kind[1] == "moe" and routing is not None:
                forced = routing[layer].reshape(-1, routing.shape[-1])
                layer += 1
            x, violation = _block(x, p, kind, c, forced)
            worst = jnp.maximum(worst, violation)
        x = _rms_norm(x, params["final_norm"].astype(F32), float(c["rms_norm_eps"]))
        return x @ params["lm_head"].astype(F32), worst


def logits(params, tokens, c):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    return logits_routed(params, tokens, c)[0]


def lower_precision(params, how: str):
    """The control: the same weights after a round trip through the next
    precision below bf16 (`int8`: per-row absmax, as weight-only int8
    serving stores them), for every matrix a token is multiplied with:
    every leaf of two or more dimensions but the depthwise convolution."""
    if how != "int8":
        raise ValueError(f"no control precision {how!r}")

    def rt(path, w):
        if w.ndim < 2 or path[-1].key == "conv":
            return w
        wf = w.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1, keepdims=True), 1e-30) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    return jax.tree_util.tree_map_with_path(rt, params)


def rel_err(got, want) -> jax.Array:
    got, want = got.astype(F32), want.astype(F32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())
