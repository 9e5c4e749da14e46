"""Plain reference of SmallThinker-21BA3B-Instruct (`smallthinker_21b_instruct`):
sliding-window and global attention 3 : 1 in one stack, the global layer
FIRST in every period of four, a SEQUENTIAL block whose router reads the
attention's input, sparse-ReGLU experts, no shared expert.

    h       = RMSNorm(x; g1)            x / sqrt(mean(x^2) + eps) * g1
    idx, w  = the 6 largest of softmax(h W_r) over all 64, w renormalised
              over the chosen                      (made BEFORE the attention)
    q, k, v = h W_q, h W_k, h W_v       28 query heads on 4 key heads of 128
    sliding_window_layout[l] = 1: q, k rotated at their position by HALVES
        (lane i with lane i + 64, theta^(-2i/128), rope_layout[l] = 1);
        query n sees keys m with 0 <= n - m < sliding_window_size
    sliding_window_layout[l] = 0: no positions (rope_layout[l] = 0); query n
        sees every m <= n
    x1      = x + softmax(q k^T / sqrt(128)) v W_o
    u       = RMSNorm(x1; g2)
    x2      = x1 + sum_j w_j (relu(u W_gate[idx_j]) * (u W_up[idx_j])) W_down[idx_j]
    logits  = RMSNorm(x_L; g_f) W_head                       (an untied head)

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the equations: attention the explicit mask a block of query
rows at a time (a window layer's block against the keys its band can reach,
a global layer's against all; 9,728 positions at the published widths then
fit the chip beside the served model), the expert layer a loop over the
experts it is given with a mask, a block of tokens at a time. No cache, no
ring, no chunks of the prompt, no kernels, no sorting, no batching. Weights
arrive as the benchmark's initialiser made them (`params["runs"]`: one dict
per run of like layers, every leaf stacked on a leading axis, in the type
they are served in) and are raised to float32 a matrix (an expert) at a
time. `c` is the configuration file's dict. Nothing here imports the
program.

The share: `c["experts_held"]` names the experts this chip holds out of `of`
(the router's width; here all 64); what absent experts would add is left
out, as in the program.

`assumed` (the configuration file lists them with their reasons; each is
marked ASSUMED where it is made): the router reads the NORMED attention
input h; the ReLU sits on the gate branch; rotary lanes turn by halves; the
window counts the query's own row; no norm on q or k; no secondary experts;
dtypes (everything float32 here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_TOKENS = 2048      # tokens an expert layer takes at a time


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def held_experts(c) -> list:
    h = c["experts_held"]
    return list(range(h["first"], h["first"] + h["count"]))


def runs(c) -> list:
    """[(kind, count)]: the layers grouped as the weights are stacked; "swa"
    (`sliding_window_layout` 1) or "full" (0). A layer rotates iff it has a
    window (`rope_layout` = `sliding_window_layout`, as published)."""
    L = c["num_hidden_layers"]
    assert c["rope_layout"][:L] == c["sliding_window_layout"][:L]
    out = []
    for t in c["sliding_window_layout"][:L]:
        kind = "swa" if t else "full"
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [tuple(r) for r in out]


def rotate_halves(x, positions, theta):
    """x [s, heads, d] at positions [s]: lanes (i, i + d/2) turned by the
    angle position x theta^(-2i/d). ASSUMED: rotate-half, not interleaved."""
    d = x.shape[-1]
    angle = positions.astype(F32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, p, kind, c):
    """h [s, d] (normed) -> [s, d]. ASSUMED: no norm on q or k, no bias; the
    window counts the query's own row."""
    H, kvh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    assert c["rope_scaling"] is None
    s = h.shape[0]
    wq, wk, wv, wo = (p[n].astype(F32) for n in ("wq", "wk", "wv", "wo"))
    W, theta = c["sliding_window_size"], float(c["rope_theta"])
    banded = kind == "swa"
    k, v = (h @ wk).reshape(s, kvh, hd), (h @ wv).reshape(s, kvh, hd)
    if banded:
        k = rotate_halves(k, jnp.arange(s), theta)
    # a block of query rows at a time: a window layer's against the keys its
    # band can reach (W + block columns), a global layer's against all
    blk = min(256, s)
    n_blk = -(-s // blk)
    cols = min(s, W + blk) if banded else s
    hp = jnp.pad(h, ((0, n_blk * blk - s), (0, 0)))

    def rows(i):
        at = i * blk + jnp.arange(blk)
        q = (jax.lax.dynamic_slice_in_dim(hp, i * blk, blk) @ wq).reshape(blk, H, hd)
        if banded:
            q = rotate_halves(q, at, theta)
        lo = jnp.clip(i * blk + blk - cols, 0, s - cols)      # first column read
        kb = jax.lax.dynamic_slice_in_dim(k, lo, cols)
        vb = jax.lax.dynamic_slice_in_dim(v, lo, cols)
        m = lo + jnp.arange(cols)
        ok = m[None, :] <= at[:, None]
        if banded:
            ok &= at[:, None] - m[None, :] < W
        sc = jnp.einsum("qgrd,kgd->grqk", q.reshape(blk, kvh, H // kvh, hd), kb) \
            * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(ok[None, None], sc, -1e30), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", pr, vb).reshape(blk, H * hd) @ wo

    return jax.lax.map(rows, jnp.arange(n_blk)).reshape(n_blk * blk, -1)[:s]


def _reglu(u, gate, up, down):
    """ASSUMED: the ReLU is on the gate branch."""
    return (jax.nn.relu(u @ gate.astype(F32)) * (u @ up.astype(F32))) @ down.astype(F32)


def moe_weights(h, router, c, forced=None):
    """([T, E] float32: the weight of every expert for every token, 0 where
    the expert was not chosen; the worst routing violation). Scores are
    softmax(h W_r) over ALL experts (`moe_primary_router_apply_softmax`);
    the k largest are chosen and weigh p_e / sum of the chosen p
    (`norm_topk_prob`). h is the ATTENTION's normed input (ASSUMED: the
    normed rows, not the raw residual).

    `forced` [T, k] int32, if given, names the experts the PROGRAM chose for
    each token (-1 in a row: free choice). The k-th and (k+1)-th of 64
    logits lie hundredths apart, so bf16 rounding upstream turns the choice
    for some tokens, and a turned choice moves that token's hidden state: a
    discrete event, not an error of arithmetic. Under `forced` the reference
    follows the program's choice (weights still from its own scores), and
    reports how far the worst forced expert's LOGIT lies under its own k-th
    best: a near-tie is hundredths, a router computed wrongly is tenths."""
    assert c["moe_primary_router_apply_softmax"] and c["norm_topk_prob"]
    k = c["moe_num_active_primary_experts"]
    logits = h @ router
    best, idx = jax.lax.top_k(logits, k)
    violation = jnp.zeros((), F32)
    if forced is not None:
        use = forced[:, :1] >= 0
        want = jnp.maximum(forced, 0)
        under = best[:, -1] - jnp.min(jnp.take_along_axis(logits, want, -1), -1)
        violation = jnp.max(jnp.where(use[:, 0], jnp.maximum(under, 0.0), 0.0))
        idx = jnp.where(use, want, idx)
    probs = jax.nn.softmax(logits, axis=-1)
    w = jnp.take_along_axis(probs, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(w), violation


def moe(u, weights, p, held):
    """u [T, d] (the post-attention normed rows), `weights` [T, E] from
    `moe_weights` -> [T, d]: the part of the experts `held` (global ids;
    `p`'s stacked expert weights are theirs, in order). ASSUMED: no
    secondary experts, no shared expert."""
    def one_expert(y, e):            # a loop over the experts it is given
        gate, up, down, eid = e
        return y + jnp.take(weights, eid, axis=1)[:, None] * _reglu(u, gate, up, down), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                        (p["w_gate"], p["w_up"], p["w_down"],
                         jnp.asarray(held, jnp.int32)))
    return y


def block(x, p, kind, c, forced=None, held=None):
    """One layer: x [s, d] float32 -> (x2, the routing violation): the route
    from the attention's input, the experts over the updated residual."""
    s, d = x.shape
    eps = float(c["rms_norm_eps"])
    held = held_experts(c) if held is None else held
    h = _rms_norm(x, p["mixer_norm"].astype(F32), eps)
    weights, violation = moe_weights(h, p["moe"]["router"].astype(F32), c, forced)
    x1 = x + attention(h, p[kind], kind, c)
    u = _rms_norm(x1, p["ffn_norm"].astype(F32), eps)
    blk = min(_TOKENS, s)
    n_blk = -(-s // blk)
    pad = n_blk * blk - s
    cut = lambda a: jnp.pad(a, ((0, pad), (0, 0))).reshape(n_blk, blk, -1)
    f = jax.lax.map(lambda t: moe(t[0], t[1], p["moe"], held), (cut(u), cut(weights)))
    return x1 + f.reshape(n_blk * blk, d)[:s], violation


def features_routed(params, tokens, c, routing=None, held=None):
    """tokens [b, s] -> (the last layer's hidden rows after the final norm
    [b, s, d] float32, the worst routing violation). `routing`
    [layers, b, s, k] int32 forces the experts each position uses
    (`moe_weights`); None: the reference's own choice. `held`: the experts
    whose part is computed (None: `experts_held`). One sequence after
    another: nothing is batched."""
    with jax.default_matmul_precision("highest"):
        feats, worst = [], jnp.zeros((), F32)
        for b in range(tokens.shape[0]):
            x, layer = params["embed"][tokens[b]].astype(F32), 0
            for rp, (kind, k) in zip(params["runs"], runs(c)):
                for i in range(k):
                    p = jax.tree_util.tree_map(lambda a: a[i], rp)
                    x, violation = block(
                        x, p, kind, c, None if routing is None else routing[layer, b],
                        held)
                    worst = jnp.maximum(worst, violation)
                    layer += 1
            feats.append(_rms_norm(x, params["final_norm"].astype(F32),
                                   float(c["rms_norm_eps"])))
        return jnp.stack(feats), worst


def head(params, feats, c):
    """Rows of `features_routed` [..., d] -> logits [..., vocab] float32: the
    untied head. Apart from the stack so that a caller who needs a few rows
    of 9,728 does not make 9,728 x 151,936."""
    assert not c["tie_word_embeddings"]
    with jax.default_matmul_precision("highest"):
        return feats @ params["lm_head"].astype(F32)


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
    every leaf whose last two dimensions are a matrix, but the per-layer
    norm weights (stacked, they have two dimensions too)."""
    if how != "int8":
        raise ValueError(f"no control precision {how!r}")

    def rt(path, w):
        if w.ndim < 2 or path[-1].key in ("mixer_norm", "ffn_norm", "final_norm"):
            return w
        wf = w.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1, keepdims=True), 1e-30) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    return jax.tree_util.tree_map_with_path(rt, params)


def rel_err(got, want) -> jax.Array:
    got, want = got.astype(F32), want.astype(F32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())
