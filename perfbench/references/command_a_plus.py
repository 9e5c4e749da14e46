"""Plain reference of Command A+'s language model (`cohere2_moe`,
`command-a-plus-05-2026`): sliding-window and full attention 3 : 1 in one
stack, ONE LayerNorm a layer that attention and expert layer both read, and
the two added to the stream in parallel.

    h       = LayerNorm(x_l)            (x - mean) / sqrt(var + eps) * w, no bias
    q, k, v = h W_q, h W_k, h W_v       128 query heads on 8 key heads of 128
    window layers: q, k rotated at their position over INTERLEAVED pairs
        (lane 2i with lane 2i + 1, theta^(-2i/128)); query n sees keys m
        with 0 <= n - m < sliding_window
    full layers: no positions; query n sees every m <= n
    A       = softmax(q k^T / sqrt(128)) v W_o
    s       = sigmoid(h W_r); the 8 largest of 128, weights s_e / sum chosen
    F       = sum_chosen w_e E_e(h) + (1 / 4) sum_j S_j(h)
    x_(l+1) = x_l + A + F               (the parallel block)
    logits  = LayerNorm(x_L) E^T * logit_scale

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the equations: attention the explicit mask a block of query
rows at a time (a window layer's block against the keys its band can reach,
a full layer's against all; 48k positions then fit the chip), the expert
layer a loop over the experts it is given with a mask, the four shared
experts computed APART and averaged, both a block of tokens at a time. No
cache, no ring, no chunks of the prompt, no kernels, no sorting, no
batching. Weights arrive as the benchmark's initialiser made them
(`params["runs"]`: one dict per run of like layers, every leaf stacked on a
leading axis, in the type they are served in) and are raised to float32 a
matrix (an expert) at a time. `c` is the configuration file's dict. Nothing
here imports the program.

The share: `c["experts_held"]` names the experts this chip holds out of `of`
(the router's width); what the absent experts would add is left out, as in
the program. The vocabulary is the slice `vocab_size`.

Departures from the published description, marked `DEPARTURE` below: the
four shared experts arrive as ONE matrix a projection, expert j's columns
(rows, for the down projection) at [j f, (j + 1) f), and are cut apart here.

`assumed` (the configuration file lists them with their reasons): the MEAN
of the shared experts is added to the routed sum; no selection bias and a
routed scale of 1; the window counts the query's own row; an expert's width
is `intermediate_size`; no leading dense layer; dtypes (everything float32
here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_TOKENS = 2048      # tokens an expert layer takes at a time


def _layer_norm(x, w, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def held_experts(c) -> list:
    h = c["experts_held"]
    return list(range(h["first"], h["first"] + h["count"]))


def runs(c) -> list:
    """[(kind, count)]: the layers grouped as the weights are stacked; "swa"
    or "full" from `layer_types`."""
    out = []
    for t in c["layer_types"][:c["num_hidden_layers"]]:
        kind = {"sliding_attention": "swa", "full_attention": "full"}[t]
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [tuple(r) for r in out]


def rotate_pairs(x, positions, theta):
    """x [s, heads, d] at positions [s]: lanes (2i, 2i + 1) turned by the
    angle position x theta^(-2i/d)."""
    s, heads, d = x.shape
    angle = positions.astype(F32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    pairs = x.reshape(s, heads, d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(s, heads, d)


def attention(h, p, kind, c, window=None):
    """h [s, d] (normed) -> [s, d]. `window`: the positions a window layer's
    query sees, its own among them (None: the configuration's; the
    `no_window` control's comparison passes nothing else either: the
    reference keeps the published band)."""
    H, kvh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    assert c["position_embedding_type"] == "rope_gptj" and c["rotary_pct"] == 1 \
        and not c["attention_bias"] and not c["use_qk_norm"]
    s = h.shape[0]
    wq, wk, wv, wo = (p[n].astype(F32) for n in ("wq", "wk", "wv", "wo"))
    W = window or c["sliding_window"]
    banded = kind == "swa"
    positions = jnp.arange(s)
    k, v = (h @ wk).reshape(s, kvh, hd), (h @ wv).reshape(s, kvh, hd)
    if banded:
        k = rotate_pairs(k, positions, float(c["rope_theta"]))
    # a block of query rows at a time: a window layer's against the keys its
    # band can reach (W + block columns), a full layer's against all
    blk = min(256 if banded else 32, s)
    n_blk = -(-s // blk)
    cols = min(s, W + blk) if banded else s
    hp = jnp.pad(h, ((0, n_blk * blk - s), (0, 0)))

    def rows(i):
        at = i * blk + jnp.arange(blk)
        q = (jax.lax.dynamic_slice_in_dim(hp, i * blk, blk) @ wq).reshape(blk, H, hd)
        if banded:
            q = rotate_pairs(q, at, float(c["rope_theta"]))
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


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) @ down.astype(F32)


def moe_weights(h, router, c, forced=None):
    """([T, E] float32: the weight of every expert for every token, 0 where
    the expert was not chosen; the worst routing violation). Scores are
    sigmoid(h W_r); the k largest are chosen and weigh s_e / sum of the
    chosen s (`norm_topk_prob`); no bias, no scale.

    `forced` [T, k] int32, if given, names the experts the PROGRAM chose for
    each token (-1 in a row: free choice). The k-th and (k+1)-th of 128
    scores lie thousandths apart, so bf16 rounding upstream turns the choice
    for some tokens, and a turned choice moves that token's hidden state: a
    discrete event, not an error of arithmetic. Under `forced` the reference
    follows the program's choice (weights still from its own scores), and
    reports how far the worst forced expert's score lies UNDER its own k-th
    best: a near-tie is thousandths, a router computed wrongly is tenths."""
    assert c["expert_selection_fn"] == "sigmoid" and c["norm_topk_prob"]
    k = c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ router)
    best, idx = jax.lax.top_k(scores, k)
    violation = jnp.zeros((), F32)
    if forced is not None:
        use = forced[:, :1] >= 0
        want = jnp.maximum(forced, 0)
        under = best[:, -1] - jnp.min(jnp.take_along_axis(scores, want, -1), -1)
        violation = jnp.max(jnp.where(use[:, 0], jnp.maximum(under, 0.0), 0.0))
        idx = jnp.where(use, want, idx)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(w), violation


def moe(h, p, c, held=None, shared=True, forced=None):
    """h [T, d] -> ([T, d]: the mean of the shared experts plus the part of
    the experts `held` (global ids; `p`'s stacked expert weights are theirs,
    in order), the worst routing violation under `forced`)."""
    assert c["shared_expert_combination_strategy"] == "average" \
        and c["hidden_act"] == "silu" and c["use_gated_activation"]
    held = held_experts(c) if held is None else held
    W, violation = moe_weights(h, p["router"].astype(F32), c, forced)
    y = jnp.zeros_like(h)
    if shared:
        # DEPARTURE: the four shared experts arrive as one matrix a
        # projection and are cut apart; their MEAN is added
        n, f, s = c["num_shared_experts"], c["intermediate_size"], p["shared"]
        for j in range(n):
            cut = slice(j * f, (j + 1) * f)
            y = y + _swiglu(h, s["w_gate"][:, cut], s["w_up"][:, cut],
                            s["w_down"][cut]) / n

    def one_expert(y, e):            # a loop over the experts it is given
        gate, up, down, eid = e
        return y + jnp.take(W, eid, axis=1)[:, None] * _swiglu(h, gate, up, down), None

    y, _ = jax.lax.scan(one_expert, y, (p["w_gate"], p["w_up"], p["w_down"],
                                        jnp.asarray(held, jnp.int32)))
    return y, violation


def block(x, p, kind, c, forced=None):
    """One layer: x [s, d] float32 -> (x + A + F, the routing violation):
    attention and expert layer read the SAME normed rows."""
    assert c["use_parallel_block"] and not c["first_k_dense_replace"]
    s, d = x.shape
    h = _layer_norm(x, p["mixer_norm"].astype(F32), float(c["layer_norm_eps"]))
    a = attention(h, p[kind], kind, c)
    blk = min(_TOKENS, s)
    n_blk = -(-s // blk)
    pad = n_blk * blk - s
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(n_blk, blk, d)
    fp = None if forced is None else jnp.pad(
        forced, ((0, pad), (0, 0)), constant_values=-1).reshape(n_blk, blk, -1)
    f, violation = jax.lax.map(
        lambda t: moe(t[0], p["moe"], c, forced=None if fp is None else t[1]),
        (hp, fp if fp is not None else hp[..., :1]))
    return x + a + f.reshape(n_blk * blk, d)[:s], jnp.max(violation)


def features_routed(params, tokens, c, routing=None):
    """tokens [b, s] -> (the last layer's hidden rows after the final norm
    [b, s, d] float32, the worst routing violation). `routing`
    [layers, b, s, k] int32 forces the experts each position uses
    (`moe_weights`); None: the reference's own choice. One sequence after
    another: nothing is batched."""
    with jax.default_matmul_precision("highest"):
        feats, worst = [], jnp.zeros((), F32)
        for b in range(tokens.shape[0]):
            x, layer = params["embed"].astype(F32)[tokens[b]], 0
            for rp, (kind, k) in zip(params["runs"], runs(c)):
                for i in range(k):
                    p = jax.tree_util.tree_map(lambda a: a[i], rp)
                    x, violation = block(x, p, kind, c,
                                         None if routing is None else routing[layer, b])
                    worst = jnp.maximum(worst, violation)
                    layer += 1
            feats.append(_layer_norm(x, params["final_norm"].astype(F32),
                                     float(c["layer_norm_eps"])))
        return jnp.stack(feats), worst


def head(params, feats, c):
    """Rows of `features_routed` [..., d] -> logits [..., vocab] float32: the
    tied head, times `logit_scale`. Apart from the stack so that a caller
    who needs a few rows of 48k does not make 48k x 32,768."""
    assert c["tie_word_embeddings"]
    with jax.default_matmul_precision("highest"):
        return feats @ params["embed"].astype(F32).T * float(c["logit_scale"])


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
        if w.ndim < 2 or path[-1].key in ("mixer_norm", "final_norm"):
            return w
        wf = w.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1, keepdims=True), 1e-30) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    return jax.tree_util.tree_map_with_path(rt, params)


def rel_err(got, want) -> jax.Array:
    got, want = got.astype(F32), want.astype(F32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())
