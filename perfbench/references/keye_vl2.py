"""Plain reference of Keye-VL-2.0-30B-A3B's language model: a Qwen3-MoE
decoder whose every query attends to the `topk` rows a learned indexer
scores highest (DeepSeek-Sparse-Attention's lightning indexer over
grouped-query attention). For every layer, h = RMSNorm(x):

    1. q = W_q h (H heads of hd), k, v = W_k h, W_v h (kvh heads of hd);
       q and k each through an RMSNorm a head, then rotary over all hd lanes
       (half-rotation layout, `rope_theta`, one position a token).
    2. qI = W_qI h (J heads of di), kI = LayerNorm(W_kI h) (ONE key of di),
       wI = W_wI h (J weights); rotary on qI and kI over all di lanes.
       I[t, s] = sum_j wI[t, j] ReLU(qI[t, j] . kI[s]) for s <= t.
    3. S_t = the `topk` rows s <= t with the largest I[t, s] (every row
       while t + 1 <= topk), one choice a position for all heads.
    4. o_t = softmax over s in S_t of q_t . k_s / sqrt(hd), times v_s, a
       head (kvh : H grouped); x += W_o o.
    5. x += sum over the `num_experts_per_tok` experts e with the largest
       router logits of p_e W_down_e (SiLU(W_gate_e h') * W_up_e h'),
       h' = RMSNorm(x), p = softmax over ALL logits, renormalised over the
       chosen (`norm_topk_prob`); no shared expert.
    6. logits = RMSNorm(x_L) W_head (an untied head).

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the equations: the indexer's scores and the attention's are
computed for a block of query rows against EVERY row, the choice is
`jax.lax.top_k` over the full causal score row, the expert layer a loop
over the experts with a mask. No cache, no chunks of the program's, no
kernels, no threshold search. Weights arrive as the benchmark's initialiser
made them (`params["runs"][0]`: every leaf stacked on a leading axis of
layers, in the type they are served in) and are raised to float32 a layer
(an expert) at a time. `c` is the configuration file's dict. Nothing here
imports the program.

`sa_config.q_chunk_size` / `kv_chunk_size` say how the published
implementation tiles the indexer's scores; they do not change a result and
are ignored here.

`assumed` (the configuration file lists each with its reason): the RMSNorm a
head on q and k (Qwen3-MoE's `q_norm` / `k_norm`), the indexer's inputs (the
layer's normed hidden state), its key's LayerNorm, rotary over all of its
lanes at the layer's theta, the half-rotation layout, text positions only
(the three sections of `mrope_section` then rotate by the one position: plain
rotary), seeded norm weights, dtypes (everything float32 here).

DEPARTURE from the published layout, the same numbers: an expert's weights
arrive as `w_gate`, `w_up` [E, d, f] and `w_down` [E, f, d].
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def _rotary(x, theta, positions):
    """x [s, heads, w] at `positions` [s]: the half-rotation layout (lane i
    pairs with lane i + w / 2), frequencies theta^(-2 i / w)."""
    w = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, w, 2, dtype=F32) / w)
    ang = positions.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., : w // 2], x[..., w // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def unpack_rows(packed, t):
    """`ops.dsa.pack_rows`' words [ceil(s / 32), s] int32 and query numbers t
    [q] -> [q, s] bool: whether query t[i] chose row s."""
    words = packed[t // 32]
    return (jnp.right_shift(words, (t % 32)[:, None]) & 1) == 1


def sparse_attention(x, p, c, chosen=None, followed=0, q_block=64):
    """Equations 1-4 for one sequence: x [s, d] float32 (the normed hidden
    state) -> (W_o o [s, d], the worst selection violation). `chosen`
    [ceil(s / 32), s] int32, if given, holds the rows the PROGRAM chose for
    every query (`unpack_rows`): the k-th and (k+1)-th best of thousands of
    scores lie a rounding apart, so the program's bf16 indexer turns the
    choice for some rows, a discrete event and no error of arithmetic.
    Under `chosen` the reference attends to the program's rows at the first
    `followed` queries (its own behind them) and reports how far the worst
    of them scores UNDER its own k-th best of that query, in standard
    deviations of the query's causal score row."""
    sa = c["sa_config"]
    H, kvh, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    J, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    s = x.shape[0]
    every = jnp.arange(s)
    k = _rotary(_rms_norm((x @ p["wk"]).reshape(s, kvh, hd), p["k_norm"], eps), theta,
                every)
    v = (x @ p["wv"]).reshape(s, kvh, hd)
    ki = _rotary(_layer_norm(x @ p["w_ki"], p["ki_norm"], p["ki_bias"], eps)[:, None],
                 theta, every)[:, 0]
    blk = min(q_block, s)
    n_blk = -(-s // blk)
    x = jnp.pad(x, ((0, n_blk * blk - s), (0, 0)))
    cols = every

    def rows(i):
        # a block of queries: their projections, scores against EVERY row
        xb = jax.lax.dynamic_slice_in_dim(x, i * blk, blk, axis=0)
        t = i * blk + jnp.arange(blk)
        q = _rotary(_rms_norm((xb @ p["wq"]).reshape(blk, H, hd), p["q_norm"], eps),
                    theta, t).reshape(blk, kvh, H // kvh, hd)   # head h on kv head h // (H / kvh)
        qi = _rotary((xb @ p["w_qi"]).reshape(blk, J, di), theta, t)
        wi = xb @ p["w_wi"]
        causal = cols[None, :] <= t[:, None]
        score = jnp.einsum("tj,tjs->ts", wi, jax.nn.relu(
            jnp.einsum("tjd,sd->tjs", qi, ki)))
        score = jnp.where(causal, score, -jnp.inf)
        best, idx = jax.lax.top_k(score, min(topk, s))
        mine = jnp.zeros((blk, s), bool).at[jnp.arange(blk)[:, None], idx].set(
            best > -jnp.inf)
        violation = jnp.zeros((), F32)
        if chosen is not None:
            theirs = unpack_rows(chosen, jnp.minimum(t, s - 1)) & causal
            n = jnp.maximum(jnp.sum(causal, axis=1), 1)
            mean = jnp.sum(jnp.where(causal, score, 0.0), axis=1) / n
            sd = jnp.sqrt(jnp.sum(jnp.where(causal, (score - mean[:, None]) ** 2, 0.0),
                                  axis=1) / n)
            under = best[:, -1] - jnp.min(jnp.where(theirs, score, jnp.inf), axis=1)
            # a query of fewer than topk rows has no k-th best: every row is chosen
            counts = (t < followed) & (best[:, -1] > -jnp.inf)
            violation = jnp.max(jnp.where(counts, jnp.maximum(under, 0.0)
                                          / jnp.maximum(sd, 1e-30), 0.0))
            mine = jnp.where((t < followed)[:, None], theirs, mine)
        sc = jnp.einsum("tgrd,sgd->grts", q, k) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(mine[None, None], sc, -1e30), axis=-1)
        o = jnp.einsum("grts,sgd->tgrd", pr, v)
        return o.reshape(blk, H * hd) @ p["wo"], violation

    out, violation = jax.lax.map(rows, jnp.arange(n_blk))
    return out.reshape(n_blk * blk, -1)[:s], jnp.max(violation)


def moe_weights(h, router, c, forced=None):
    """([T, E] float32: the weight of every expert for every token, 0 where
    the expert was not chosen; the worst routing violation). p = softmax over
    ALL logits, the k largest, renormalised over the chosen. `forced` [T, k]
    int32 names the experts the PROGRAM chose for each token (-1 in a row:
    free choice): the reference then follows that choice (weights from its
    own logits) and reports how far the worst forced expert's logit lies
    UNDER its own k-th best."""
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
    p = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1)
    if c["norm_topk_prob"]:
        p = p / jnp.sum(p, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(p), violation


def moe(h, p, layer, c, forced=None):
    """h [T, d] -> (the experts' weighted sum [T, d], the routing
    violation): a loop over ALL the experts of layer `layer`, each over all
    the tokens. `p` holds every layer's experts ([layers, E, ...]): an
    expert's three matrices are raised to float32 one expert at a time."""
    W, violation = moe_weights(h, p["router"][layer].astype(F32), c, forced)

    E = p["w_gate"].shape[1]
    flat = {n: p[n].reshape((-1,) + p[n].shape[2:])     # [layers x E, ...]: read in place
            for n in ("w_gate", "w_up", "w_down")}

    def one_expert(y, e):
        gate, up, down = (flat[n][layer * E + e].astype(F32)
                          for n in ("w_gate", "w_up", "w_down"))
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return y + jnp.take(W, e, axis=1)[:, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(E))
    return y, violation


def _layer(rp, x, layer, forced, chosen, followed, *, c):
    """One layer: x [b, s, d] float32 -> (x, the routing violation, the
    selection violation). `rp` holds every layer's weights stacked; `layer`
    says which are this one's."""
    eps = float(c["rms_norm_eps"])
    b, s, d = x.shape
    with jax.default_matmul_precision("highest"):
        p = {k: rp[k][layer] for k in ("mixer_norm", "ffn_norm")}
        p["dsa"] = {k: a[layer] for k, a in rp["dsa"].items()}
        h = _rms_norm(x, p["mixer_norm"].astype(F32), eps)
        outs = [sparse_attention(h[i], _f32(p["dsa"]), c,
                                 None if chosen is None else chosen[i], followed)
                for i in range(b)]
        x = x + jnp.stack([o for o, _ in outs])
        select_worst = jnp.zeros((), F32)
        for _, w in outs:
            select_worst = jnp.maximum(select_worst, w)
        h = _rms_norm(x, p["ffn_norm"].astype(F32), eps)
        y, route_worst = moe(h.reshape(b * s, d), rp["moe"], layer, c,
                             None if forced is None else
                             forced.reshape(-1, forced.shape[-1]))
        return x + y.reshape(b, s, d), route_worst, select_worst


_LAYER_PROGRAMS = {}


def _layer_program(c):
    """`_layer` jitted, ONE program for every layer of a configuration (the
    layer is an argument): a 32k sequence's six layers unrolled in one
    program hold 7 GB of temporaries beside 8.75 GB of weights, one layer
    1.5 GB. It runs on its own: do not call it under an outer `jit`."""
    key = json.dumps(c, sort_keys=True, default=str)
    if key not in _LAYER_PROGRAMS:
        _LAYER_PROGRAMS[key] = jax.jit(functools.partial(_layer, c=c))
    return _LAYER_PROGRAMS[key]


def features_routed(params, tokens, c, routing=None, chosen=None, followed=0):
    """tokens [b, s] -> (the last layer's hidden rows after the final norm
    [b, s, d] float32, the worst routing violation, the worst selection
    violation). `routing` [layers, b, s, k] int32 forces the experts each
    position uses (`moe_weights`), `chosen` [layers, b, ceil(s / 32), s]
    int32 the rows each of the first `followed` queries attends to
    (`sparse_attention`); None: the reference's own choice. A layer at a
    time (`_layer_program`)."""
    x = params["embed"][tokens].astype(F32)
    route_worst, select_worst = jnp.zeros((), F32), jnp.zeros((), F32)
    step = _layer_program(c)
    for layer in range(c["num_hidden_layers"]):
        x, r, w = step(params["runs"][0], x, jnp.asarray(layer, jnp.int32),
                       None if routing is None else routing[layer],
                       None if chosen is None else chosen[layer],
                       jnp.asarray(followed, jnp.int32))
        route_worst, select_worst = jnp.maximum(route_worst, r), jnp.maximum(select_worst, w)
    return (_rms_norm(x, params["final_norm"].astype(F32), float(c["rms_norm_eps"])),
            route_worst, select_worst)


def head(params, feats, c):
    """Rows of `features_routed` [..., d] -> logits [..., vocab] float32: the
    untied head. Apart from the stack so that a caller who needs a few rows
    of 32k does not make 32k x 151,936."""
    w = params["lm_head"]
    parts = 8 if w.shape[1] % 8 == 0 else 1     # an eighth of the columns at a time
    width = w.shape[1] // parts
    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(lambda j: feats @ jax.lax.dynamic_slice_in_dim(
            w, j * width, width, axis=1).astype(F32), jnp.arange(parts))
    return jnp.moveaxis(out, 0, -2).reshape(feats.shape[:-1] + (w.shape[1],))


def logits(params, tokens, c):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    return head(params, features_routed(params, tokens, c)[0], c)


def lower_precision(params, how: str):
    """The controls. `int8`: the same weights after a round trip through
    the next precision below bf16 (per-row absmax, as weight-only int8
    serving stores them), for every matrix a token is multiplied with:
    every leaf whose last two dimensions are a matrix, but the per-layer
    vectors (stacked, they have two dimensions too). `every_row` leaves the
    weights alone: the replica runs the program with every causal row
    chosen."""
    if how == "every_row":
        return params
    if how != "int8":
        raise ValueError(f"no control precision {how!r}")
    vectors = ("mixer_norm", "ffn_norm", "final_norm", "q_norm", "k_norm",
               "ki_norm", "ki_bias")

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
