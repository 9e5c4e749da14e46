"""Plain reference of the openPangu-Ultra-MoE block stack
(openPangu-Ultra-MoE-718B): rotary multi-head latent attention with a
low-rank query, sandwich norms, a leading dense SwiGLU layer, then a
sigmoid-routed top-k expert layer with one shared expert, an untied head,
and the model's multi-token-prediction module.

Straightforward `jax.numpy` in float32 at `highest` matmul precision,
written from the equations:

    c_q = RMSNorm(W_qa x);  q_h = W_qb,h c_q = [q_n (128), q_r (64)]
    [c, k_r] = W_kva x;  c <- RMSNorm(c)
    q_r, k_r <- RoPE(position)      (half-rotation layout, one k_r for all heads)
    [k_n, v]_h = W_kvb,h c;  k_h = [k_n,h, k_r]
    o_h = softmax_causal(q_h . k_h / sqrt(192)) v_h;  y = W_o [o_1 .. o_128]
    x += Norm_post_attn(y);  x += Norm_post_mlp(FFN(Norm_pre_mlp(x)))

    module: h' = W_p [RMSNorm(h_i) ; RMSNorm(Emb(t_(i+1)))], one such block
    (expert-layer kind), the module's final norm, the main head: t_(i+2)

MLA is its expanded form (every head's keys and values materialised), a
block of query rows at a time so that a long sequence fits; the expert
layer is a loop over the experts it is given. No cache, no absorbed
product, no kernels, no sorting, no batching. Weights arrive as the
benchmark's initialiser made them (per-layer dicts, in the type they are
served in) and are raised to float32 one matrix at a time. `c` is the
configuration file's dict. Nothing here imports the program.

The share: `c["experts_held"]` names the experts this chip holds out of
`of` (the router's width); what the absent experts would add is left out,
as in the program. The vocabulary is the slice `vocab_size`.

It MAY follow the program's choice of experts (`logits_routed` with
`routing`): the 8th and 9th of 256 sigmoid scores lie thousandths apart, so
bf16 rounding upstream turns the choice for a token in ten, a discrete
event and no error of arithmetic; it then reports how far the worst forced
expert scored under its own 8th best (`route_margin_max`).

Departures from the published description are marked `DEPARTURE`; what the
config leaves open is under `assumed` in the configuration file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def held_experts(c) -> list:
    h = c["experts_held"]
    return list(range(h["first"], h["first"] + h["count"]))


def layer_kinds(c) -> list:
    """"dense" or "moe" per layer of the cut stack."""
    return ["dense" if i < c["first_k_dense_replace"] else "moe"
            for i in range(c["num_hidden_layers"])]


def _rope(x, theta):
    """x [b, s, ..., d] at positions 0 .. s-1: the pair (x[j], x[j + d/2])
    turns by position * theta^(-2j/d)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]          # [s, d/2]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _mla(x, p, c, q_block=128):
    """x [b, s, d] (already normed) -> [b, s, d]."""
    H = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r, eps, theta = c["kv_lora_rank"], float(c["rms_norm_eps"]), float(c["rope_theta"])
    b, s, _ = x.shape
    c_q = _rms_norm(x @ p["w_qa"].astype(F32), p["q_norm"], eps)
    q = (c_q @ p["w_qb"].astype(F32)).reshape(b, s, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    ckr = x @ p["w_kva"].astype(F32)
    lat = _rms_norm(ckr[..., :r], p["kv_norm"], eps)
    k_r = _rope(ckr[..., r:], theta)                       # ONE for all heads
    kv = (lat @ p["w_kvb"].astype(F32)).reshape(b, s, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (b, s, H, dr))], -1)
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
    return o @ p["wo"].astype(F32)


def _swiglu(h, p):
    gate = jax.nn.silu(h @ p["w_gate"].astype(F32))
    return (gate * (h @ p["w_up"].astype(F32))) @ p["w_down"].astype(F32)


def moe_weights(h, router, bias, c, forced=None):
    """([T, E] float32: the weight of every expert for every token, 0 where
    the expert was not chosen; the worst routing violation). Sigmoid scores;
    the top k of score + bias (the bias chooses only); weights renormalised
    over the chosen (`norm_topk_prob`) and scaled by `routed_scaling_factor`.
    `forced` [T, k] int32 names the experts the PROGRAM chose (-1 in a row:
    free choice); the violation is how far the worst forced expert's score
    + bias lies under this router's own k-th best."""
    k = c["num_experts_per_tok"]
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
    if c["norm_topk_prob"]:
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
    """The sandwich block: four RMSNorms a layer."""
    eps = float(c["rms_norm_eps"])
    y = _mla(_rms_norm(x, p["mixer_norm"], eps), p["mla"], c)
    x = x + _rms_norm(y, p["mixer_post_norm"], eps)
    h = _rms_norm(x, p["ffn_norm"], eps)
    b, s, d = h.shape
    if kind == "dense":
        y, violation = _swiglu(h, p["ffn"]), jnp.zeros((), F32)
    else:
        y, violation = _moe_routed(h.reshape(b * s, d), p["moe"], c, forced=forced)
        y = y.reshape(b, s, d)
    return x + _rms_norm(y, p["ffn_post_norm"], eps), violation


def _forced(routing, layer):
    return None if routing is None else \
        routing[layer].reshape(-1, routing.shape[-1])


def logits_routed(params, tokens, c, routing=None):
    """tokens [b, s] -> (logits [b, s, vocab] float32; the prediction
    module's logits [b, s, vocab]: row i, from the stack's hidden row i and
    token i + 1, is for token i + 2, and row s - 1 (which has no token
    behind it) means nothing; the worst routing violation). `routing`
    [expert layers + 1, b, s, k] int32 forces the experts each position
    uses, the module's layer last (`moe_weights`); None: the reference's
    own choice."""
    eps = float(c["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        x = embed[tokens]
        worst, layer = jnp.zeros((), F32), 0
        for p, kind in zip(params["layers"], layer_kinds(c)):
            x, violation = _block(x, p, kind, c,
                                  _forced(routing, layer) if kind == "moe" else None)
            layer += kind == "moe"
            worst = jnp.maximum(worst, violation)
        head = params["lm_head"].astype(F32)
        main = _rms_norm(x, params["final_norm"], eps) @ head
        # the module: the hidden row BEFORE the final norm, the next token's
        # embedding, both normed, side by side through W_p
        m = params["mtp"]
        following = jnp.roll(tokens, -1, axis=1)
        both = jnp.concatenate([_rms_norm(x, m["h_norm"], eps),
                                _rms_norm(embed[following], m["e_norm"], eps)], -1)
        y, violation = _block(both @ m["proj"].astype(F32), m["layer"], "moe", c,
                              _forced(routing, layer))
        worst = jnp.maximum(worst, violation)
        return main, _rms_norm(y, m["final_norm"], eps) @ head, worst


def logits(params, tokens, c):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    return logits_routed(params, tokens, c)[0]


def mtp_logits(params, tokens, c):
    """tokens [b, s] -> the module's logits [b, s - 1, vocab], float32."""
    return logits_routed(params, tokens, c)[1][:, :-1]


def lower_precision(params, how: str):
    """The control: the same weights after a round trip through the next
    precision below bf16 (`int8`: per-row absmax, as weight-only int8
    serving stores them), for every matrix a token is multiplied with:
    every leaf of two or more dimensions."""
    if how != "int8":
        raise ValueError(f"no control precision {how!r}")

    def rt(w):
        if w.ndim < 2:
            return w
        wf = w.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1, keepdims=True), 1e-30) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    return jax.tree_util.tree_map(rt, params)


def rel_err(got, want) -> jax.Array:
    got, want = got.astype(F32), want.astype(F32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())
