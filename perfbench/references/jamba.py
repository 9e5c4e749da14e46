"""Plain reference of the Jamba block stack (AI21-Jamba2-3B): Mamba-1
selective-scan mixers and plain grouped-query attention without positions,
every layer's FFN a dense SwiGLU (`num_experts` 1), RMSNorm before each
half of a block and at the end, the head tied to the embedding.

Straightforward `jax.numpy` in float32 at `highest` matmul precision: the
selective scan is the token-by-token recurrence (one `lax.scan` over the
positions), the convolution a sum of four shifted copies, attention the
full score matrix. No cache, no chunks, no kernels. Weights arrive as the
benchmark's initialiser made them (`params["runs"]`: one dict per run of
like layers, every leaf stacked on a leading axis, in the type they are
served in) and are raised to float32 one layer at a time. `c` is the
configuration file's dict. Nothing here imports the program.

Layer i (from 0) is attention iff i % attn_layer_period == attn_layer_offset.

`assumed` (the configuration file lists them): the three inner RMSNorms on
dt, B and C (the public Jamba implementation's `dt_layernorm`,
`b_layernorm`, `c_layernorm`); A = -exp(A_log) with A_log held
[d_state, d_inner].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_kinds(c) -> list:
    """"mamba" or "attn" per layer, layers counted from 0 as the config does."""
    return ["attn" if i % c["attn_layer_period"] == c["attn_layer_offset"]
            else "mamba" for i in range(c["num_hidden_layers"])]


def runs(c) -> list:
    """[(kind, count)]: the layers grouped as the weights are stacked."""
    out = []
    for kind in layer_kinds(c):
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [tuple(r) for r in out]


def selective_recurrence(u, dt, A, B, C, D):
    """u, dt [b, s, di]; A [n, di]; B, C [b, s, n]; D [di] -> y [b, s, di]:
    h_t = exp(dt_t A) h_(t-1) + (dt_t u_t) B_t, y_t = h_t C_t + D u_t, one
    position at a time from h_0 = 0."""
    def step(h, t):
        u_t, dt_t, B_t, C_t = t
        h = jnp.exp(dt_t[:, None, :] * A) * h \
            + (dt_t * u_t)[:, None, :] * B_t[:, :, None]
        return h, jnp.sum(h * C_t[:, :, None], axis=1) + D * u_t

    b, _, di = u.shape
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (u, dt, B, C))
    _, y = jax.lax.scan(step, jnp.zeros((b, A.shape[0], di), F32), xs)
    return jnp.moveaxis(y, 0, 1)


def _mamba(x, p, c):
    """x [b, s, d] (already normed) -> [b, s, d]."""
    eps = float(c["rms_norm_eps"])
    K, n, r = c["mamba_d_conv"], c["mamba_d_state"], c["mamba_dt_rank"]
    s = x.shape[1]
    u, z = jnp.split(x @ p["w_in"], 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = sum(padded[:, j:j + s] * p["conv"][j] for j in range(K)) + p["conv_bias"]
    u = jax.nn.silu(u)
    dbc = u @ p["w_x"]
    dt = _rms_norm(dbc[..., :r], p["dt_norm"], eps)
    B = _rms_norm(dbc[..., r:r + n], p["b_norm"], eps)
    C = _rms_norm(dbc[..., r + n:], p["c_norm"], eps)
    dt = jax.nn.softplus(dt @ p["w_dt"] + p["dt_bias"])
    y = selective_recurrence(u, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    return (y * jax.nn.silu(z)) @ p["w_out"]


def _attention(x, p, c):
    """x [b, s, d] -> [b, s, d]: causal softmax at 1/sqrt(head width), query
    heads sharing the key/value heads in groups, no positional encoding."""
    b, s, d = x.shape
    H, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    q = (x @ p["wq"]).reshape(b, s, kvh, H // kvh, hd)
    k = (x @ p["wk"]).reshape(b, s, kvh, hd)
    v = (x @ p["wv"]).reshape(b, s, kvh, hd)
    sc = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * hd ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(sc, axis=-1), v)
    return o.reshape(b, s, H * hd) @ p["wo"]


def _block(x, p, kind, c):
    eps = float(c["rms_norm_eps"])
    h = _rms_norm(x, p["mixer_norm"], eps)
    x = x + (_mamba(h, p["mamba"], c) if kind == "mamba" else _attention(h, p["attn"], c))
    h = _rms_norm(x, p["ffn_norm"], eps)
    f = p["ffn"]
    return x + (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]


def logits(params, tokens, c):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        x = embed[tokens]
        for stacked, (kind, count) in zip(params["runs"], runs(c)):
            for i in range(count):   # one layer raised to float32 at a time
                layer = _f32(jax.tree_util.tree_map(lambda a: a[i], stacked))
                x = _block(x, layer, kind, c)
        x = _rms_norm(x, params["final_norm"].astype(F32), float(c["rms_norm_eps"]))
        return x @ embed.T


def lower_precision(params, how: str):
    """The control: the same weights after a round trip through the next
    precision below bf16 (`int8`: per-row absmax, as weight-only int8
    serving stores them), for every matrix a token is multiplied with: every
    leaf named `w*` and the embedding (which is the head too); the depthwise
    convolution, the norms, A_log, D and the biases stay."""
    if how != "int8":
        raise ValueError(f"no control precision {how!r}")

    def rt(path, w):
        name = path[-1].key
        if not (name.startswith("w") or name == "embed"):
            return w
        wf = w.astype(F32)
        scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-1, keepdims=True), 1e-30) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    return jax.tree_util.tree_map_with_path(rt, params)


def rel_err(got, want) -> jax.Array:
    got, want = got.astype(F32), want.astype(F32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())
