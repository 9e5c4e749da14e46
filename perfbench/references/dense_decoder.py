"""Plain reference of the dense decoder block (Mistral-7B, InternLM2 and
every model of that shape): RMSNorm, grouped-query attention with rotary
positions (half-split, as in the published Hugging Face code), SwiGLU, an
untied head; the loss is mean next-token cross-entropy.

Straightforward `jax.numpy` in float32 at `highest` matmul precision: no
kernels, no cache, no batching tricks. Weights arrive as the benchmark's
initialiser made them from the seed (stacked over layers, in the type they
are served in) and are raised to float32 one layer at a time, so the
reference fits beside the program on one chip. `c` is the configuration
file's dict. Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotary(x, theta):
    """x [b, s, heads, hd], positions 0..s-1."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs  # [s, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(x, p, c):
    b, s, d = x.shape
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // nq
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    p = {k: v.astype(F32) for k, v in p.items()}
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rotary((h @ p["wq"]).reshape(b, s, nq, hd), theta)
    k = _rotary((h @ p["wk"]).reshape(b, s, nkv, hd), theta)
    v = (h @ p["wv"]).reshape(b, s, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -1e30), axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nq * hd)
    x = x + attn @ p["wo"]
    h = _rms_norm(x, p["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def logits(params, tokens, c):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]

        @jax.checkpoint  # a gradient keeps one layer's float32 weights, not all
        def body(x, p):
            return _block(x, p, c), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(F32), float(c["rms_norm_eps"]))
        head = (params["embed"].astype(F32).T if c.get("tie_word_embeddings")
                else params["lm_head"].astype(F32))
        return x @ head


def loss(params, inputs, targets, c):
    lg = logits(params, inputs, c)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def _get(tree, path):
    for p in path.split("."):
        tree = tree[p]
    return tree


def _put(tree, path, leaf):
    keys = path.split(".")
    if len(keys) == 1:
        return {**tree, keys[0]: leaf}
    return {**tree, keys[0]: _put(tree[keys[0]], ".".join(keys[1:]), leaf)}


def loss_and_grads(params, inputs, targets, c, wrt):
    """The loss, and its float32 gradient with respect to the leaves named
    in `wrt` (dotted paths such as `layers.w_down`)."""
    picked = {path: _get(params, path).astype(F32) for path in wrt}

    def f(picked):
        p = params
        for path, leaf in picked.items():
            p = _put(p, path, leaf)
        return loss(p, inputs, targets, c)

    return jax.value_and_grad(f)(picked)


def lower_precision(params, how: str):
    """The control: the same weights after a round trip through the next
    precision below the one the configuration states, for every matrix a
    token is multiplied with. `float8_e4m3fn`: per-row scaled, rounded to 4 exponent and 3 mantissa bits.
    `int8`: per-row absmax, as weight-only int8 serving stores them."""
    def rt(w):
        if how == "float8_e4m3fn":
            # per-row scaled to the format's range, then rounded to 4 exponent
            # and 3 mantissa bits by the rounding operation itself (a cast
            # there and back is folded away on a chip with no fp8 type)
            wf = w.astype(F32)
            scale = jnp.max(jnp.abs(wf), axis=-1, keepdims=True) / 224.0
            return (jax.lax.reduce_precision(wf / scale, 4, 3) * scale).astype(w.dtype)
        if how == "int8":
            wf = w.astype(F32)
            scale = jnp.max(jnp.abs(wf), axis=-1, keepdims=True) / 127.0
            return (jnp.round(wf / scale) * scale).astype(w.dtype)
        if how == "bfloat16":
            return w.astype(jnp.bfloat16).astype(w.dtype)
        raise ValueError(f"no control precision {how!r}")

    out = dict(params)
    out["layers"] = {k: (rt(v) if k in MATMUL_LEAVES else v)
                     for k, v in params["layers"].items()}
    for k in ("embed", "lm_head"):
        if k in params:
            out[k] = rt(params[k])
    return out


def rel_err(got, want) -> jax.Array:
    got, want = got.astype(F32), want.astype(F32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())
