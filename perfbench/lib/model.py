"""From a configuration file to the program's `ModelConfig`, and the
benchmark's own initialiser: every weight from the seed, on the device, in
the type it is served or trained in, in ONE jitted call. (The program's
`init_params` runs eagerly, one small compile per leaf.)

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def model_config(c: dict, **overrides):
    """`c` is a configuration file (Hugging Face key names; its `run` group
    holds how this deployment runs it)."""
    from ray_tpu.models import ModelConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]]
    if c.get("head_dim", c["hidden_size"] // c["num_attention_heads"]) != \
            c["hidden_size"] // c["num_attention_heads"]:
        raise ValueError("the program's block takes head_dim = d / heads")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)), dtype=dtype)
    kw.update(overrides)
    return ModelConfig(**kw)


def _leaf_specs(cfg):
    """(path, shape, std) of every leaf, in the tree `init_params` makes;
    std None = a norm vector of ones."""
    d, hd, L = cfg.d_model, cfg.head_dim, cfg.n_layers
    nq, nkv, ff = cfg.n_heads * hd, cfg.n_kv_heads * hd, cfg.d_ff
    specs = [(("embed",), (cfg.vocab_size, d), 0.02),
             (("final_norm",), (d,), None)]
    for name, shape, fan_in in (
            ("wq", (L, d, nq), d), ("wk", (L, d, nkv), d), ("wv", (L, d, nkv), d),
            ("wo", (L, nq, d), nq), ("w_gate", (L, d, ff), d),
            ("w_up", (L, d, ff), d), ("w_down", (L, ff, d), ff)):
        specs.append((("layers", name), shape, fan_in ** -0.5))
    specs += [(("layers", "attn_norm"), (L, d), None),
              (("layers", "mlp_norm"), (L, d), None)]
    if not cfg.tie_embeddings:
        specs.append((("lm_head",), (d, cfg.vocab_size), 0.02))
    return specs


def make_params(cfg, seed: int, shardings=None):
    """The whole parameter tree from `seed`, one program, straight into
    `shardings` (a tree of shardings like the parameters) if given."""
    if cfg.n_experts:
        raise ValueError("this initialiser makes the dense block only")
    specs = _leaf_specs(cfg)

    def build(key):
        tree = {"layers": {}}
        for i, (path, shape, std) in enumerate(specs):
            if std is None:
                leaf = jnp.ones(shape, cfg.dtype)
            else:
                leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * std).astype(cfg.dtype)
            node = tree
            for p in path[:-1]:
                node = node[p]
            node[path[-1]] = leaf
        return tree

    # a seed may pass 2**31: fold its two halves into the key
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(build, out_shardings=shardings)(key)
