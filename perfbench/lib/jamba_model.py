"""From the Jamba configuration file to the program's `HybridConfig` in its
runs form (Mamba and attention mixers as scanned runs of like layers), and
every weight from the seed in ONE jitted call (the program's pure
`models.hybrid.init_params`, which seeds non-zero `dt_bias`, `D` and
convolution bias and sets `A_log` to log(1..d_state)).

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.lib.hybrid_model import make_params  # noqa: F401  (the same call)


def model_config(c: dict, **overrides):
    """`c` is the configuration file (Hugging Face key names; `run` says how
    this deployment runs it). Layer i, counted from 0, is attention iff
    i % attn_layer_period == attn_layer_offset."""
    from ray_tpu.models.hybrid import HybridConfig

    if c["num_experts"] != 1 or not c["tie_word_embeddings"] \
            or not c["mamba_conv_bias"] or c["mamba_proj_bias"]:
        raise ValueError("the runs form is dense FFNs, a tied head, a bias on "
                         "the convolution and none on the projections")
    L, d = c["num_hidden_layers"], c["hidden_size"]
    attn = tuple(i + 1 for i in range(L)
                 if i % c["attn_layer_period"] == c["attn_layer_offset"])
    kw = dict(
        vocab_size=c["vocab_size"], d_model=d, n_layers=L, kda_layers=(),
        first_dense=L, attn_layers=attn,
        mamba_layers=tuple(i for i in range(1, L + 1) if i not in attn),
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=d // c["num_attention_heads"],
        d_inner=c["mamba_expand"] * d, d_state=c["mamba_d_state"],
        dt_rank=c["mamba_dt_rank"], conv_kernel=c["mamba_d_conv"],
        d_ff=c["intermediate_size"], norm_eps=float(c["rms_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]],
        prefill_tokens=c["run"].get("prefill_tokens", 4096))
    kw.update(overrides)
    return HybridConfig(**kw)
