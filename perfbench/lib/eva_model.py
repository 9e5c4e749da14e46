"""From the EvaByte configuration file to the program's `HybridConfig` in
its runs form (ONE run of EVA layers over dense FFNs), and every weight from
the seed in ONE jitted call (the program's pure `models.hybrid.init_params`,
which seeds non-zero `phi`, `mu` and norm weights around zero).

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.lib.hybrid_model import make_params  # noqa: F401  (the same call)


def model_config(c: dict, **overrides):
    """`c` is the configuration file (Hugging Face key names; `run` says how
    this deployment runs it)."""
    from ray_tpu.models.hybrid import HybridConfig

    if c["attention_class"] != "eva" or c["tie_word_embeddings"] \
            or c["attention_bias"] or not c["norm_add_unit_offset"] \
            or not (c["fp32_skip_add"] and c["fp32_logits"] and c["mixedp_attn"]) \
            or c["rope_scaling"] is not None or c["hidden_act"] != "silu":
        raise ValueError("the program's EVA stack is: EVA attention, no biases, "
                         "an untied head, norms that scale by 1 + w, a float32 "
                         "residual, float32 logits and softmax, plain rotary "
                         "positions, SwiGLU")
    L, d, H = c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"]
    kw = dict(
        vocab_size=c["vocab_size"], d_model=d, n_layers=L, kda_layers=(),
        first_dense=L, eva_layers=tuple(range(1, L + 1)),
        eva_window=c["window_size"], eva_chunk=c["chunk_size"],
        n_heads=H, n_kv_heads=c["num_key_value_heads"], head_dim=d // H,
        rope_theta=float(c["rope_theta"]), n_pred_heads=c["num_pred_heads"],
        norm_unit_offset=True, d_ff=c["intermediate_size"],
        norm_eps=float(c["rms_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]],
        prefill_tokens=c["run"].get("prefill_tokens", 2048))
    kw.update(overrides)
    return HybridConfig(**kw)
