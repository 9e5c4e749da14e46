"""From the Granite-4.0-H configuration file to the program's `HybridConfig`
in its runs form (Mamba-2 and attention mixers as scanned runs, an expert
layer in every layer), and every weight from the seed in ONE jitted call
(the program's pure `models.hybrid.init_params`, which seeds `A_log`,
`dt_bias`, `D` and the convolution bias in Mamba's published ranges).

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.lib.hybrid_model import make_params  # noqa: F401  (the same call)


def model_config(c: dict, **overrides):
    """`c` is the configuration file (Hugging Face key names; `experts_held`
    says which experts of the router's `of` live here; `run` how this
    deployment runs it). The layers are the first `num_hidden_layers` of
    `layer_types`."""
    from ray_tpu.models.hybrid import HybridConfig

    held, d = c["experts_held"], c["hidden_size"]
    if held["count"] != c["num_local_experts"]:
        raise ValueError("num_local_experts is the number of experts held here")
    if c["mamba_n_groups"] != 1 or not c["tie_word_embeddings"] \
            or not c["mamba_conv_bias"] or c["mamba_proj_bias"] \
            or c["position_embedding_type"] != "nope" \
            or c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_expand"] * d \
            or c["shared_intermediate_size"] % c["intermediate_size"]:
        raise ValueError("the runs form's Mamba-2 is one group, a tied head, a "
                         "bias on the convolution and none on the projections, "
                         "attention without positions, a shared MLP of whole "
                         "expert widths")
    L = c["num_hidden_layers"]
    kinds = c["layer_types"][:L]
    kw = dict(
        vocab_size=c["vocab_size"], d_model=d, n_layers=L, kda_layers=(),
        first_dense=0,
        mamba2_layers=tuple(i + 1 for i, t in enumerate(kinds) if t == "mamba"),
        attn_layers=tuple(i + 1 for i, t in enumerate(kinds) if t == "attention"),
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=d // c["num_attention_heads"],
        ssd_heads=c["mamba_n_heads"], ssd_head_dim=c["mamba_d_head"],
        ssd_state=c["mamba_d_state"], ssd_chunk=c["mamba_chunk_size"],
        conv_kernel=c["mamba_d_conv"],
        d_expert=c["intermediate_size"], n_experts=held["of"],
        experts_held=tuple(range(held["first"], held["first"] + held["count"])),
        top_k=c["num_experts_per_tok"],
        n_shared=c["shared_intermediate_size"] // c["intermediate_size"],
        router="softmax",
        embed_scale=float(c["embedding_multiplier"]),
        residual_scale=float(c["residual_multiplier"]),
        attn_scale=float(c["attention_multiplier"]),
        logit_divisor=float(c["logits_scaling"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]],
        prefill_tokens=c["run"].get("prefill_tokens", 4096))
    kw.update(overrides)
    return HybridConfig(**kw)
