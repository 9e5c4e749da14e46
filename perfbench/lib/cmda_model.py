"""From the Command A+ configuration file to the program's `HybridConfig`
in its runs form (runs of window layers and of full layers, each layer over
a scanned expert layer beside the mean of the shared experts, ONE LayerNorm
a layer, the head the embedding), and every weight from the seed in ONE
jitted call (the program's pure `models.hybrid.init_params`, which seeds the
LayerNorms' weights away from 1).

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.lib.hybrid_model import make_params  # noqa: F401  (the same call)

KINDS = {"sliding_attention": "swa_layers", "full_attention": "full_layers"}


def model_config(c: dict, **overrides):
    """`c` is the configuration file (Hugging Face key names; `experts_held`
    says which experts of the router's `of` live here; `run` how this
    deployment runs it). `overrides` lay fields over the result (the
    `no_window` control: every layer full)."""
    from ray_tpu.models.hybrid import HybridConfig

    held = c["experts_held"]
    if not c["tie_word_embeddings"] or c["attention_bias"] or c["use_qk_norm"] \
            or not c["use_parallel_block"] or c["first_k_dense_replace"] \
            or c["expert_selection_fn"] != "sigmoid" or not c["norm_topk_prob"] \
            or c["shared_expert_combination_strategy"] != "average" \
            or c["hidden_act"] != "silu" or not c["use_gated_activation"] \
            or c["position_embedding_type"] != "rope_gptj" or c["rotary_pct"] != 1 \
            or c["rope_parameters"]["rope_type"] != "default" or c["logit_scale"] != 1 \
            or held["count"] != c["num_experts"]:
        raise ValueError("the program's window-and-full stack is: a tied head, "
                         "no biases, no q / k norm, the parallel block, an "
                         "expert layer in every layer routed by renormalised "
                         "sigmoid scores beside the MEAN of the shared experts, "
                         "SwiGLU, interleaved rotary over the whole head, "
                         "logit_scale 1, num_experts the experts held here")
    L = c["num_hidden_layers"]
    layers = {name: tuple(i + 1 for i, t in enumerate(c["layer_types"][:L])
                          if KINDS[t] == name) for name in KINDS.values()}
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=L,
        kda_layers=(), first_dense=0, **layers, swa_window=c["sliding_window"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], rope_theta=float(c["rope_theta"]),
        d_expert=c["intermediate_size"], n_experts=held["of"],
        experts_held=tuple(range(held["first"], held["first"] + held["count"])),
        top_k=c["num_experts_per_tok"], n_shared=c["num_shared_experts"],
        router="sigmoid", route_scale=1.0, renormalize=True,
        norm_eps=float(c["layer_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    kw.update(overrides)
    return HybridConfig(**kw)
