"""From the openPangu-Ultra-MoE configuration file to the program's
`HybridConfig` (the layer-list form: every mixer rotary MLA with a low-rank
query, sandwich norms, one leading dense layer, then expert layers, one
multi-token-prediction module), and every weight from the seed in ONE
jitted call (`lib.hybrid_model.make_params`: the program's pure
`models.hybrid.init_params`).

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.lib.hybrid_model import make_params  # noqa: F401


def model_config(c: dict, **overrides):
    """`c` is the configuration file (Hugging Face key names; `experts_held`
    says which experts of the router's `of` live here; `run` how this
    deployment runs it)."""
    from ray_tpu.models.hybrid import HybridConfig

    held = c["experts_held"]
    if held["count"] != c["n_routed_experts"]:
        raise ValueError("n_routed_experts is the number of experts held here")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], kda_layers=(),
        first_dense=c["first_k_dense_replace"],
        n_heads=c["num_attention_heads"], kv_lora_rank=c["kv_lora_rank"],
        q_lora_rank=c["q_lora_rank"], qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        sandwich_norm=bool(c["sandwich_norm"]),
        n_predict=c["num_nextn_predict_layers"],
        d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        n_experts=held["of"],
        experts_held=tuple(range(held["first"], held["first"] + held["count"])),
        top_k=c["num_experts_per_tok"], n_shared=c["n_shared_experts"],
        route_scale=float(c["routed_scaling_factor"]),
        renormalize=bool(c["norm_topk_prob"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]],
        prefill_tokens=c["run"].get("prefill_tokens", 8192))
    kw.update(overrides)
    return HybridConfig(**kw)
