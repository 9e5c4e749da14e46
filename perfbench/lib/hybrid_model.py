"""From the Kimi-Linear configuration file to the program's `HybridConfig`,
and every weight from the seed in ONE jitted call (the program's pure
`models.hybrid.init_params`, which also seeds small non-zero values for
the router's correction bias, `A_log` and `dt_bias`).

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def model_config(c: dict, **overrides):
    """`c` is the configuration file (Hugging Face key names; `experts_held`
    says which experts of the router's `of` live here; `run` how this
    deployment runs it)."""
    from ray_tpu.models.hybrid import HybridConfig

    la, held = c["linear_attn_config"], c["experts_held"]
    if sorted(la["kda_layers"] + la["full_attn_layers"]) != \
            list(range(1, c["num_hidden_layers"] + 1)):
        raise ValueError("kda_layers and full_attn_layers must split the layers")
    if held["count"] != c["num_experts"]:
        raise ValueError("num_experts is the number of experts held here")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], kda_layers=tuple(la["kda_layers"]),
        first_dense=c["first_k_dense_replace"],
        kda_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        conv_kernel=la["short_conv_kernel_size"], kda_rank=la["head_dim"],
        n_heads=c["num_attention_heads"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], d_ff=c["intermediate_size"],
        d_expert=c["moe_intermediate_size"], n_experts=held["of"],
        experts_held=tuple(range(held["first"], held["first"] + held["count"])),
        top_k=c["num_experts_per_token"], n_shared=c["num_shared_experts"],
        route_scale=float(c["routed_scaling_factor"]),
        renormalize=bool(c["moe_renormalize"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]],
        prefill_tokens=c["run"].get("prefill_tokens", 4096))
    kw.update(overrides)
    return HybridConfig(**kw)


def make_params(cfg, seed: int):
    from ray_tpu.models.hybrid import init_params

    # a seed may pass 2**31: fold its two halves into the key
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(init_params, static_argnames=("cfg",))(key, cfg=cfg)
