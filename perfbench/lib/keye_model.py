"""From the Keye-VL-2.0 configuration file to the program's `HybridConfig`
in its runs form (ONE run of sparse-attention layers, each over a scanned
expert layer without a shared MLP, an untied head), and every weight from
the seed in ONE jitted call (the program's pure `models.hybrid.init_params`,
which seeds the q / k norms and the indexer's LayerNorm away from 1 and 0).

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.lib.hybrid_model import make_params  # noqa: F401  (the same call)


def model_config(c: dict, **overrides):
    """`c` is the configuration file (Hugging Face key names; `run` says how
    this deployment runs it). `overrides` lay fields over the result (the
    `every_row` control: `dsa_topk` past every context)."""
    from ray_tpu.models.hybrid import HybridConfig

    sa = c["sa_config"]
    if c["tie_word_embeddings"] or c["attention_bias"] or c["mlp_only_layers"] \
            or c["decoder_sparse_step"] != 1 or not c["norm_topk_prob"] \
            or c["use_sliding_window"] or c["hidden_act"] != "silu" \
            or sa["indexer_num_kv_heads"] != 1 \
            or c["num_local_experts"] != c["num_experts"] \
            or c["rope_scaling"]["rope_type"] != "default":
        raise ValueError("the program's sparse-attention stack is: an untied "
                         "head, no biases, an expert layer in every layer with "
                         "renormalised weights, every expert held, no window, "
                         "SwiGLU, one indexer key head, plain rotary positions")
    L = c["num_hidden_layers"]
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=L,
        kda_layers=(), first_dense=0, dsa_layers=tuple(range(1, L + 1)),
        dsa_topk=sa["topk"], dsa_heads=sa["indexer_num_heads"],
        dsa_head_dim=sa["indexer_head_dim"], dsa_chunk=sa["q_chunk_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], rope_theta=float(c["rope_theta"]),
        d_expert=c["moe_intermediate_size"], n_experts=c["num_experts"],
        experts_held=tuple(range(c["num_experts"])),
        top_k=c["num_experts_per_tok"], n_shared=0, router="softmax",
        untied_head=True, norm_eps=float(c["rms_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]],
        prefill_tokens=c["run"].get("prefill_tokens", 8192))
    kw.update(overrides)
    return HybridConfig(**kw)
