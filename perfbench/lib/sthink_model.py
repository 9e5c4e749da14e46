"""From the SmallThinker configuration file to the program's `HybridConfig`
in its window form (runs of global and of window layers, the global layer
first in every period, each layer over a scanned expert layer with every
expert held) with SmallThinker's block: sequential, RMSNorm, the route read
from the attention's input, ReGLU experts, half-rotation rotary, an untied
head; and every weight from the seed in ONE jitted call (the program's pure
`models.hybrid.init_params`, which seeds the norms' weights away from 1).

Imported only in the process that holds the chip."""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.lib.hybrid_model import make_params  # noqa: F401  (the same call)


def model_config(c: dict, **overrides):
    """`c` is the configuration file (the published key names; `experts_held`
    says which experts of the router's `of` live here; `run` how this
    deployment runs it). The layers are the first `num_hidden_layers` of
    `sliding_window_layout`."""
    from ray_tpu.models.hybrid import HybridConfig

    held, L = c["experts_held"], c["num_hidden_layers"]
    layout = c["sliding_window_layout"][:L]
    if c["tie_word_embeddings"] or c["rope_scaling"] is not None \
            or not c["moe_primary_router_apply_softmax"] or not c["norm_topk_prob"] \
            or c["rope_layout"][:L] != layout or len(layout) != L \
            or held["of"] != c["moe_num_primary_experts"]:
        raise ValueError("the program's SmallThinker stack is: an untied head, "
                         "plain rotary positions on exactly the window layers, "
                         "a softmax router whose chosen weights are "
                         "renormalised, experts_held.of the router's width")
    kw = dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=L,
        kda_layers=(), first_dense=0,
        swa_layers=tuple(i + 1 for i, t in enumerate(layout) if t),
        full_layers=tuple(i + 1 for i, t in enumerate(layout) if not t),
        swa_window=c["sliding_window_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], rope_theta=float(c["rope_theta"]),
        d_expert=c["moe_ffn_hidden_size"], n_experts=held["of"],
        experts_held=tuple(range(held["first"], held["first"] + held["count"])),
        top_k=c["moe_num_active_primary_experts"], n_shared=0, router="softmax",
        untied_head=True, swa_block="sequential", swa_norm="rms",
        swa_rotary="half", route_from="mixer", gate_act="relu",
        norm_eps=float(c["rms_norm_eps"]),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]])
    kw.update(overrides)
    return HybridConfig(**kw)
