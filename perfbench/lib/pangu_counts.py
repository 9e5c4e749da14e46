"""Parameters, bytes and operations of the openPangu-Ultra-MoE cut, from the
configuration file's numbers (`tests/perfbench/test_perfbench_pangu.py`
checks them by hand at the published widths). The prediction module counts
as one more expert layer with a projection in front. Pure arithmetic: the
benchmark's parent reads metrics through it and never imports jax."""

from __future__ import annotations

LANES = 128


def latent_lanes(c: dict) -> int:
    """The lanes the PROGRAM stores a cached latent row in
    (`HybridConfig.latent_width`: `[c, k_r]` rounded up to whole tiles of
    128; a test holds the two together): what a step has to read of a row."""
    width = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return -(-width // LANES) * LANES


def mla_mixer_params(c: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o (the norms' vectors are left out)."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r, qr = c["kv_lora_rank"], c["q_lora_rank"]
    return d * qr + qr * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def n_layers_of(c: dict):
    """(dense layers, expert layers of the main stack, prediction modules)."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense, c["num_nextn_predict_layers"]


def expert_layer_fixed_params(c: dict) -> int:
    """What an expert layer reads whatever the routing: mixer, router, the
    shared expert."""
    return (mla_mixer_params(c) + c["hidden_size"] * c["experts_held"]["of"]
            + c["n_shared_experts"] * expert_params(c))


def total_params(c: dict) -> int:
    dense, moe, mtp = n_layers_of(c)
    d = c["hidden_size"]
    return (dense * (mla_mixer_params(c) + 3 * d * c["intermediate_size"])
            + (moe + mtp) * (expert_layer_fixed_params(c)
                             + c["experts_held"]["count"] * expert_params(c))
            + mtp * 2 * d * d + 2 * c["vocab_size"] * d)


def decode_fixed_weight_bytes(c: dict, bytes_per_weight: int = 2) -> int:
    """What every decode step reads whatever the routing: every mixer, the
    dense layer, routers, shared experts, the module's projection, the head
    (once: both positions and the module's share one read; the embedding
    rows of a few tokens are left out)."""
    dense, moe, mtp = n_layers_of(c)
    d = c["hidden_size"]
    return bytes_per_weight * (
        dense * (mla_mixer_params(c) + 3 * d * c["intermediate_size"])
        + (moe + mtp) * expert_layer_fixed_params(c)
        + mtp * 2 * d * d + c["vocab_size"] * d)


def latent_layers(c: dict) -> int:
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def latent_row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """The latent rows of ONE position over the MLA layers, as stored."""
    return latent_layers(c) * latent_lanes(c) * bytes_per_value


def latent_bytes_per_step(c: dict, latent_rows: float) -> float:
    """The live latent rows of the busy slots, read once by every layer."""
    return latent_rows * latent_row_bytes(c)


def decode_step_bytes(c: dict, latent_rows: float, experts_touched: float) -> float:
    """Fixed weights once, the touched experts' weights once, the live rows."""
    return (decode_fixed_weight_bytes(c) + experts_touched * expert_params(c) * 2
            + latent_bytes_per_step(c, latent_rows))


def mla_decode_flops(c: dict, latent_rows: float, n_query: int) -> float:
    """The absorbed decode's two products over the live rows of every MLA
    layer: scores over the row's `kv_lora_rank + qk_rope_head_dim` values
    (the zero lanes a stored row is padded with are no work), values over
    the first `kv_lora_rank`, for `n_query` positions x heads."""
    per_row = 2 * n_query * c["num_attention_heads"] * (
        c["kv_lora_rank"] + c["qk_rope_head_dim"] + c["kv_lora_rank"])
    return latent_layers(c) * latent_rows * per_row


def held_expert_slots(c: dict) -> int:
    """Held experts summed over the expert layers, the module's with them."""
    _, moe, mtp = n_layers_of(c)
    return (moe + mtp) * c["experts_held"]["count"]
