"""Operations and bytes the Kimi-Linear stack needs, from shapes alone.
A configuration is the dict of its file (Hugging Face key names); counts
are of what THIS chip holds (`experts_held`, the vocabulary slice)."""

from __future__ import annotations

from typing import List, Optional


def _kda(c):
    la = c["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def n_layers_of(c: dict):
    """(KDA layers, MLA layers, dense-FFN layers, expert layers)."""
    la = c["linear_attn_config"]
    dense = c["first_k_dense_replace"]
    return (len(la["kda_layers"]), len(la["full_attn_layers"]), dense,
            c["num_hidden_layers"] - dense)


def kda_mixer_params(c: dict) -> int:
    d = c["hidden_size"]
    H, dk, K = _kda(c)
    r = dk  # the low rank of decay and gate (`assumed`)
    return (d * 3 * H * dk + K * 3 * H * dk      # q, k, v and their convolution
            + 2 * (d * r + r * H * dk)           # decay and output gate
            + d * H + H * dk * d)                # beta, output projection


def mla_mixer_params(c: dict) -> int:
    d, H = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def swiglu_params(c: dict, width: int) -> int:
    return 3 * c["hidden_size"] * width


def expert_params(c: dict) -> int:
    return swiglu_params(c, c["moe_intermediate_size"])


def param_count(c: dict) -> int:
    """Every matrix held here (norm vectors, A_log, dt_bias and the router's
    bias, 0.01% of the whole, are left out)."""
    kda, mla, dense, moe = n_layers_of(c)
    d = c["hidden_size"]
    return (kda * kda_mixer_params(c) + mla * mla_mixer_params(c)
            + dense * swiglu_params(c, c["intermediate_size"])
            + moe * (c["experts_held"]["count"] * expert_params(c)
                     + c["num_shared_experts"] * expert_params(c)
                     + d * c["experts_held"]["of"])
            + 2 * c["vocab_size"] * d)


def decode_fixed_weight_bytes(c: dict, bytes_per_weight: int = 2) -> int:
    """What every decode step reads whatever the routing: mixers, dense
    layer, shared experts, routers, the head (the embedding rows of a few
    tokens are left out)."""
    kda, mla, dense, moe = n_layers_of(c)
    d = c["hidden_size"]
    return bytes_per_weight * (
        kda * kda_mixer_params(c) + mla * mla_mixer_params(c)
        + dense * swiglu_params(c, c["intermediate_size"])
        + moe * (c["num_shared_experts"] * expert_params(c)
                 + d * c["experts_held"]["of"])
        + c["vocab_size"] * d)


def kda_state_bytes_per_slot(c: dict) -> int:
    """S of every KDA layer, float32."""
    H, dk, _ = _kda(c)
    return n_layers_of(c)[0] * H * dk * dk * 4


def conv_tail_bytes_per_slot(c: dict, bytes_per_value: int = 2) -> int:
    H, dk, K = _kda(c)
    return n_layers_of(c)[0] * (K - 1) * 3 * H * dk * bytes_per_value


def latent_row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """The latent rows of ONE position over the MLA layers."""
    return n_layers_of(c)[1] * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) \
        * bytes_per_value


def state_bytes_per_step(c: dict, state_slots: float, latent_rows: float) -> float:
    """KDA state and convolution tail read AND written for every slot whose
    state advances, plus the live latent rows read."""
    return (2.0 * state_slots * (kda_state_bytes_per_slot(c)
                                 + conv_tail_bytes_per_slot(c))
            + latent_rows * latent_row_bytes(c))


def decode_step_bytes(c: dict, state_slots: float, latent_rows: float,
                      experts_touched: float) -> float:
    """Fixed weights once, the touched experts' weights once, the state."""
    return (decode_fixed_weight_bytes(c) + experts_touched * expert_params(c) * 2
            + state_bytes_per_step(c, state_slots, latent_rows))


def step_args(run, key: str, within: Optional[tuple] = None) -> List[dict]:
    """The arguments of the window's `engine.step` spans that carry `key`
    (the program's own counters, `perfbench/lib/program_spans.py`); `within`
    = (t0, t1) in seconds after the window opened keeps those steps only.
    Empty where the program records no such counter."""
    from perfbench.lib import program_spans

    steps = (program_spans.window(run) or {}).get("steps", [])
    if within is not None:
        t0, t1 = (1e6 * (run["t_open"] + t) for t in within)
        steps = [s for s in steps if t0 <= s["ts"] < t1]
    return [s["args"] for s in steps if key in s.get("args", {})]


def held_expert_slots(c: dict) -> int:
    """Held experts summed over the expert layers."""
    return n_layers_of(c)[3] * c["experts_held"]["count"]
