"""Operations and bytes the Granite-4.0-H stack needs, from shapes alone. A
configuration is the dict of its file (Hugging Face key names;
`num_local_experts` is what is HELD here, `experts_held.of` the router's
width)."""

from __future__ import annotations

from typing import List, Optional


def n_layers_of(c: dict):
    """(Mamba-2 layers, attention layers) of the layers that are run."""
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    return kinds.count("mamba"), kinds.count("attention")


def d_inner(c: dict) -> int:
    return c["mamba_n_heads"] * c["mamba_d_head"]


def conv_channels(c: dict) -> int:
    """x, B and C go through the convolution together (one group)."""
    return d_inner(c) + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def mamba2_mixer_params(c: dict) -> int:
    d, di, H, K = c["hidden_size"], d_inner(c), c["mamba_n_heads"], c["mamba_d_conv"]
    return (d * (di + conv_channels(c) + H) + di * d   # W_in [z | xBC | dt], W_out
            + (K + 1) * conv_channels(c)               # convolution and its bias
            + 3 * H + di)                              # dt_bias, A_log, D; the gated norm


def attn_mixer_params(c: dict) -> int:
    d, H, kvh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    return 2 * d * H * hd + 2 * d * kvh * hd


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def shared_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["shared_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["experts_held"]["of"]


def layer_params(c: dict, kind: str) -> int:
    """One layer: its mixer, router, held experts and shared MLP (the two
    norms' 8192 are left out)."""
    mixer = mamba2_mixer_params(c) if kind == "mamba" else attn_mixer_params(c)
    return (mixer + router_params(c) + shared_params(c)
            + c["num_local_experts"] * expert_params(c))


def param_count(c: dict) -> int:
    """Every matrix and per-channel vector held here; the embedding slice
    once: it is the head too."""
    mamba, attn = n_layers_of(c)
    return (mamba * layer_params(c, "mamba") + attn * layer_params(c, "attention")
            + c["vocab_size"] * c["hidden_size"])


def held_expert_slots(c: dict) -> int:
    """Held experts summed over the layers (every layer has them)."""
    return c["num_hidden_layers"] * c["num_local_experts"]


def decode_fixed_weight_bytes(c: dict, bytes_per_weight: int = 2) -> int:
    """What every decode step reads whatever the routing: mixers, routers,
    shared MLPs, the head slice (the embedding rows of the step's tokens are
    left out)."""
    mamba, attn = n_layers_of(c)
    return bytes_per_weight * (
        mamba * mamba2_mixer_params(c) + attn * attn_mixer_params(c)
        + (mamba + attn) * (router_params(c) + shared_params(c))
        + c["vocab_size"] * c["hidden_size"])


def ssm_state_bytes_per_slot(c: dict) -> int:
    """S of every Mamba-2 layer: heads x head width x state columns, float32."""
    return n_layers_of(c)[0] * d_inner(c) * c["mamba_d_state"] * 4


def conv_tail_bytes_per_slot(c: dict, bytes_per_value: int = 2) -> int:
    return n_layers_of(c)[0] * (c["mamba_d_conv"] - 1) * conv_channels(c) \
        * bytes_per_value


def kv_row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """K and V of ONE position over the attention layers."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return n_layers_of(c)[1] * 2 * c["num_key_value_heads"] * hd * bytes_per_value


def slot_bytes(c: dict, max_len: int) -> int:
    """State, tails and K/V rows one slot holds at `max_len` positions."""
    return (ssm_state_bytes_per_slot(c) + conv_tail_bytes_per_slot(c)
            + max_len * kv_row_bytes(c))


def state_bytes_per_step(c: dict, state_slots: float) -> float:
    """S and the convolution tail read AND written for every slot whose
    state the step needs."""
    return 2.0 * state_slots * (ssm_state_bytes_per_slot(c)
                                + conv_tail_bytes_per_slot(c))


def decode_step_bytes(c: dict, state_slots: float, kv_rows: float,
                      experts_touched: float) -> float:
    """The fixed weights once, the TOUCHED experts' weights once (not the
    held ones'), the busy slots' state read and written, live K/V rows read."""
    return (decode_fixed_weight_bytes(c) + 2.0 * experts_touched * expert_params(c)
            + state_bytes_per_step(c, state_slots) + kv_rows * kv_row_bytes(c))


def step_kernel_bytes(c: dict, busy_slots: float) -> float:
    """What ONE call of the `ssd_step` kernel (one layer, one decode step)
    has to move, all float32: per busy slot the state read and written once,
    the decay and dt x rows read, y written, B and C read."""
    di, n = d_inner(c), c["mamba_d_state"]
    return 4.0 * busy_slots * (2 * n * di + 3 * di + 2 * n)


def decode_flops(c: dict) -> int:
    """Matmul operations of one token through the stack and the head, with
    the experts a token uses HERE on average (k x held / all)."""
    mamba, attn = n_layers_of(c)
    here = c["num_experts_per_tok"] * c["num_local_experts"] / c["experts_held"]["of"]
    return int(2 * (mamba * mamba2_mixer_params(c) + attn * attn_mixer_params(c)
                    + (mamba + attn) * (router_params(c) + shared_params(c)
                                        + here * expert_params(c))
                    + c["vocab_size"] * c["hidden_size"]))


def step_args(run, within: Optional[tuple] = None) -> List[dict]:
    """The arguments of the window's `engine.step` spans of THIS cache kind:
    the runs cache's `kv_rows` beside the scanned expert layers'
    `experts_touched` (no other model's steps carry both). Empty on another
    cell's record or a program without the counters."""
    from perfbench.lib import hybrid_counts

    return [a for a in hybrid_counts.step_args(run, "experts_touched", within)
            if "kv_rows" in a]
