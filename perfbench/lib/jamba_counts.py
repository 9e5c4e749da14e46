"""Operations and bytes the Jamba stack needs, from shapes alone. A
configuration is the dict of its file (Hugging Face key names)."""

from __future__ import annotations


def n_layers_of(c: dict):
    """(Mamba layers, attention layers)."""
    L = c["num_hidden_layers"]
    attn = sum(1 for i in range(L)
               if i % c["attn_layer_period"] == c["attn_layer_offset"])
    return L - attn, attn


def d_inner(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def mamba_mixer_params(c: dict) -> int:
    d, di, n, r, K = (c["hidden_size"], d_inner(c), c["mamba_d_state"],
                      c["mamba_dt_rank"], c["mamba_d_conv"])
    return (d * 2 * di + di * d            # W_in, W_out
            + di * (r + 2 * n) + r * di    # W_x, W_dt
            + n * di + (K + 3) * di)       # A_log; convolution, its bias, b_dt, D


def attn_mixer_params(c: dict) -> int:
    d, H, kvh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    return 2 * d * H * hd + 2 * d * kvh * hd


def swiglu_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def param_count(c: dict) -> int:
    """Every matrix and per-channel vector (the norms' 0.15M are left out);
    the embedding once: it is the head too."""
    mamba, attn = n_layers_of(c)
    return (mamba * mamba_mixer_params(c) + attn * attn_mixer_params(c)
            + (mamba + attn) * swiglu_params(c)
            + c["vocab_size"] * c["hidden_size"])


def decode_weight_bytes(c: dict, bytes_per_weight: int = 2) -> int:
    """What every decode step reads: all of it, the embedding as the head
    (the embedding rows of the step's tokens are left out)."""
    return bytes_per_weight * param_count(c)


def ssm_state_bytes_per_slot(c: dict) -> int:
    """h of every Mamba layer, float32."""
    return n_layers_of(c)[0] * c["mamba_d_state"] * d_inner(c) * 4


def conv_tail_bytes_per_slot(c: dict, bytes_per_value: int = 2) -> int:
    return n_layers_of(c)[0] * (c["mamba_d_conv"] - 1) * d_inner(c) * bytes_per_value


def kv_row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """K and V of ONE position over the attention layers."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return n_layers_of(c)[1] * 2 * c["num_key_value_heads"] * hd * bytes_per_value


def state_bytes_per_step(c: dict, state_slots: float, kv_rows: float) -> float:
    """SSM state and convolution tail read AND written for every slot whose
    state the step needs, plus the live K/V rows read."""
    return (2.0 * state_slots * (ssm_state_bytes_per_slot(c)
                                 + conv_tail_bytes_per_slot(c))
            + kv_rows * kv_row_bytes(c))


def decode_step_bytes(c: dict, state_slots: float, kv_rows: float) -> float:
    return decode_weight_bytes(c) + state_bytes_per_step(c, state_slots, kv_rows)


def scan_kernel_bytes(c: dict, batch: int, positions: int) -> int:
    """What ONE call of the `selective_scan` kernel (one layer, a prompt
    bucket of `batch` x `positions` after padding) has to move, all float32:
    u and dt read, y written, B and C read, the state read and written once,
    A once."""
    di, n = d_inner(c), c["mamba_d_state"]
    return 4 * (3 * batch * positions * di + 2 * batch * positions * n
                + 2 * batch * n * di + n * di)


def step_kernel_bytes(c: dict, busy_slots: float) -> float:
    """What ONE call of the `selective_step` kernel (one layer, one decode
    step) has to move, all float32: per busy slot the state read and written
    once, dt and u read, y written, B and C read; A once."""
    di, n = d_inner(c), c["mamba_d_state"]
    return 4.0 * (busy_slots * (2 * n * di + 3 * di + 2 * n) + n * di)


def decode_flops(c: dict) -> int:
    """Matmul operations of one token through the stack and the head."""
    return 2 * param_count(c)
