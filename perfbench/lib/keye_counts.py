"""Operations and bytes Keye-VL-2.0's decoder needs, from shapes alone. A
configuration is the dict of its file (Hugging Face key names). Every count
is the LEAST the work needs: a share computed from it cannot pass 100%."""

from __future__ import annotations

from typing import List, Optional

KEY_LANES = 128     # lanes an indexer key is stored in (64 values, whole tiles)


def attn_params(c: dict) -> int:
    d, H, kvh, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    return 2 * d * H * hd + 2 * d * kvh * hd + 2 * hd        # + q_norm, k_norm


def indexer_params(c: dict) -> int:
    sa, d = c["sa_config"], c["hidden_size"]
    J, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return d * J * di + d * di + d * J + 2 * di              # + the LayerNorm


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_params(c: dict) -> int:
    return (attn_params(c) + indexer_params(c) + router_params(c)
            + c["num_experts"] * expert_params(c) + 2 * c["hidden_size"])


def param_count(c: dict) -> int:
    """Every matrix and vector held here: embedding AND head (untied)."""
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def held_expert_slots(c: dict) -> int:
    return c["num_hidden_layers"] * c["num_experts"]


def kv_position_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """A position's `[k ; v]` in ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def key_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """A position's stored indexer key in ONE layer (whole tiles of lanes)."""
    return KEY_LANES * bytes_per_value


def cache_bytes(c: dict, slots: int, max_len: int) -> int:
    return c["num_hidden_layers"] * slots * max_len * (kv_position_bytes(c)
                                                      + key_bytes(c))


def decode_fixed_weight_bytes(c: dict, bytes_per_weight: int = 2) -> int:
    """What every decode step reads whatever the routing: mixers, indexers,
    routers, the head (the embedding rows of the step's tokens are left out)."""
    return bytes_per_weight * (
        c["num_hidden_layers"] * (attn_params(c) + indexer_params(c)
                                  + router_params(c))
        + c["vocab_size"] * c["hidden_size"])


def cache_bytes_per_step(c: dict, index_rows: float, selected_rows: float) -> float:
    """The indexer keys the step scores and the K/V positions it reads, over
    the layers."""
    return c["num_hidden_layers"] * (index_rows * key_bytes(c)
                                     + selected_rows * kv_position_bytes(c))


def decode_step_bytes(c: dict, index_rows: float, selected_rows: float,
                      experts_touched: float) -> float:
    """The fixed weights once, the TOUCHED experts' weights once, the scored
    keys and the chosen positions."""
    return (decode_fixed_weight_bytes(c) + 2.0 * experts_touched * expert_params(c)
            + cache_bytes_per_step(c, index_rows, selected_rows))


def select_flops(c: dict, n: int) -> float:
    """The indexer's scores of one prompt of n positions in one layer: a
    product of J heads x di lanes for every causal pair."""
    sa = c["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * n * (n + 1) / 2


def chosen_pairs(c: dict, n: int) -> float:
    """(query, row) pairs a prompt of n positions attends to: min(t + 1,
    topk) for every query t."""
    k = min(c["sa_config"]["topk"], n)
    return k * (k + 1) / 2 + (n - k) * k


def attention_flops(c: dict, n: int) -> float:
    """q . k and p . v over the CHOSEN rows of one prompt in one layer."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * chosen_pairs(c, n)


def step_args(run, within: Optional[tuple] = None) -> List[dict]:
    """The arguments of the window's `engine.step` spans of THIS cache kind
    (`index_rows` is the sparse-attention cache's own). Empty on another
    cell's record or a program without the counters."""
    from perfbench.lib import hybrid_counts

    return hybrid_counts.step_args(run, "index_rows", within)


def prefill_dispatches(run) -> List[dict]:
    """The window's `engine.prefill_dispatch` spans that carry `tokens`."""
    from perfbench.lib import program_spans

    if not program_spans.window(run):
        return []
    got = run.get("program_spans") or program_spans._fetch()
    t0 = 1e6 * run["t_open"]
    t1 = t0 + 1e6 * run["seconds"]
    return [e for e in got["events"] if e["name"] == "engine.prefill_dispatch"
            and e.get("ph") == "X" and t0 <= e["ts"] < t1
            and "tokens" in (e.get("args") or {})]


def kernel_calls(run, kernel: str):
    """What the traced run's reduction kept of one kernel's device events
    (`lib.keye_replica`), or None."""
    return ((run.get("trace") or {}).get("kernel_calls") or {}).get(kernel) or None
