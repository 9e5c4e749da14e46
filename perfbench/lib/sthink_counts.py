"""Operations and bytes SmallThinker-21BA3B needs, from shapes alone. A
configuration is the dict of its file (the published key names;
`experts_held` says which experts of the router's `of` live here: all).
Every count is the LEAST the work needs, counted from the PAIRS (query, key)
the equations name and from the experts a step TOUCHED, not from the blocks
a kernel visits: a share computed from it reads the same work whatever
implements it, and cannot pass 100%. What does not depend on the
configuration's keys (the program's spans of prompt passes, the trace's
kernel events) is `lib.cmda_counts`'s."""

from __future__ import annotations

from typing import List, Optional, Tuple

from perfbench.lib.cmda_counts import (finished_passes, kernel_calls,  # noqa: F401
                                       pass_steps, prefill_dispatches,
                                       prompt_kernel_events)


def attn_params(c: dict) -> int:
    d, H, kvh, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    return 2 * d * H * hd + 2 * d * kvh * hd


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["experts_held"]["of"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_ffn_hidden_size"]


def fixed_layer_params(c: dict) -> int:
    """A layer outside its routed experts: what every token is multiplied
    with, and its two RMSNorms."""
    return attn_params(c) + router_params(c) + 2 * c["hidden_size"]


def layer_params(c: dict) -> int:
    return fixed_layer_params(c) + c["experts_held"]["count"] * expert_params(c)


def param_count(c: dict) -> int:
    """Every matrix and vector held here: the layers, the embedding, the
    untied head, the final norm."""
    return (c["num_hidden_layers"] * layer_params(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def layer_kinds(c: dict) -> Tuple[int, int]:
    """(window layers, global layers) of the layers held here."""
    layout = c["sliding_window_layout"][:c["num_hidden_layers"]]
    return sum(layout), len(layout) - sum(layout)


def held_expert_slots(c: dict) -> int:
    return c["num_hidden_layers"] * c["experts_held"]["count"]


def row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """A position's k and v in ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def slot_rows(c: dict, max_len: int) -> int:
    """Rows one slot keeps over the layers: a global layer a row a position,
    a window layer a ring of `sliding_window_size` rows."""
    n_window, n_full = layer_kinds(c)
    return n_full * max_len + n_window * min(c["sliding_window_size"], max_len)


def cache_bytes(c: dict, slots: int, max_len: int) -> int:
    return slots * slot_rows(c, max_len) * row_bytes(c)


def rows_per_step(c: dict, window_rows: float, full_rows: float) -> float:
    """Rows a step reads over the layers, from the rows ONE layer of each
    kind reads (the program's `window_rows` and `full_rows`)."""
    n_window, n_full = layer_kinds(c)
    return n_window * window_rows + n_full * full_rows


def cache_bytes_per_step(c: dict, window_rows: float, full_rows: float) -> float:
    return rows_per_step(c, window_rows, full_rows) * row_bytes(c)


def decode_fixed_weight_bytes(c: dict, bytes_per_weight: int = 2) -> int:
    """What every decode step reads whatever the routing: attention, routers,
    the head (the embedding rows of the step's tokens and the norms' vectors
    are left out)."""
    return bytes_per_weight * (
        c["num_hidden_layers"] * (attn_params(c) + router_params(c))
        + c["vocab_size"] * c["hidden_size"])


def decode_step_bytes(c: dict, window_rows: float, full_rows: float,
                      experts_touched: float) -> float:
    """The fixed weights once, the TOUCHED experts' weights once, the rows
    of ring and full caches."""
    return (decode_fixed_weight_bytes(c) + 2.0 * experts_touched * expert_params(c)
            + cache_bytes_per_step(c, window_rows, full_rows))


def attended_pairs(c: dict, n: int) -> Tuple[float, float]:
    """(query, key) pairs of a prompt of n positions in ONE layer: (a window
    layer: min(t + 1, W) for every query t; a global layer: t + 1)."""
    w = min(c["sliding_window_size"], n)
    return w * (w + 1) / 2 + (n - w) * w, n * (n + 1) / 2


def attention_flops(c: dict, n: float) -> float:
    """q . k and p . v over the pairs of one prompt, all layers held here."""
    window, full = attended_pairs(c, n)
    n_window, n_full = layer_kinds(c)
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * (
        n_window * window + n_full * full)


def product_flops(c: dict, tokens: float, assignments: Optional[float] = None,
                  head_rows: float = 0.0) -> float:
    """The matrix products of `tokens` positions through the layers held
    here: attention's projections, router, and the routed experts of the
    `assignments` that LANDED here (None: tokens x layers x k x held / of:
    with every expert held, all of them), and the head for `head_rows` rows."""
    L, held = c["num_hidden_layers"], c["experts_held"]
    if assignments is None:
        assignments = tokens * L * c["moe_num_active_primary_experts"] \
            * held["count"] / held["of"]
    return 2.0 * (tokens * L * (attn_params(c) + router_params(c))
                  + assignments * expert_params(c)
                  + head_rows * c["vocab_size"] * c["hidden_size"])


def step_args(run, within: Optional[tuple] = None) -> List[dict]:
    """The arguments of the window's `engine.step` spans of THIS cache kind
    as this cell's program reports them (`wrapped_slots` beside
    `window_rows` and `full_rows`). Empty on another cell's record or a
    program without the counter."""
    from perfbench.lib import hybrid_counts

    return [a for a in hybrid_counts.step_args(run, "full_rows", within)
            if "window_rows" in a and "wrapped_slots" in a]


def pass_kernel_calls(c: dict, a: dict) -> int:
    """Calls of the prompt kernel under the span `a` (`tokens`, `bucket`) of
    ONE prompt: one a layer for every window the pass walks; a prompt inside
    one window walks none."""
    W = c["sliding_window_size"]
    walked = -(-a["tokens"] // W) if a["bucket"] > W else 1
    return c["num_hidden_layers"] * walked


def pass_attention_flops(c: dict, a: dict) -> float:
    """`attention_flops` of the prompts under the span `a` (one prompt a span
    here: `SwaCache.max_prefill_batch` is 1; of several, the even split is
    the least their pairs can be)."""
    return a["batch"] * attention_flops(c, a["tokens"] / a["batch"])
