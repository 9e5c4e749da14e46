"""Operations and bytes Command A+'s language model needs, from shapes alone.
A configuration is the dict of its file (Hugging Face key names;
`experts_held` says which experts of the router's `of` live here). Every
count is the LEAST the work needs, counted from the PAIRS (query, key) the
equations name and not from the blocks a kernel visits: a share computed
from it reads the same work whatever implements it, and cannot pass 100%."""

from __future__ import annotations

from typing import List, Optional, Tuple


def attn_params(c: dict) -> int:
    d, H, kvh, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    return 2 * d * H * hd + 2 * d * kvh * hd


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["experts_held"]["of"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def shared_params(c: dict) -> int:
    return c["num_shared_experts"] * expert_params(c)


def fixed_layer_params(c: dict) -> int:
    """A layer outside its routed experts: what every token is multiplied
    with, and its one LayerNorm."""
    return attn_params(c) + router_params(c) + shared_params(c) + c["hidden_size"]


def layer_params(c: dict) -> int:
    return fixed_layer_params(c) + c["experts_held"]["count"] * expert_params(c)


def param_count(c: dict) -> int:
    """Every matrix and vector held here; the head is the embedding."""
    return (c["num_hidden_layers"] * layer_params(c)
            + c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def layer_kinds(c: dict) -> Tuple[int, int]:
    """(window layers, full layers) of the layers held here."""
    types = c["layer_types"][:c["num_hidden_layers"]]
    return types.count("sliding_attention"), types.count("full_attention")


def held_expert_slots(c: dict) -> int:
    return c["num_hidden_layers"] * c["experts_held"]["count"]


def row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """A position's k and v in ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def slot_rows(c: dict, max_len: int) -> int:
    """Rows one slot keeps over the layers: a full layer a row a position, a
    window layer a ring of `sliding_window` rows."""
    n_window, n_full = layer_kinds(c)
    return n_full * max_len + n_window * min(c["sliding_window"], max_len)


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
    the shared experts, the head (the embedding rows of the step's tokens
    are left out)."""
    return bytes_per_weight * (
        c["num_hidden_layers"] * (fixed_layer_params(c) - c["hidden_size"])
        + c["vocab_size"] * c["hidden_size"])


def decode_step_bytes(c: dict, window_rows: float, full_rows: float,
                      experts_touched: float) -> float:
    """The fixed weights once, the TOUCHED experts' weights once, the rows
    of ring and full caches."""
    return (decode_fixed_weight_bytes(c) + 2.0 * experts_touched * expert_params(c)
            + cache_bytes_per_step(c, window_rows, full_rows))


def attended_pairs(c: dict, n: int) -> Tuple[float, float]:
    """(query, key) pairs of a prompt of n positions in ONE layer: (a window
    layer: min(t + 1, W) for every query t; a full layer: t + 1)."""
    w = min(c["sliding_window"], n)
    return w * (w + 1) / 2 + (n - w) * w, n * (n + 1) / 2


def attention_flops(c: dict, n: int) -> float:
    """q . k and p . v over the pairs of one prompt, all layers held here."""
    window, full = attended_pairs(c, n)
    n_window, n_full = layer_kinds(c)
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * (
        n_window * window + n_full * full)


def product_flops(c: dict, tokens: float, assignments: Optional[float] = None,
                  head_rows: float = 0.0) -> float:
    """The matrix products of `tokens` positions through the layers held
    here: attention's projections, router, shared experts, and the routed
    experts of the `assignments` that LANDED here (None: the even share,
    tokens x layers x k x held / of), and the head for `head_rows` rows."""
    L, held = c["num_hidden_layers"], c["experts_held"]
    if assignments is None:
        assignments = tokens * L * c["num_experts_per_tok"] * held["count"] / held["of"]
    return 2.0 * (tokens * L * (fixed_layer_params(c) - c["hidden_size"])
                  + assignments * expert_params(c)
                  + head_rows * c["vocab_size"] * c["hidden_size"])


def step_args(run, within: Optional[tuple] = None) -> List[dict]:
    """The arguments of the window's `engine.step` spans of THIS cache kind
    (`window_rows` beside `full_rows` is the ring-and-rows cache's own).
    Empty on another cell's record or a program without the counters."""
    from perfbench.lib import hybrid_counts

    return [a for a in hybrid_counts.step_args(run, "full_rows", within)
            if "window_rows" in a]


def pass_steps(run, within: Optional[tuple] = None) -> List[tuple]:
    """(start, end, [arguments of its `engine.prefill_dispatch` spans: `tokens`,
    the TRUE tokens of the pass, `bucket`]) of every `engine.step` that
    dispatched a prompt pass and both began and ENDED inside `within` = (t0,
    t1) seconds after the window opened (None: the window); start and end in
    seconds on the wall clock. The step that dispatches a pass waits for its
    first token, so a pass whose step ended there ran there whole, between
    the step's start and its end."""
    from perfbench.lib import program_spans

    if not program_spans.window(run):
        return []
    got = run.get("program_spans") or program_spans._fetch()
    t0, t1 = (1e6 * (run["t_open"] + t) for t in (within or (0.0, run["seconds"])))
    steps = [(e["pid"], e["tid"], e["ts"], e["ts"] + e["dur"]) for e in got["events"]
             if e["name"] == "engine.step" and e.get("ph") == "X"
             and t0 <= e["ts"] and e["ts"] + e["dur"] < t1]
    passes = [e for e in got["events"]
              if e["name"] == "engine.prefill_dispatch" and e.get("ph") == "X"
              and "tokens" in (e.get("args") or {})]
    out = [(a / 1e6, b / 1e6, [e["args"] for e in passes
                               if p == e["pid"] and t == e["tid"] and a <= e["ts"] < b])
           for p, t, a, b in sorted(steps, key=lambda s: s[2])]
    return [s for s in out if s[2]]


def finished_passes(run, within: Optional[tuple] = None) -> List[dict]:
    """The arguments of the prompt passes of `pass_steps`."""
    return [a for _, _, passes in pass_steps(run, within) for a in passes]


def pass_kernel_calls(c: dict, a: dict) -> int:
    """Calls of the prompt kernel under the span `a` (`tokens`, `bucket`) of
    ONE prompt: one a layer for every window the pass walks; a prompt inside
    one window walks none."""
    walked = -(-a["tokens"] // c["sliding_window"]) \
        if a["bucket"] > c["sliding_window"] else 1
    return c["num_hidden_layers"] * walked


def pass_attention_flops(c: dict, a: dict) -> float:
    """`attention_flops` of the prompts under the span `a`: `batch` prompts
    of one bucket admitted in one step pass one after another under ONE span
    that holds the sum of their `tokens`. The pairs grow faster than the
    tokens, so the even split is the least they can be."""
    return a["batch"] * attention_flops(c, a["tokens"] / a["batch"])


def prefill_dispatches(run) -> List[dict]:
    """The window's `engine.prefill_dispatch` spans that carry `tokens`."""
    from perfbench.lib import keye_counts

    return keye_counts.prefill_dispatches(run)


def prompt_kernel_events(run) -> List[list]:
    """[[start, seconds], ...] of the prompt kernel's device events in the
    traced run, the start on the wall clock (`lib.cmda_replica`)."""
    return (run.get("trace") or {}).get("prompt_kernel_events") or []


def kernel_calls(run, kernel: str):
    """[events, seconds] the traced run's reduction kept of one kernel
    (`lib.cmda_replica`), or None."""
    return ((run.get("trace") or {}).get("kernel_calls") or {}).get(kernel) or None
