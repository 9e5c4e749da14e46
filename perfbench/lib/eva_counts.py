"""Operations and bytes the EvaByte stack needs, from shapes alone. A
configuration is the dict of its file (Hugging Face key names)."""

from __future__ import annotations


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def mixer_params(c: dict) -> int:
    """W_q, W_k, W_v, W_o (a key head a query head)."""
    d, H, kvh, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], head_dim(c))
    return 2 * d * H * hd + 2 * d * kvh * hd


def swiglu_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["num_pred_heads"] * c["vocab_size"]


def param_count(c: dict) -> int:
    """Every matrix: the layers, the embedding and the head of all the
    prediction heads (the norms and `phi`, `mu`, 0.01% of it, are left out)."""
    return (c["num_hidden_layers"] * (mixer_params(c) + swiglu_params(c))
            + c["vocab_size"] * c["hidden_size"] + head_params(c))


def decode_weight_bytes(c: dict, bytes_per_weight: int = 2) -> int:
    """What every decode step reads: the layers and the FIRST prediction
    head's columns (the embedding rows of the step's bytes and the other
    heads' columns are not read)."""
    return bytes_per_weight * (
        c["num_hidden_layers"] * (mixer_params(c) + swiglu_params(c))
        + c["hidden_size"] * c["vocab_size"])


def row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """K and V of ONE row (a position of the window region or a chunk's
    summary) of ONE layer: every head has its own."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * bytes_per_value


def rows_per_slot(c: dict, max_len: int) -> int:
    """Rows of a slot's table a layer: the window region and a summary a chunk."""
    return c["window_size"] + -(-max_len // c["chunk_size"])


def cache_bytes(c: dict, slots: int, max_len: int) -> int:
    """The slot tables and the open chunks' rows, all layers."""
    return c["num_hidden_layers"] * slots * row_bytes(c) * (
        rows_per_slot(c, max_len) + c["chunk_size"])


def cache_bytes_per_step(c: dict, window_rows: float, summary_rows: float) -> float:
    """What one decode step reads of the slot tables: the busy slots' live
    rows of both regions (the program's `window_rows` + `summary_rows`, a
    layer), every layer."""
    return c["num_hidden_layers"] * (window_rows + summary_rows) * row_bytes(c)


def decode_step_bytes(c: dict, window_rows: float, summary_rows: float) -> float:
    return decode_weight_bytes(c) + cache_bytes_per_step(c, window_rows, summary_rows)


def attend_kernel_bytes(c: dict, window_rows: float, summary_rows: float) -> float:
    """What ONE call of the `eva_decode_attention` kernel (one layer, one
    decode step) has to move: the live rows of both regions. (The queries,
    the positions' own rows and the outputs, 3 x 32 x 128 values a slot, are
    left out.)"""
    return (window_rows + summary_rows) * row_bytes(c)


def prompt_pass_flops(c: dict, positions: int) -> float:
    """Matmul operations of a prompt pass over `positions` positions: two a
    weight and position through layers and first head, and the attention's
    4 hd a head for every (query, row) pair a query scores: its own window up
    to itself (half a window on average) and the summaries of the windows
    before it."""
    W, C = c["window_size"], c["chunk_size"]
    H, hd = c["num_attention_heads"], head_dim(c)
    pairs = 0.0
    for start in range(0, positions, W):
        n = min(W, positions - start)
        pairs += n * (n + 1) / 2 + n * (start // C)
    weights = (c["num_hidden_layers"] * (mixer_params(c) + swiglu_params(c)))
    return (2.0 * weights * positions
            + c["num_hidden_layers"] * 4.0 * H * hd * pairs)


def decode_flops(c: dict) -> int:
    """Matmul operations of one byte through the stack and the first head."""
    return decode_weight_bytes(c, 1) * 2
