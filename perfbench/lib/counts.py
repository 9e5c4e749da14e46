"""Operations and bytes the algorithm needs, from shapes alone.

A configuration is the dict of its file (Hugging Face key names)."""

from __future__ import annotations


def _dims(c: dict):
    d = c["hidden_size"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // nq
    return d, nq, nkv, hd, c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"]


def layer_matmul_params(c: dict) -> int:
    """Weights of one block that a token is multiplied with."""
    d, nq, nkv, hd, ff, _, _ = _dims(c)
    return d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * ff


def matmul_params(c: dict) -> int:
    """Every matmul weight a token passes: the blocks and the untied head.
    The embedding table is a gather, not a matmul, and is left out."""
    d, _, _, _, _, vocab, layers = _dims(c)
    return layers * layer_matmul_params(c) + d * vocab


def param_count(c: dict) -> int:
    d, _, _, _, _, vocab, layers = _dims(c)
    tied = c.get("tie_word_embeddings", False)
    return (layers * (layer_matmul_params(c) + 2 * d) + d
            + vocab * d * (1 if tied else 2))


def train_matmul_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward matmul operations one token of a causal sequence
    of `seq` needs: 6 per weight (2 forward, 4 backward) plus attention's
    QK^T and PV over the causal half, 2*seq*nq*hd forward per layer and
    three times that with the backward. No recompute, no embedding gather."""
    _, nq, _, hd, _, _, layers = _dims(c)
    return 6.0 * matmul_params(c) + 6.0 * layers * seq * nq * hd


def decode_weight_bytes(c: dict, bytes_per_weight: int = 2) -> int:
    """What one decode step must read of the weights: every block, the norms
    and the head once (the embedding rows of a few tokens are negligible and
    left out)."""
    d, _, _, _, _, _, layers = _dims(c)
    return (matmul_params(c) + layers * 2 * d + d) * bytes_per_weight


def cache_row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of ONE position over all layers."""
    _, _, nkv, hd, _, _, layers = _dims(c)
    return layers * 2 * nkv * hd * bytes_per_value


def decode_step_bytes(c: dict, live_rows: float) -> float:
    """Weights once plus the live rows of the cache (sum over busy slots of
    their current length)."""
    return decode_weight_bytes(c) + live_rows * cache_row_bytes(c)
