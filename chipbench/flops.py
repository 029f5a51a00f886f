"""Operations of the model's work, counted from shapes.

Counted from the operation, never from the compiled program, so a
program that does less work cannot move its own yardstick.  One
multiply-add is two operations.  ``c`` is a configuration file's dict.
"""
from __future__ import annotations


def _dims(c):
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    return d, hd, c["num_attention_heads"], c["num_key_value_heads"]


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    projections and the MLP of every layer, and the LM head (a tied
    embedding counts once, as the head; an untied embedding is a lookup)."""
    d, hd, h, kv = _dims(c)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * c["intermediate_size"]
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * d


def attention_flops(c: dict, n_keys_total: int) -> float:
    """Forward operations of attention scores and the weighted sum, for
    ``n_keys_total`` (query, key) pairs in all, in every layer: 2 matrix
    products x 2 x heads x head size per pair."""
    d, hd, h, kv = _dims(c)
    return 4.0 * h * hd * n_keys_total * c["num_hidden_layers"]


def causal_pairs(length: int, start: int = 0) -> int:
    """(query, key) pairs of causal attention for queries at positions
    [start, length) over keys [0, query]."""
    return sum(q + 1 for q in range(start, length))


def train_flops(c: dict, lengths) -> float:
    """Model operations to train on sequences of the given lengths: a
    forward and a backward pass, 6 N per token, plus attention at the
    sequences' own lengths, three forward passes' worth.  The proximal
    forward of decoupled PPO recomputes log-probabilities and is not
    counted."""
    n = matmul_params(c)
    toks = sum(lengths)
    pairs = sum(causal_pairs(x) for x in lengths)
    return 6.0 * n * toks + 3 * attention_flops(c, pairs)


def decode_flops(c: dict, n_tokens: int, key_pairs: int) -> float:
    """Operations to generate ``n_tokens``, attending ``key_pairs`` cached
    keys in all."""
    return 2.0 * matmul_params(c) * n_tokens + attention_flops(c, key_pairs)
