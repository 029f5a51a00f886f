"""Operations of the model's work, counted from shapes.

Counted from the operation, never from the compiled program, so a
program that does less work cannot move its own yardstick.  One
multiply-add is two operations.  ``c`` is a configuration file's dict;
the two counts that depend on the architecture, ``matmul_params`` and
``attention_flops``, are its reference module's
(``reference/<model_type>.py``).
"""
from __future__ import annotations

import arch


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product for every token (an
    embedding that is only looked up is not among them)."""
    return arch.reference(c).matmul_params(c)


def attention_flops(c: dict, n_keys_total: int) -> float:
    """Forward operations of attention for ``n_keys_total`` (query, key)
    pairs in all, in every layer."""
    return arch.reference(c).attention_flops(c, n_keys_total)


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
