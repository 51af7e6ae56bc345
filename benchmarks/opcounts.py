"""Operations a transformer LM's training step needs, from the
configuration's sizes alone.  Recomputed operations are not counted."""

from __future__ import annotations


def matmul_params(sizes: dict) -> int:
    """Weights that take part in a matrix product once per token: the four
    attention projections and two FFN matrices of each layer, and the
    output head.  Embedding rows are looked up, not multiplied."""
    d, f = sizes["n_embd"], sizes["n_inner"]
    return sizes["n_layer"] * (4 * d * d + 2 * d * f) + d * sizes["vocab_size"]


def forward_flops_per_token(sizes: dict, seq_len: int) -> float:
    """2 operations per weight per token, plus causal attention: a query
    at position i meets i + 1 keys, (seq_len + 1) / 2 on average, in each
    of the two products (scores, values) of each layer."""
    attention = sizes["n_layer"] * 2 * 2 * sizes["n_embd"] * (seq_len + 1) / 2
    return 2.0 * matmul_params(sizes) + attention


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward plus backward: the backward pass costs twice the forward."""
    return 3.0 * forward_flops_per_token(sizes, seq_len)
