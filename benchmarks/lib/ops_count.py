"""Analytic operation and byte counts, from a configuration's sizes.

These are the numerators of every ``*_mfu`` and ``*_roofline`` metric. They
count what the algorithm needs, not what a program happens to execute:
recomputation, padding and masked-out work count nothing. A multiply-add is
two operations.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> float:
    """Parameters that take part in a matrix product for every token:
    per layer Wqkv 3d², Wo d², the feed-forward pair 2·d·ff, and the d·V
    vocabulary head. The embedding is a gather and counts nothing."""
    d, ff = cfg["hidden_size"], cfg["ffn_dim"]
    return (cfg["num_hidden_layers"] * (4.0 * d * d + 2.0 * d * ff)
            + d * cfg["vocab_size"])


def attention_flops(cfg: dict, context: float) -> float:
    """Forward operations of one token's attention over ``context`` keys in
    every layer: q·kᵀ and p·v, 2·d multiply-adds each."""
    return cfg["num_hidden_layers"] * 4.0 * cfg["hidden_size"] * context


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward of one token in a causal LM trained at
    ``seq_len``: three times the forward (the backward is two matrix
    products for each forward one); the causal attention sees (T+1)/2 keys
    on average, counted as T/2 (copied from bench.py's
    ``_transformer_train_flops_per_token``)."""
    return 3.0 * (2.0 * matmul_params(cfg)
                  + attention_flops(cfg, seq_len / 2.0))


def serve_flops(cfg: dict, computed_tokens: float,
                attended_keys: float) -> float:
    """Forward operations of serving: ``computed_tokens`` tokens pushed
    through the matrices (prompt tokens taken from the prefix cache are not
    among them) and ``attended_keys`` (token, key) pairs, summed over the
    computed tokens, in one layer."""
    return (2.0 * matmul_params(cfg) * computed_tokens
            + attention_flops(cfg, 1.0) * attended_keys)


def causal_attention_flops(batch: int, seq_len: int, heads: int,
                           head_dim: int, products: int) -> float:
    """Necessary operations of ``products`` matrix products over the causal
    triangle of one self-attention call on ``[batch, seq_len, heads,
    head_dim]``: T(T+1)/2 (query, key) pairs, the diagonal included. The
    forward pass has 2 products (q·kᵀ, p·v); the backward pass has 4 (dp,
    dv, dq, dk); a recomputed q·kᵀ counts nothing."""
    pairs = seq_len * (seq_len + 1) / 2.0
    return products * 2.0 * batch * heads * head_dim * pairs


def attention_tensor_bytes(batch: int, seq_len: int, heads: int,
                           head_dim: int, tensors: int,
                           itemsize: int = 2) -> float:
    """Bytes of ``tensors`` arrays of ``[batch, seq_len, heads, head_dim]``
    moved once each: the forward pass reads q, k, v and writes o (4); the
    backward pass reads q, k, v, o, do and writes dq, dk, dv (8). The
    log-sum-exp rows are 1/head_dim of one tensor and left out."""
    return float(tensors) * batch * seq_len * heads * head_dim * itemsize
