"""Analytic operation and byte counts that belong to no one family: a
causal-attention call's, from its shape. (A whole model's, the numerators of
``*_mfu``, are its family's: ``families/<model_type>.py``.) They count what
the algorithm needs, not what a program happens to execute: recomputation,
padding and masked-out work count nothing. A multiply-add is two operations.
"""

from __future__ import annotations


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
