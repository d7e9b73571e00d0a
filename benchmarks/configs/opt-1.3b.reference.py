"""Plain reference of the OPT decoder as this benchmark runs it.

Straightforward ``jax.numpy``: no kernels, no cache, no batching, nothing
imported from the program under test. The block is OPT's (Zhang et al. 2022,
``facebook/opt-1.3b``): pre-LayerNorm, multi-head causal self-attention, ReLU
feed-forward, final LayerNorm, vocabulary head. Departures from the published
model, in the program and here alike (``assumed`` in the configuration file):
no positional table, no bias on q/k/v (one bias after the output projection),
untied input and output embeddings, random weights from the seed.

``mode`` chooses the arithmetic of every matrix product:

  ``f32``   float32 operands at ``precision=HIGHEST``: the reference.
  ``bf16``  operands rounded to bfloat16, float32 accumulation: what the
            configuration states (``mixed_bf16``); used by tests only.
  ``fp8``   operands rounded to float8_e4m3fn under a per-tensor scale,
            float32 accumulation: the control, the nearest precision below
            the stated one.

Weights come as the flat dict that ``lib/weights.py`` makes from the seed:
``embed [V,d]``, ``l{i}.ln1_g/ln1_b/wqkv/wo/bo/ln2_g/ln2_b/w1/b1/w2/b2``,
``lnf_g/lnf_b``, ``head_w [d,V]``, ``head_b [V]``. ``wqkv`` is ``[d, 3d]``
with columns ordered (q|k|v, head, head_dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
LAYER_KEYS = ("ln1_g", "ln1_b", "wqkv", "wo", "bo", "ln2_g", "ln2_b",
              "w1", "b1", "w2", "b2")


def _fp8(x):
    """Round to float8_e4m3fn under a per-tensor scale (largest magnitude
    at the format's largest number, 448), as fp8 inference does."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), scale


def _mm_fp8_once(a, b):
    (qa, sa), (qb, sb) = _fp8(a), _fp8(b)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.float32) * (sa * sb)


@jax.custom_vjp
def _mm_fp8(a, b):
    """Every matrix product with fp8 operands, the two of the backward
    pass too (each cotangent under its own scale, as fp8 training does:
    unscaled, a cotangent of 1e-4 would round to nought)."""
    return _mm_fp8_once(a, b)


def _mm_fp8_fwd(a, b):
    return _mm_fp8_once(a, b), (a, b)


def _mm_fp8_bwd(saved, dy):
    a, b = saved            # both 2-D, or both with the same leading axes
    return (_mm_fp8_once(dy, jnp.swapaxes(b, -1, -2)),
            _mm_fp8_once(jnp.swapaxes(a, -1, -2), dy))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def mm(a, b, mode: str):
    """``a @ b`` over the last axis of ``a`` and the first of ``b``."""
    if mode == "f32":
        return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode != "fp8":
        raise ValueError(f"unknown arithmetic {mode!r}")
    return _mm_fp8(a.astype(jnp.float32), b.astype(jnp.float32))


def layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def causal_attention(q, k, v, mode: str, q_block: int):
    """softmax(q kᵀ / sqrt(d)) v with a causal mask, one sequence.
    q, k, v: [t, h, d]. Computed in blocks of ``q_block`` query rows so that
    the [h, q_block, t] scores fit; each block is rematerialised in the
    backward pass."""
    t, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    kt = jnp.transpose(k, (1, 2, 0))            # [h, d, t]
    vt = jnp.transpose(v, (1, 0, 2))            # [h, t, d]
    n_blocks = max(1, t // q_block)
    qb = jnp.transpose(q, (1, 0, 2)).reshape(h, n_blocks, t // n_blocks, d)
    qb = jnp.transpose(qb, (1, 0, 2, 3))        # [n_blocks, h, rows, d]
    rows = t // n_blocks

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = mm(qi, kt, mode) * scale            # [h, rows, t]
        q_pos = i * rows + jnp.arange(rows)
        allow = jnp.arange(t)[None, :] <= q_pos[:, None]
        s = jnp.where(allow[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm(p, vt, mode)                  # [h, rows, d]

    out = jax.lax.map(one, (jnp.arange(n_blocks), qb))
    out = jnp.transpose(out, (1, 0, 2, 3)).reshape(h, t, d)
    return jnp.transpose(out, (1, 0, 2))        # [t, h, d]


def block(p, x, n_heads: int, mode: str, q_block: int):
    """One decoder layer on one sequence ``x [t, d]``; ``p`` holds the
    layer's eleven leaves under ``LAYER_KEYS``."""
    t, d = x.shape
    h = layer_norm(x, p["ln1_g"], p["ln1_b"])
    qkv = mm(h, p["wqkv"], mode).reshape(t, 3, n_heads, d // n_heads)
    att = causal_attention(qkv[:, 0], qkv[:, 1], qkv[:, 2], mode, q_block)
    x = x + mm(att.reshape(t, d), p["wo"], mode) + p["bo"]
    h = layer_norm(x, p["ln2_g"], p["ln2_b"])
    h = jnp.maximum(mm(h, p["w1"], mode) + p["b1"], 0.0)
    return x + mm(h, p["w2"], mode) + p["b2"]


def layer_params(weights, i: int):
    return {k: weights[f"l{i}.{k}"] for k in LAYER_KEYS}


@functools.lru_cache(maxsize=None)
def _jit_block(n_heads: int, mode: str, q_block: int):
    return jax.jit(functools.partial(block, n_heads=n_heads, mode=mode,
                                     q_block=q_block))


@functools.lru_cache(maxsize=None)
def _jit_head(mode: str):
    def head(rows, lnf_g, lnf_b, head_w, head_b):
        return mm(layer_norm(rows, lnf_g, lnf_b), head_w, mode) + head_b
    return jax.jit(head)


def hidden(weights, ids, *, cfg: dict, mode: str = "f32",
           q_block: int = 1024):
    """Residual stream after the last layer (before the final LayerNorm)
    for one sequence of token ids ``[t]``, layer by layer; depth and heads
    are the configuration's (``num_hidden_layers``,
    ``num_attention_heads``)."""
    x = jnp.take(weights["embed"], jnp.asarray(ids, jnp.int32), axis=0)
    x = x.astype(jnp.float32)
    f = _jit_block(cfg["num_attention_heads"], mode,
                   min(q_block, x.shape[0]))
    for i in range(cfg["num_hidden_layers"]):
        x = f(layer_params(weights, i), x)
    return x


def logits_at(weights, ids, positions, *, cfg: dict, mode: str = "f32",
              q_block: int = 1024):
    """Next-token logits ``[len(positions), V]`` (float32) at the given
    positions of one sequence: row j rates the token FOLLOWING position
    ``positions[j]``."""
    x = hidden(weights, ids, cfg=cfg, mode=mode, q_block=q_block)
    rows = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
    return _jit_head(mode)(rows, weights["lnf_g"], weights["lnf_b"],
                           weights["head_w"], weights["head_b"])
