"""Plain reference of the ``evabyte`` decoder as this benchmark runs it
(EvaByte: EVA attention, a gated FFN, norms with a unit offset, a float32
residual stream and a float32 head over 320 byte ids).

Straightforward ``jax.numpy``: no kernels, no cache, no pages, no batching,
no running softmax, nothing imported from the program under test. One
sequence at a time, ``d`` = ``hidden_size``, ``H`` heads of ``D``
(``hidden_size / num_attention_heads``), window ``W`` = ``window_size``,
chunk ``C`` = ``chunk_size``:

    N(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + g)
    h <- h + Attn(N(h; g1))             in float32
    h <- h + FFN(N(h; g2))              in float32
    logits = N(h; g_f) @ head_w

Attention, head by head, positions ``t`` and ``j`` absolute: ``q_t =
R_t(y_t @ W_q)``, ``k_j = R_j(y_j @ W_k)``, ``v_j = y_j @ W_v``, where ``R``
turns pair ``i`` = columns ``(i, i + D/2)`` of a head (the ``rotate_half``
pairing) by the angle ``t / rope_theta^(2i/D)``. Every complete chunk ``c``
(positions ``C c .. C c + C - 1``) has a summary pair ``k~_c = sum_j
softmax_j(phi . k_j / sqrt(D)) k_j`` and ``v~_c = sum_j softmax_j(mu . k_j /
sqrt(D)) v_j`` over its ``C`` positions (the keys after rotation; no count
term on a summary's logit). Query ``t`` takes, in ONE softmax of logits
scaled by ``1 / sqrt(D)``, the keys ``j`` of its own window block (``W
floor(t / W) <= j <= t``) and the summaries of every chunk before that
block (``c < floor(t / W) W / C``). ``out = concat_h(...) @ W_o``.

Computed one window block at a time (``lax.map`` over blocks of ``W``
queries), each block against its own ``W`` keys and all the sequence's
summaries, masked: the scores of 30k positions never exist at once. The
program pools each chunk from its page and reads a window page by page in
a running softmax; here a block's one softmax over its keys and summaries.

FFN: ``(silu(u @ W_gate) * (u @ W_up)) @ W_down``.

``mode`` chooses the arithmetic of every product (``f32``: float32
operands at ``precision=HIGHEST``, the reference; ``bf16``; ``fp8``:
operands rounded to float8_e4m3fn under a per-tensor scale, the control):
the projections, the pooling vectors' products and sums, the attention's
two products, the FFN and the head. Norms, the rotary map and the softmaxes
are elementwise float32 in every mode. Weights come as the flat dict of
``lib/weights.py`` in the type the configuration stores (bfloat16), upcast
where they are used.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _fp8(x):
    """Round to float8_e4m3fn under a per-tensor scale (largest magnitude
    at the format's largest number, 448), as fp8 inference does."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), scale


def ein(spec: str, a, b, mode: str):
    """``jnp.einsum(spec, a, b)`` in the arithmetic ``mode`` names."""
    if mode == "f32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode != "fp8":
        raise ValueError(f"unknown arithmetic {mode!r}")
    (qa, sa), (qb, sb) = _fp8(a), _fp8(b)
    return jnp.einsum(spec, qa, qb,
                      preferred_element_type=jnp.float32) * (sa * sb)


def mm(a, b, mode: str):
    return ein("...i,io->...o", a, b, mode)


def rms_norm(x, g, eps: float):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * (1.0 + g.astype(jnp.float32)))


def rotary(x, theta: float):
    """``R_t`` over the last axis of ``x [t, H, D]``, row ``t`` at absolute
    position ``t``."""
    t, r = x.shape[0], x.shape[-1]
    inv_freq = jnp.asarray(
        1.0 / np.power(float(theta), np.arange(0, r, 2) / float(r)),
        jnp.float32)
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq)[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def summaries(p, k, v, *, chunk: int, mode: str):
    """``(k~, v~) [t / C, H, D]`` of the complete chunks of ``k, v [t, H,
    D]`` (``t`` a whole number of chunks)."""
    d = k.shape[-1]
    kc = k.reshape(-1, chunk, *k.shape[1:])
    vc = v.reshape(-1, chunk, *v.shape[1:])

    def pool(vec, rows):
        w = jax.nn.softmax(ein("nchd,hd->nch", kc, vec, mode)
                           / np.sqrt(d), axis=1)
        return ein("nch,nchd->nhd", w, rows, mode)
    return pool(p["phi"], kc), pool(p["mu"], vc)


def attention(p, u, *, cfg: dict, mode: str):
    t = u.shape[0]
    h = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // h
    w, c = cfg["window_size"], cfg["chunk_size"]
    if t % w:
        raise ValueError(f"{t} positions: pad to a whole number of windows")
    theta = float(cfg["rope_theta"])
    q = rotary(mm(u, p["W_q"], mode).reshape(t, h, d), theta)
    k = rotary(mm(u, p["W_k"], mode).reshape(t, h, d), theta)
    v = mm(u, p["W_v"], mode).reshape(t, h, d)
    ks, vs = summaries(p, k, v, chunk=c, mode=mode)            # [t/C, H, D]
    n = ks.shape[0]

    def block(i):
        qi, ki, vi = (jax.lax.dynamic_slice_in_dim(x, i * w, w)
                      for x in (q, k, v))
        logits = jnp.concatenate(
            [ein("qhd,khd->hqk", qi, ki, mode),
             ein("qhd,nhd->hqn", qi, ks, mode)], axis=-1) / np.sqrt(d)
        allow = jnp.concatenate(
            [jnp.tril(jnp.ones((w, w), bool)),
             jnp.broadcast_to(jnp.arange(n)[None, :] < i * w // c, (w, n))],
            axis=1)
        pr = jax.nn.softmax(jnp.where(allow[None], logits, -jnp.inf),
                            axis=-1)
        return (ein("hqk,khd->qhd", pr[..., :w], vi, mode)
                + ein("hqn,nhd->qhd", pr[..., w:], vs, mode))

    out = jax.lax.map(block, jnp.arange(t // w))            # [t/W, W, H, D]
    return mm(out.reshape(t, h * d), p["W_o"], mode)


def ffn(p, u, *, cfg: dict, mode: str):
    return mm(jax.nn.silu(mm(u, p["W_gate"], mode)) * mm(u, p["W_up"], mode),
              p["W_down"], mode)


MIXERS = {"attention": attention, "ffn": ffn}
_KEYS = ("num_attention_heads", "hidden_size", "window_size", "chunk_size",
         "rope_theta", "rms_norm_eps")


@functools.lru_cache(maxsize=None)
def _jit_half(kind: str, frozen: tuple, mode: str):
    """One half of a layer: ``x + Mixer(N(x; g))``."""
    cfg = dict(frozen)

    def half(p, g, x):
        u = rms_norm(x, g, cfg["rms_norm_eps"])
        return x + MIXERS[kind](p, u, cfg=cfg, mode=mode)
    return jax.jit(half)


@functools.lru_cache(maxsize=None)
def _jit_head(mode: str, eps: float):
    return jax.jit(lambda rows, g, w: mm(rms_norm(rows, g, eps), w, mode))


def hidden(weights, ids, *, cfg: dict, mode: str = "f32"):
    """Residual stream after the last layer (before the final norm) for
    one sequence of ids ``[t]``, padded with id 0 to a whole number of
    windows (causal: the padding changes no earlier position)."""
    t = len(ids)
    w = cfg["window_size"]
    padded = np.zeros(-(-t // w) * w, np.int32)
    padded[:t] = np.asarray(ids, np.int32)
    x = jnp.take(weights["embed"], jnp.asarray(padded), axis=0)
    x = x.astype(jnp.float32)
    frozen = tuple((k, cfg[k]) for k in _KEYS)
    for i in range(cfg["num_hidden_layers"]):
        own = {k[len(f"l{i}."):]: v for k, v in weights.items()
               if k.startswith(f"l{i}.")}
        x = _jit_half("attention", frozen, mode)(own, own["g1"], x)
        x = _jit_half("ffn", frozen, mode)(own, own["g2"], x)
    return x[:t]


def logits_at(weights, ids, positions, *, cfg: dict, mode: str = "f32",
              q_block: int = 0):
    """Next-token logits ``[len(positions), V]`` (float32) at the given
    positions of one sequence: row j rates the byte FOLLOWING position
    ``positions[j]``. ``q_block`` is the harness's padding, unused: the
    sequence is padded to whole windows here."""
    x = hidden(weights, ids, cfg=cfg, mode=mode)
    rows = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
    return _jit_head(mode, cfg["rms_norm_eps"])(rows, weights["final_g"],
                                                weights["head_w"])
