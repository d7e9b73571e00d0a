"""Plain reference of the ``solar_open2`` hybrid decoder as this benchmark
runs it (Solar-Open2-250B: Kimi delta attention layers beside gated NoPE
grouped-query layers, every layer followed by gated experts).

Straightforward ``jax.numpy``: no kernels, no cache, no batching, no
chunked form, no sorted dispatch, nothing imported from the program under
test. One sequence at a time, ``d`` = ``hidden_size``:

    N(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g
    h <- h + Mixer_l(N(h; g1))       GQA where l is in gqa_layers, else KDA
    h <- h + Experts_l(N(h; g2))     for each layer
    logits = N(h; g_f) @ head_w

KDA (``linear_attn_config``: ``H`` heads of ``D``, convolution of ``K``
taps; gate rank ``r`` = ``kda_gate_rank``): ``[q~ | k~ | v~ | b | f | g] = u @
W_in`` (3 x H·D, H, r, r); a depthwise causal convolution of ``K`` taps
over ``[q~ | k~ | v~]`` (zeros to the left of position 0), no bias, then
``silu``; per head ``q = q / |q|``, ``k = k / |k|`` (``|x| = sqrt(sum x^2 +
1e-6)``), ``q = q / sqrt(D)``; ``beta = 2 sigmoid(b)``; ``g = -exp(A_log) *
softplus(f @ W_fu + dt_bias)`` per channel; THE RECURRENCE ITSELF, one
position at a time (``lax.scan``): ``S <- Diag(exp g) S``, ``S <- (I - beta k
k^T) S + beta k v^T``, ``o = S^T q``; per head ``N(o; norm_g)`` times
``sigmoid(g @ W_gu)``; ``@ W_o``.

GQA: ``num_attention_heads`` query heads and ``num_key_value_heads`` K/V
heads of ``head_dim`` (``[q | k | v] = u @ Wqkv``), query head ``i`` reading
K/V head ``i // (heads / kv heads)``; no positional term (``use_rope``
false); scores over ``sqrt(head_dim)``; causal softmax, in blocks of at most
256 query rows so that no ``[heads, rows, 16k]`` block outgrows the chip;
the heads' output times ``sigmoid(u @ Wgate)`` (``use_gqa_gate``); ``@ Wo``.

Experts: ``s = sigmoid(u @ router)``; the ``num_experts_per_tok`` experts
with the largest ``s + e_bias``; ``w_e = s_e / (sum of the chosen s + 1e-20)
* routed_scaling_factor``; the experts as a LOOP over the experts held here
(``n_routed_experts`` from ``expert_offset`` on, of the router's
``n_routed_experts_published``), each computing every position and a mask
keeping what was routed to it: ``(silu(u @ wg[e]) * (u @ wu[e])) @ wd[e]``;
what an absent expert would add is left out, as in the program; plus the
shared expert, the same form with weight 1.

``mode`` chooses the arithmetic of every matrix product (``f32``: float32
operands at ``precision=HIGHEST``, the reference; ``bf16``; ``fp8``: operands
rounded to float8_e4m3fn under a per-tensor scale, the control). The
recurrence, the convolution, the gates, the norms, the router's sigmoid and
the softmax are elementwise float32 in every mode. Weights come as the flat
dict of ``lib/weights.py`` in the type the configuration stores (bfloat16):
each leaf is upcast where it is used, an expert's matrices one expert at a
time, and the tree is never held in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_ROWS = 256


def _fp8(x):
    """Round to float8_e4m3fn under a per-tensor scale (largest magnitude
    at the format's largest number, 448), as fp8 inference does."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), scale


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
    (qa, sa), (qb, sb) = _fp8(a), _fp8(b)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.float32) * (sa * sb)


def rms_norm(x, g, eps: float):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * g.astype(jnp.float32))


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def gated(u, wg, wu, wd, mode: str):
    return mm(jax.nn.silu(mm(u, wg, mode)) * mm(u, wu, mode), wd, mode)


# -- KDA: Kimi delta attention, the recurrence --------------------------------

def kda(p, u, *, cfg: dict, mode: str):
    t = u.shape[0]
    h, d, r = cfg["kda_heads"], cfg["kda_head_dim"], cfg["kda_gate_rank"]
    taps = cfg["kda_conv"]
    hd = h * d
    qkv, b, f, g_lo = jnp.split(mm(u, p["W_in"], mode),
                                [3 * hd, 3 * hd + h, 3 * hd + h + r], axis=-1)
    cat = jnp.concatenate([jnp.zeros((taps - 1, 3 * hd), jnp.float32), qkv])
    w = p["conv_w"].astype(jnp.float32)
    conv = jax.nn.silu(sum(w[:, k] * cat[k:k + t] for k in range(taps)))
    q, k, v = (x.reshape(t, h, d) for x in jnp.split(conv, 3, axis=-1))
    q = unit(q) / jnp.sqrt(jnp.float32(d))
    k = unit(k)
    beta = 2.0 * jax.nn.sigmoid(b)                             # [t, H]
    decay = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        mm(f, p["W_fu"], mode) + p["dt_bias"].astype(jnp.float32)
    ).reshape(t, h, d)
    eye = jnp.eye(d, dtype=jnp.float32)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s                       # Diag(exp g) S
        erase = eye - b_t[:, None, None] * k_t[:, :, None] * k_t[:, None, :]
        s = (jnp.einsum("hij,hjv->hiv", erase, s,
                        precision=jax.lax.Precision.HIGHEST)
             + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :])
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)         # S^T q

    _, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32),
                        (q, k, v, decay, beta))
    o = rms_norm(o, p["norm_g"], cfg["rms_norm_eps"])          # [t, H, D]
    o = o.reshape(t, hd) * jax.nn.sigmoid(mm(g_lo, p["W_gu"], mode))
    return mm(o, p["W_o"], mode)


# -- GQA: gated grouped-query attention, no positional term ---------------------

def attention(p, u, *, cfg: dict, mode: str):
    t = u.shape[0]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    q, k, v = jnp.split(mm(u, p["Wqkv"], mode), [h * d, (h + kv) * d],
                        axis=-1)
    q = jnp.transpose(q.reshape(t, h, d), (1, 0, 2))           # [h, t, d]
    k = jnp.repeat(jnp.transpose(k.reshape(t, kv, d), (1, 2, 0)), h // kv,
                   axis=0)                                     # [h, d, t]
    v = jnp.repeat(jnp.transpose(v.reshape(t, kv, d), (1, 0, 2)), h // kv,
                   axis=0)                                     # [h, t, d]
    rows = t if t <= Q_ROWS or t % Q_ROWS else Q_ROWS
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    def one(i):       # a block of query rows, so that [h, rows, t] fits
        qi = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = mm(qi, k, mode) * scale
        allow = (jnp.arange(t)[None, :]
                 <= (i * rows + jnp.arange(rows))[:, None])
        s = jnp.where(allow[None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v, mode)         # [h, rows, d]

    out = jax.lax.map(one, jnp.arange(t // rows))      # [blocks, h, rows, d]
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(t, h * d)
    out = out * jax.nn.sigmoid(mm(u, p["Wgate"], mode))
    return mm(out, p["Wo"], mode)


# -- experts: gated, the experts held here -------------------------------------

def experts(p, u, *, cfg: dict, mode: str):
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    s = jax.nn.sigmoid(mm(u, p["router"], mode))       # [t, all experts]
    _, idx = jax.lax.top_k(s + p["e_bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = (chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
         * cfg["routed_scaling_factor"])               # [t, k]

    def one(e, acc):
        mine = idx == offset + e                       # [t, k]
        w_e = jnp.sum(jnp.where(mine, w, 0.0), axis=-1)
        return acc + w_e[:, None] * gated(u, p["wg"][e], p["wu"][e],
                                          p["wd"][e], mode)

    routed = jax.lax.fori_loop(0, held, one,
                               jnp.zeros(u.shape, jnp.float32))
    return routed + gated(u, p["sg"], p["su"], p["sd"], mode)


# -- the model ------------------------------------------------------------------

MIXERS = {"kda": kda, "gqa": attention, "experts": experts}


def _frozen(cfg: dict) -> tuple:
    lin = cfg["linear_attn_config"]
    return (("kda_heads", lin["num_heads"]), ("kda_head_dim", lin["head_dim"]),
            ("kda_conv", lin["short_conv_kernel_size"]),
            ("kda_gate_rank", cfg["kda_gate_rank"]),
            ("num_attention_heads", cfg["num_attention_heads"]),
            ("num_key_value_heads", cfg["num_key_value_heads"]),
            ("head_dim", cfg["head_dim"]),
            ("rms_norm_eps", cfg["rms_norm_eps"]),
            ("n_routed_experts", cfg["n_routed_experts"]),
            ("num_experts_per_tok", cfg["num_experts_per_tok"]),
            ("routed_scaling_factor", cfg["routed_scaling_factor"]),
            ("expert_offset", cfg.get("expert_offset", 0)))


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
    one sequence of token ids ``[t]``, half a layer at a time."""
    x = jnp.take(weights["embed"], jnp.asarray(ids, jnp.int32), axis=0)
    x = x.astype(jnp.float32)
    frozen = _frozen(cfg)
    gqa = set(cfg["gqa_layers"])
    for i in range(cfg["num_hidden_layers"]):
        own = {k[len(f"l{i}."):]: v for k, v in weights.items()
               if k.startswith(f"l{i}.")}
        mixer = "gqa" if i in gqa else "kda"
        x = _jit_half(mixer, frozen, mode)(own, own["g1"], x)
        x = _jit_half("experts", frozen, mode)(own, own["g2"], x)
    return x


def logits_at(weights, ids, positions, *, cfg: dict, mode: str = "f32",
              q_block: int = Q_ROWS):
    """Next-token logits ``[len(positions), V]`` (float32) at the given
    positions of one sequence: row j rates the token FOLLOWING position
    ``positions[j]``. The attention takes its query rows in blocks of
    ``Q_ROWS`` whatever ``q_block`` says (a sequence whose length is no
    multiple of it, in one block)."""
    del q_block
    x = hidden(weights, ids, cfg=cfg, mode=mode)
    rows = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
    return _jit_head(mode, cfg["rms_norm_eps"])(rows, weights["final_g"],
                                                weights["head_w"])
