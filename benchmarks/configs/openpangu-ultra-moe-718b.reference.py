"""Plain reference of the ``pangu_ultra_moe`` decoder as this benchmark runs
it (openPangu-Ultra-MoE-718B: latent attention with a rotary term, sandwich
norms, leading dense gated layers, then gated experts with a shared one).

Straightforward ``jax.numpy``: no kernels, no cache, no batching, no
absorbed products, no sorted dispatch, nothing imported from the program
under test. One sequence at a time, ``d`` = ``hidden_size``, ``H`` heads:

    N(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g
    h <- h + N(Attn(N(h; g1)); g2)
    h <- h + N(FFN(N(h; g3)); g4)           for each layer
    logits = N(h; g_f) @ head_w

Attention, THE TEXTBOOK FORM (keys and values expanded per head from the
latent; the program's paged path never expands them): ``cq = N(u @ W_qa;
q_norm_g)``; ``q = cq @ W_qb``, per head ``[q_nope (nope) | q_r (rope)]``;
``[ckv | k_r] = u @ W_kva``; ``c = N(ckv; kv_norm_g)``; ``[k_nope_h | v_h] =
c @ W_kvb`` per head; the rotary map ``R_t`` over the ``rope`` columns of
``q_r`` (each head) and of ``k_r`` (one row for all heads): pair ``i`` is
columns ``(i, i + rope/2)``, angle ``t / rope_theta^(2i/rope)``, ``t`` the
absolute position, no long-context scaling; ``score_h(t, s) = (q_nope_h .
k_nope_h(s) + R_t(q_r_h) . R_s(k_r(s))) / sqrt(nope + rope)``; causal
softmax; ``out = concat_h(sum_s p_h v_h(s)) @ W_o``.

Dense FFN (the first ``first_k_dense_replace`` layers): ``(silu(u @ W_gate)
* (u @ W_up)) @ W_down``.

Expert layer: ``s = sigmoid(u @ router)``; the ``num_experts_per_tok``
experts with the largest ``s + e_bias``; ``w_e = s_e / (sum of the chosen s +
1e-20) * routed_scaling_factor``; the experts as a LOOP over the experts
held here (``n_routed_experts`` from ``expert_offset`` on, of the router's
``n_routed_experts_published``), each computing every position and a mask
keeping what was routed to it: ``(silu(u @ wg[e]) * (u @ wu[e])) @ wd[e]``;
what an absent expert would add is left out, as in the program; plus the
shared expert, the same form with weight 1.

``mode`` chooses the arithmetic of every matrix product (``f32``: float32
operands at ``precision=HIGHEST``, the reference; ``bf16``; ``fp8``: operands
rounded to float8_e4m3fn under a per-tensor scale, the control). Norms, the
router's sigmoid, the rotary map and the softmax are elementwise float32 in
every mode. Weights come as the flat dict of ``lib/weights.py`` in the type
the configuration stores (bfloat16): each leaf is upcast where it is used,
an expert's matrices one expert at a time, and the tree is never held in
float32.
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


def rotary(x, theta: float):
    """``R_t`` over the last axis of ``x [t, ..., r]``, row ``t`` at absolute
    position ``t``."""
    t, r = x.shape[0], x.shape[-1]
    inv_freq = jnp.asarray(
        1.0 / np.power(float(theta), np.arange(0, r, 2) / float(r)),
        jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq   # [t, r/2]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


# -- latent attention, keys and values expanded ---------------------------------

def attention(p, u, *, cfg: dict, mode: str, q_block: int):
    t = u.shape[0]
    h, eps, theta = (cfg["num_attention_heads"], cfg["rms_norm_eps"],
                     cfg["rope_theta"])
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvr = cfg["kv_lora_rank"]
    cq = rms_norm(mm(u, p["W_qa"], mode), p["q_norm_g"], eps)
    q = mm(cq, p["W_qb"], mode).reshape(t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)],
                        axis=-1)
    kv = mm(u, p["W_kva"], mode)
    c = rms_norm(kv[:, :kvr], p["kv_norm_g"], eps)
    k_rope = rotary(kv[:, kvr:], theta)                        # [t, rope]
    kv_h = mm(c, p["W_kvb"], mode).reshape(t, h, nope + dv)
    k = jnp.concatenate([kv_h[..., :nope],
                         jnp.broadcast_to(k_rope[:, None, :], (t, h, rope))],
                        axis=-1)                               # [t, h, 192]
    q = jnp.transpose(q, (1, 0, 2))                            # [h, t, 192]
    k = jnp.transpose(k, (1, 2, 0))                            # [h, 192, t]
    v = jnp.transpose(kv_h[..., nope:], (1, 0, 2))             # [h, t, dv]
    rows = min(q_block, t, 256)      # [h, rows, t] scores have to fit
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * rows, rows, axis=1)
        s = mm(qi, k, mode) * scale
        allow = (jnp.arange(t)[None, :]
                 <= (i * rows + jnp.arange(rows))[:, None])
        s = jnp.where(allow[None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), v, mode)         # [h, rows, dv]

    out = jax.lax.map(one, jnp.arange(t // rows))      # [blocks, h, rows, dv]
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(t, h * dv)
    return mm(out, p["W_o"], mode)


# -- the two feed-forward kinds ---------------------------------------------------

def gated(u, wg, wu, wd, mode: str):
    return mm(jax.nn.silu(mm(u, wg, mode)) * mm(u, wu, mode), wd, mode)


def dense_ffn(p, u, *, cfg: dict, mode: str):
    return gated(u, p["W_gate"], p["W_up"], p["W_down"], mode)


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

MIXERS = {"attention": attention, "dense": dense_ffn, "experts": experts}
_KEYS = ("num_attention_heads", "rms_norm_eps", "rope_theta",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "kv_lora_rank", "n_routed_experts", "num_experts_per_tok",
         "routed_scaling_factor")


def _frozen(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _KEYS) + (
        ("expert_offset", cfg.get("expert_offset", 0)),)


@functools.lru_cache(maxsize=None)
def _jit_half(kind: str, frozen: tuple, mode: str, q_block: int):
    """One half of a layer: ``x + N(Mixer(N(x; g_in)); g_out)``."""
    cfg = dict(frozen)
    extra = {"q_block": q_block} if kind == "attention" else {}

    def half(p, g_in, g_out, x):
        u = rms_norm(x, g_in, cfg["rms_norm_eps"])
        y = MIXERS[kind](p, u, cfg=cfg, mode=mode, **extra)
        return x + rms_norm(y, g_out, cfg["rms_norm_eps"])
    return jax.jit(half)


@functools.lru_cache(maxsize=None)
def _jit_head(mode: str, eps: float):
    return jax.jit(lambda rows, g, w: mm(rms_norm(rows, g, eps), w, mode))


def hidden(weights, ids, *, cfg: dict, mode: str = "f32",
           q_block: int = 1024):
    """Residual stream after the last layer (before the final norm) for
    one sequence of token ids ``[t]``, half a layer at a time."""
    x = jnp.take(weights["embed"], jnp.asarray(ids, jnp.int32), axis=0)
    x = x.astype(jnp.float32)
    frozen = _frozen(cfg)
    qb = min(q_block, x.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        own = {k[len(f"l{i}."):]: v for k, v in weights.items()
               if k.startswith(f"l{i}.")}
        ffn = "dense" if i < cfg["first_k_dense_replace"] else "experts"
        x = _jit_half("attention", frozen, mode, qb)(
            own, own["g1"], own["g2"], x)
        x = _jit_half(ffn, frozen, mode, qb)(own, own["g3"], own["g4"], x)
    return x


def logits_at(weights, ids, positions, *, cfg: dict, mode: str = "f32",
              q_block: int = 1024):
    """Next-token logits ``[len(positions), V]`` (float32) at the given
    positions of one sequence: row j rates the token FOLLOWING position
    ``positions[j]``."""
    x = hidden(weights, ids, cfg=cfg, mode=mode, q_block=q_block)
    rows = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
    return _jit_head(mode, cfg["rms_norm_eps"])(rows, weights["final_g"],
                                                weights["head_w"])
