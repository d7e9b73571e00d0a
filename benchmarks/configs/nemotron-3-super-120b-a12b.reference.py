"""Plain reference of the ``nemotron_h`` hybrid decoder as this benchmark
runs it (NVIDIA-Nemotron-3-Super-120B-A12B: one mixer a layer, by
``hybrid_override_pattern``).

Straightforward ``jax.numpy``: no kernels, no cache, no batching, no
chunked scan, no sorted dispatch, nothing imported from the program under
test. One sequence at a time, ``d`` = ``hidden_size``:

    RMSNorm(x; g) = x / sqrt(mean(x^2) + norm_eps) * g
    h <- h + Mixer_l(RMSNorm(h; g_l))         for each layer of the pattern
    logits = RMSNorm(h; g_f) @ head_w

``M`` Mamba-2: ``[z | xBC | dt] = u @ W_in``; a depthwise causal convolution
of ``conv_kernel`` taps over ``xBC`` (zeros to the left of position 0), then
``silu``; ``x [H, P]``, ``B``, ``C [G, N]`` split off it, head ``h`` using
group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
the recurrence as a ``lax.scan`` over positions, ``S_t = exp(dt_t A) S_{t-1}
+ dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; ``y * silu(z)``, RMSNorm
over each of the ``G`` groups of channels, times ``norm_g``; ``@ W_out``.

``*`` attention: ``num_attention_heads`` query heads, ``num_key_value_heads``
K/V heads of ``head_dim``, query head ``i`` reading K/V head ``i // (heads /
kv heads)``; causal softmax; ``@ Wo``. No rotary term (``assumed``).

``E`` LatentMoE: ``s = sigmoid(u @ router)``; the ``num_experts_per_tok``
experts with the largest ``s + e_bias``; ``w_e = s_e / (sum of the chosen s +
1e-20) * routed_scaling_factor``; ``lat = u @ W_down``; the experts as a LOOP
over the experts held here (``n_routed_experts`` from ``expert_offset`` on, of
the router's ``n_routed_experts_published``), each computing every position
and a mask keeping what was routed to it: ``relu(lat @ w1[e])^2 @ w2[e]``;
what an absent expert would add is left out, as in the program; ``@ W_up``;
plus the shared expert ``relu(u @ ws1)^2 @ ws2``.

``mode`` chooses the arithmetic of every matrix product (``f32``: float32
operands at ``precision=HIGHEST``, the reference; ``bf16``; ``fp8``: operands
rounded to float8_e4m3fn under a per-tensor scale, the control). The
recurrence, the convolution, the norms and the router's sigmoid are
elementwise float32 in every mode. Weights come as the flat dict of
``lib/weights.py`` in the type the configuration stores (bfloat16): each leaf
is upcast where it is used, an expert's matrices one expert at a time, and
the tree is never held in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# -- M: Mamba-2 ---------------------------------------------------------------

def mamba(p, u, *, cfg: dict, mode: str):
    t = u.shape[0]
    h, pd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, taps = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d_in = h * pd
    z, xbc, dt = jnp.split(mm(u, p["W_in"], mode),
                           [d_in, 2 * d_in + 2 * g * n], axis=-1)
    # depthwise causal convolution: position t sees t-taps+1 .. t
    cat = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32),
                           xbc])
    w = p["conv_w"].astype(jnp.float32)
    conv = p["conv_b"].astype(jnp.float32) + sum(
        w[:, k] * cat[k:k + t] for k in range(taps))
    conv = jax.nn.silu(conv)
    x, bm, cm = jnp.split(conv, [d_in, d_in + g * n], axis=-1)
    x = x.reshape(t, h, pd)
    bm = jnp.repeat(bm.reshape(t, g, n), h // g, axis=1)      # [t, H, N]
    cm = jnp.repeat(cm.reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))  # [t, H]
    a = -jnp.exp(p["A_log"].astype(jnp.float32))                 # [H]
    skip = p["D"].astype(jnp.float32)

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        y_t = jnp.sum(s * c_t[:, None, :], axis=-1) + skip[:, None] * x_t
        return s, y_t

    _, y = jax.lax.scan(step, jnp.zeros((h, pd, n), jnp.float32),
                        (x, bm, cm, dt))
    y = y.reshape(t, d_in) * jax.nn.silu(z)      # the gate comes first
    y = y.reshape(t, g, d_in // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg["norm_eps"])
    y = y.reshape(t, d_in) * p["norm_g"].astype(jnp.float32)
    return mm(y, p["W_out"], mode)


# -- *: grouped-query attention ------------------------------------------------

def attention(p, u, *, cfg: dict, mode: str, q_block: int):
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
    rows = min(q_block, t)
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
    return mm(out, p["Wo"], mode)


# -- E: LatentMoE, the experts held here ---------------------------------------

def latent_moe(p, u, *, cfg: dict, mode: str):
    held, offset = cfg["n_routed_experts"], cfg.get("expert_offset", 0)
    s = jax.nn.sigmoid(mm(u, p["router"], mode))       # [t, all experts]
    _, idx = jax.lax.top_k(s + p["e_bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = (chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
         * cfg["routed_scaling_factor"])               # [t, k]
    lat = mm(u, p["W_down"], mode)                     # [t, L]

    def one(e, acc):
        mine = idx == offset + e                       # [t, k]
        w_e = jnp.sum(jnp.where(mine, w, 0.0), axis=-1)
        y = mm(relu2(mm(lat, p["w1"][e], mode)), p["w2"][e], mode)
        return acc + w_e[:, None] * y

    routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(lat))
    shared = mm(relu2(mm(u, p["ws1"], mode)), p["ws2"], mode)
    return mm(routed, p["W_up"], mode) + shared


# -- the model ------------------------------------------------------------------

MIXERS = {"M": mamba, "*": attention, "E": latent_moe}


def _frozen(cfg: dict) -> tuple:
    keys = ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
            "conv_kernel", "norm_eps", "num_attention_heads",
            "num_key_value_heads", "head_dim", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys) + (
        ("expert_offset", cfg.get("expert_offset", 0)),)


@functools.lru_cache(maxsize=None)
def _jit_layer(kind: str, frozen: tuple, mode: str, q_block: int):
    cfg = dict(frozen)
    extra = {"q_block": q_block} if kind == "*" else {}

    def layer(p, g, x):
        u = rms_norm(x, g, cfg["norm_eps"])
        return x + MIXERS[kind](p, u, cfg=cfg, mode=mode, **extra)
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _jit_head(mode: str, eps: float):
    return jax.jit(lambda rows, g, w: mm(rms_norm(rows, g, eps), w, mode))


def hidden(weights, ids, *, cfg: dict, mode: str = "f32",
           q_block: int = 1024):
    """Residual stream after the last layer (before the final norm) for
    one sequence of token ids ``[t]``, layer by layer."""
    x = jnp.take(weights["embed"], jnp.asarray(ids, jnp.int32), axis=0)
    x = x.astype(jnp.float32)
    frozen = _frozen(cfg)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        own = {k[len(f"l{i}."):]: v for k, v in weights.items()
               if k.startswith(f"l{i}.") and k != f"l{i}.g"}
        x = _jit_layer(kind, frozen, mode, min(q_block, x.shape[0]))(
            own, weights[f"l{i}.g"], x)
    return x


def logits_at(weights, ids, positions, *, cfg: dict, mode: str = "f32",
              q_block: int = 1024):
    """Next-token logits ``[len(positions), V]`` (float32) at the given
    positions of one sequence: row j rates the token FOLLOWING position
    ``positions[j]``."""
    x = hidden(weights, ids, cfg=cfg, mode=mode, q_block=q_block)
    rows = jnp.take(x, jnp.asarray(positions, jnp.int32), axis=0)
    return _jit_head(mode, cfg["norm_eps"])(rows, weights["final_g"],
                                            weights["head_w"])
