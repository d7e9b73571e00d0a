"""Plain reference of this repo's expert block (``families/relu_moe.py``),
forward and training, at sizes a test holds: whole batches, no blocks, no
kernels, nothing imported from the program under test.

The block: pre-LayerNorm, multi-head causal self-attention (no positional
table, one bias after the output projection), then the expert layer:
gates = softmax(x · router) in float32; the top k gates are kept (ties go
to the lowest index) and renormalised to sum to one; every expert is
``relu(x · w1[e] + b1[e]) · w2[e] + b2[e]`` and the layer's output is the
gate-weighted sum over the k kept. Training adds, for each layer,
``aux_weight · E · Σ_e mean(gates_e) · mean(kept_e)`` (means over all the
batch's tokens; ``kept`` carries no gradient) to the cross-entropy summed
over a sequence's positions and divided by the number of sequences. Adam
as in ``opt-1.3b-train.reference.py``.

Weights come as the flat dict ``lib/weights.py`` makes from the family's
``leaf_shapes``, in the type the configuration stores them in. ``mode``
chooses the arithmetic of every matrix product, the router's and the
experts' among them: ``f32``, float32 operands at ``precision=HIGHEST``
(the reference), or, for the forward pass alone, ``fp8``: operands rounded
to float8_e4m3fn under a per-tensor scale, float32 accumulation (the
control of a configuration that states ``mixed_bf16``, as in
``opt-1.3b.reference.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
B1, B2, EPS = 0.9, 0.999, 1e-8
HI = jax.lax.Precision.HIGHEST


def _fp8(x):
    """Round to float8_e4m3fn under a per-tensor scale (largest magnitude
    at the format's largest number, 448), as fp8 inference does."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), scale


def ein(spec: str, a, b, mode: str):
    """``jnp.einsum(spec, a, b)`` in the arithmetic that ``mode`` names."""
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=HI)
    if mode != "fp8":
        raise ValueError(f"unknown arithmetic {mode!r}")
    (qa, sa), (qb, sb) = _fp8(a), _fp8(b)
    return jnp.einsum(spec, qa, qb,
                      preferred_element_type=jnp.float32) * (sa * sb)


def layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def attention(p, x, n_heads: int, mode: str = "f32"):
    """x [b, t, d] -> [b, t, d], causal, all heads at once."""
    b, t, d = x.shape
    qkv = ein("btd,de->bte", x, p["wqkv"], mode).reshape(
        b, t, 3, n_heads, d // n_heads)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = ein("bqhd,bkhd->bhqk", q, k, mode) \
        / jnp.sqrt(jnp.float32(d // n_heads))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, mode)
    return ein("btd,de->bte", o.reshape(b, t, d), p["wo"], mode) + p["bo"]


def experts(p, x, top_k: int, mode: str = "f32"):
    """x [b, t, d] -> (y [b, t, d], the load-balancing sum before its
    weight)."""
    e = p["router"].shape[-1]
    gates = jax.nn.softmax(ein("btd,de->bte", x, p["router"], mode), axis=-1)
    _, idx = jax.lax.top_k(gates, top_k)
    kept = jax.nn.one_hot(idx, e).sum(axis=-2) > 0              # [b, t, E]
    w = jnp.where(kept, gates, 0.0)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    h = jax.nn.relu(ein("btd,edh->ebth", x, p["w1"], mode)
                    + p["b1"][:, None, None, :])
    y = (ein("ebth,ehd->ebtd", h, p["w2"], mode)
         + p["b2"][:, None, None, :])
    balance = e * jnp.sum(jnp.mean(gates, axis=(0, 1))
                          * jnp.mean(kept.astype(jnp.float32), axis=(0, 1)))
    return ein("bte,ebtd->btd", w, y, mode), balance


def hidden(w, ids, cfg: dict, mode: str = "f32"):
    """Residual stream after the last layer for ids [b, t], and the sum
    over the layers of the load-balancing terms."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = jnp.take(w["embed"], ids, axis=0)
    balance = 0.0
    for i in range(cfg["num_hidden_layers"]):
        p = {k.split(".", 1)[1]: v for k, v in w.items()
             if k.startswith(f"l{i}.")}
        x = x + attention(p, layer_norm(x, p["ln1_g"], p["ln1_b"]),
                          cfg["num_attention_heads"], mode)
        y, bal = experts(p, layer_norm(x, p["ln2_g"], p["ln2_b"]),
                         cfg["num_experts_per_tok"], mode)
        x, balance = x + y, balance + bal
    return x, balance, w


def logits(w, ids, cfg: dict, mode: str = "f32"):
    x, balance, w = hidden(w, ids, cfg, mode)
    z = ein("btd,dv->btv", layer_norm(x, w["lnf_g"], w["lnf_b"]),
            w["head_w"], mode) + w["head_b"]
    return z, balance


def logits_at(weights, ids, positions, *, cfg: dict, mode: str = "f32",
              q_block: int = 1024):
    """Next-token logits ``[len(positions), V]`` at the given positions of
    one sequence of ids ``[t]`` (``q_block`` is a size of references that
    compute in blocks; this one does not)."""
    z, _ = logits(weights, jnp.asarray(ids, jnp.int32)[None], cfg, mode)
    return jnp.take(z[0], jnp.asarray(positions, jnp.int32), axis=0)


def loss_fn(w, ids, labels, token_weight, cfg: dict):
    z, balance = logits(w, ids, cfg)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                               labels[..., None], axis=-1)[..., 0]
    return (jnp.sum(nll * token_weight) / ids.shape[0]
            + cfg["aux_weight"] * balance)


def _norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def train_steps(weights, batches, *, cfg: dict, mode: str = "f32",
                token_weight=None, first_grads=None,
                keep_first_grads: bool = False):
    """``len(batches)`` Adam steps on batches of ``(ids [b, t], labels
    [b, t])``; the dict ``lib/compare.compare_training`` takes (see
    ``opt-1.3b-train.reference.py``, whose arguments these are)."""
    if mode != "f32":
        raise ValueError(f"this reference trains in f32 only, not {mode!r}")
    grad = jax.jit(jax.value_and_grad(functools.partial(loss_fn, cfg=cfg)))
    p = {k: v.astype(jnp.float32) for k, v in weights.items()}
    start = dict(p)
    m = {k: jnp.zeros_like(v) for k, v in p.items()}
    v_ = {k: jnp.zeros_like(v) for k, v in p.items()}
    lr = cfg["learning_rate"]
    losses, grad_norms, grad_diff_norms, kept = [], {}, {}, {}
    for step, (ids, labels) in enumerate(batches, start=1):
        ids = jnp.asarray(ids, jnp.int32)
        tw = (jnp.ones(ids.shape[1], jnp.float32) if token_weight is None
              else jnp.asarray(token_weight, jnp.float32))
        loss, g = grad(p, ids, jnp.asarray(labels, jnp.int32), tw)
        losses.append(float(loss))
        if step == 1:
            grad_norms = _norms(g)
            if first_grads is not None:
                grad_diff_norms = _norms(
                    {k: g[k] - jnp.asarray(first_grads[k], jnp.float32)
                     for k in g})
            if keep_first_grads:
                kept = {k: np.asarray(v, np.float32) for k, v in g.items()}
        for k in p:
            m[k] = B1 * m[k] + (1.0 - B1) * g[k]
            v_[k] = B2 * v_[k] + (1.0 - B2) * jnp.square(g[k])
            p[k] = p[k] - lr * (m[k] / (1.0 - B1 ** step)) / (
                jnp.sqrt(v_[k] / (1.0 - B2 ** step)) + EPS)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": _norms({k: p[k] - start[k] for k in p}),
            "grad_diff_norms": grad_diff_norms, "first_grads": kept}
