"""Loss functions (ILossFunction parity).

The reference delegates loss computation to ND4J ``ILossFunction`` impls
(used from ``nn/layers/BaseOutputLayer.java:92-115``): each computes a score
and a hand-written gradient w.r.t. pre-output. Here each loss is a pure
function of (labels, pre_output) — gradients come from ``jax.grad``; the
softmax/sigmoid + cross-entropy pairs are fused in logit space for numerical
stability (what the reference achieves by special-casing inside LossMCXENT).

Naming parity with the reference's LossFunction enum: MSE, L2, MAE/L1, XENT,
MCXENT, NEGATIVELOGLIKELIHOOD, HINGE, SQUARED_HINGE, KL_DIVERGENCE, MAPE,
MSLE, POISSON, COSINE_PROXIMITY.

Per-example semantics (matching the ND4J impls):
  L2   = sum_j (y-yhat)^2        MSE  = L2 / n_outputs
  L1   = sum_j |y-yhat|          MAE  = L1 / n_outputs
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .nn import activations as _act

EPS = 1e-7

# A loss fn maps (labels, pre_output, activation_name) -> per-(example,output)
# loss array of the same shape as labels (before any mask/reduction).
LossFn = Callable[[jax.Array, jax.Array, str], jax.Array]

_REGISTRY: Dict[str, LossFn] = {}


def register(*names: str):
    def deco(fn):
        for n in names:
            _REGISTRY[n.lower()] = fn
        return fn
    return deco


def get(name: str) -> LossFn:
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(f"unknown loss {name!r}; known: {sorted(_REGISTRY)}") from None


def names():
    return sorted(_REGISTRY)


def _activate(pre, activation):
    return _act.get(activation)(pre)


@register("mse", "squared_loss")
def mse(labels, pre, activation):
    d = _activate(pre, activation) - labels
    return d * d / labels.shape[-1]


@register("l2")
def l2(labels, pre, activation):
    d = _activate(pre, activation) - labels
    return d * d


@register("mae", "mean_absolute_error")
def mae(labels, pre, activation):
    return jnp.abs(_activate(pre, activation) - labels) / labels.shape[-1]


@register("l1")
def l1(labels, pre, activation):
    return jnp.abs(_activate(pre, activation) - labels)


@register("xent", "binary_xent", "binary_crossentropy", "reconstruction_crossentropy")
def xent(labels, pre, activation):
    """Binary cross-entropy. Fused in logit space when activation is sigmoid."""
    if activation.lower() == "sigmoid":
        # -[y*log sig(x) + (1-y)*log(1-sig(x))] = max(x,0) - x*y + log(1+exp(-|x|))
        return jnp.maximum(pre, 0) - pre * labels + jnp.log1p(jnp.exp(-jnp.abs(pre)))
    p = jnp.clip(_activate(pre, activation), EPS, 1.0 - EPS)
    return -(labels * jnp.log(p) + (1.0 - labels) * jnp.log(1.0 - p))


@register("mcxent", "negativeloglikelihood", "categorical_crossentropy")
def mcxent(labels, pre, activation):
    """Multi-class cross-entropy. Fused log-softmax when activation is softmax."""
    if activation.lower() == "softmax":
        logp = jax.nn.log_softmax(pre, axis=-1)
        return -labels * logp
    p = jnp.clip(_activate(pre, activation), EPS, 1.0 - EPS)
    return -labels * jnp.log(p)


def _in_range(ids, n_classes):
    return (ids >= 0) & (ids < n_classes)


@jax.custom_vjp
def _sparse_softmax_xent(pre, ids):
    return _sparse_softmax_xent_fwd(pre, ids)[0]


def _sparse_softmax_xent_fwd(pre, ids):
    # at least float32, from `pre` as it arrives: the converts fuse into
    # the row reductions, nothing of [.., V] is stored widened
    acc = jnp.promote_types(pre.dtype, jnp.float32)
    x = pre.astype(acc)
    m = jnp.max(pre, axis=-1).astype(acc)       # exact in any dtype
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m[..., None]), axis=-1))
    hit = jax.nn.one_hot(ids, pre.shape[-1], dtype=bool)
    picked = jnp.sum(jnp.where(hit, x, 0.0), axis=-1)
    loss = jnp.where(_in_range(ids, pre.shape[-1]), lse - picked, jnp.nan)
    return loss.astype(pre.dtype), (pre, lse, ids)


def _sparse_softmax_xent_bwd(res, g):
    pre, lse, ids = res
    # a row whose id is out of range has a NaN loss and, as when the
    # gather dropped its cotangent, no gradient
    g = jnp.where(_in_range(ids, pre.shape[-1]), g.astype(lse.dtype), 0.0)
    p = jnp.exp(pre.astype(lse.dtype) - lse[..., None])
    d = (p - jax.nn.one_hot(ids, pre.shape[-1], dtype=p.dtype)) * g[..., None]
    return d.astype(pre.dtype), None


_sparse_softmax_xent.defvjp(_sparse_softmax_xent_fwd, _sparse_softmax_xent_bwd)


@register("sparse_mcxent", "sparse_categorical_crossentropy")
def sparse_mcxent(labels, pre, activation):
    """Integer-class cross-entropy: ``labels`` holds CLASS IDS (shape =
    pre.shape minus the class axis, e.g. [b, t] ids against [b, t, V]
    logits) — the realistic-vocab path for LM training, where a one-hot
    [b, t, V] label tensor at V ≫ 1k would dominate host/device memory.
    Same per-row value as ``mcxent`` on the equivalent one-hot labels.
    Requires the fused softmax head (no dense-probability fallback: a
    clipped-log path would silently lose the log-space stability that is
    the point of this loss).

    The loss is ``logsumexp(pre) − pre[id]``, reduced in float32 (wider
    if ``pre`` is) and returned in ``pre``'s dtype, with a backward of
    its own (``jax.custom_vjp``): ``(exp(pre − lse) − [class == id]) · g``
    from the saved logits and the row ``lse``, which XLA fuses into the
    head's two backward products. Left to autodiff, the transpose of the
    label gather scatters the row cotangents into a dense zero [.., V]
    array that the log-softmax's transpose then re-lays-out, reads and
    sums: three passes over [tokens, V] to carry one value a row. And
    that transpose re-derives the softmax as ``exp`` of a log-softmax
    stored in ``pre``'s dtype: under bf16 every probability is off by up
    to 2 %; here it is ``exp`` of a float32 difference.

    Ids outside [0, V) (e.g. a tokenizer emitting V against a V-sized
    head) yield NaN loss entries instead of XLA's silent gather clamp to
    class V−1 — an off-by-one vocab bug must fail LOUDLY (non-finite
    loss, caught by skip budgets/watchdogs), not train quietly against
    the wrong class."""
    if activation.lower() != "softmax":
        raise ValueError("sparse_mcxent requires activation='softmax' "
                         f"(got {activation!r})")
    return _sparse_softmax_xent(pre, labels.astype(jnp.int32))


@register("hinge")
def hinge(labels, pre, activation):
    # labels in {-1, +1}
    out = _activate(pre, activation)
    return jnp.maximum(0.0, 1.0 - labels * out)


@register("squared_hinge")
def squared_hinge(labels, pre, activation):
    h = hinge(labels, pre, activation)
    return h * h


@register("kl_divergence", "kld")
def kld(labels, pre, activation):
    p = jnp.clip(_activate(pre, activation), EPS, 1.0 - EPS)
    y = jnp.clip(labels, EPS, 1.0)
    return y * (jnp.log(y) - jnp.log(p))


@register("mape", "mean_absolute_percentage_error")
def mape(labels, pre, activation):
    out = _activate(pre, activation)
    return 100.0 * jnp.abs((labels - out) / jnp.where(jnp.abs(labels) < EPS, EPS, labels)) / labels.shape[-1]


@register("msle", "mean_squared_logarithmic_error")
def msle(labels, pre, activation):
    out = _activate(pre, activation)
    d = jnp.log1p(jnp.maximum(out, -1 + EPS)) - jnp.log1p(jnp.maximum(labels, -1 + EPS))
    return d * d / labels.shape[-1]


@register("poisson")
def poisson(labels, pre, activation):
    out = jnp.maximum(_activate(pre, activation), EPS)
    return out - labels * jnp.log(out)


@register("cosine_proximity")
def cosine_proximity(labels, pre, activation):
    out = _activate(pre, activation)
    ln = jnp.linalg.norm(labels, axis=-1, keepdims=True)
    on = jnp.linalg.norm(out, axis=-1, keepdims=True)
    cos = jnp.sum(labels * out, axis=-1, keepdims=True) / jnp.maximum(ln * on, EPS)
    # Broadcast so the per-element array keeps labels' shape; sum over features
    # then yields n_out * (-cos)/n_out = -cos per example.
    return -cos * jnp.ones_like(labels) / labels.shape[-1]


def score_array(loss_name: str, labels, pre_output, activation: str,
                mask: Optional[jax.Array] = None) -> jax.Array:
    """Per-example loss (summed over output features), mask applied.

    mask may be None, shape [batch], or broadcastable to labels' shape —
    matching the reference's per-output and per-timestep mask handling.
    """
    per_elem = get(loss_name)(labels, pre_output, activation)
    if mask is not None:
        m = mask
        while m.ndim < per_elem.ndim:
            m = m[..., None]
        per_elem = per_elem * m
    # sum over all non-batch axes
    axes = tuple(range(1, per_elem.ndim))
    return jnp.sum(per_elem, axis=axes) if axes else per_elem


def is_sparse(loss_name: str) -> bool:
    """True for losses whose labels are CLASS IDS (no class axis) rather
    than per-output arrays — changes the mask-ndim contract below."""
    return loss_name.lower() in ("sparse_mcxent",
                                 "sparse_categorical_crossentropy")


def masked_denominator(mask: Optional[jax.Array], labels,
                       batch_size: int, *, sparse: bool = False) -> jax.Array:
    """The averaging denominator under the explicit mask-kind contract
    (single source of truth — used by both :func:`score` and the network
    runtime's loss):
      - mask is None — the batch size.
      - mask.ndim <  labels.ndim — a per-row mask ([b] or [b,t]); each entry
        covers one example/timestep, so the denominator is ``sum(mask)``.
      - mask.ndim == labels.ndim — a per-output mask; a row counts as active
        if ANY of its outputs is unmasked, so the denominator is
        ``sum(any(mask, axis=-1))``.
    ``sparse=True`` (id-labeled losses — :func:`is_sparse`) declares that
    labels carry NO class axis, so an equal-ndim mask is per-row there,
    exactly like its dense one-hot equivalent — declared by the caller
    from the loss identity, never sniffed from the label dtype (a dense
    loss fed integer-typed labels must keep the per-output contract)."""
    if mask is None:
        return jnp.float32(batch_size)
    if mask.ndim == labels.ndim and not sparse:
        row_active = jnp.max(mask, axis=-1)    # per-output mask
        return jnp.maximum(jnp.sum(row_active), 1.0)
    return jnp.maximum(jnp.sum(mask), 1.0)     # per-row (example/timestep)


def score(loss_name: str, labels, pre_output, activation: str,
          mask: Optional[jax.Array] = None, average: bool = True) -> jax.Array:
    """Scalar loss. With a mask, averaging divides by the active row count
    (parity with reference masked-score semantics in BaseOutputLayer);
    see :func:`masked_denominator` for the mask-kind contract.
    """
    arr = score_array(loss_name, labels, pre_output, activation, mask)
    total = jnp.sum(arr)
    if not average:
        return total
    return total / masked_denominator(mask, labels, labels.shape[0],
                                      sparse=is_sparse(loss_name))
