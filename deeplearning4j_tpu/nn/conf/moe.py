"""Mixture-of-experts FFN as a config-DSL layer.

No reference analog (SURVEY §2.9: EP = NO) — the expert-parallelism
north-star surfaced in the same builder DSL as every other layer, so MoE
transformers are ordinary ComputationGraphs (serde, listeners, remat,
SP/PP trainers all apply). The math is ``parallel/expert.py``'s
dense-dispatch formulation (every expert computes every token, top-k
gates zero the rest — static shapes, no scatter, compiler-friendly) with
the time axis preserved, so under a mesh the expert-stacked einsums
partition over ``ep`` (see ``parallel.expert.expert_param_specs`` /
``ExpertParallelGraphTrainer``) and
the time axis can simultaneously shard over ``seq``.

The Shazeer-style load-balancing auxiliary loss is returned through the
layer's state under ``"aux_loss"`` — both network runtimes add any such
entries to the training objective (scaled by ``aux_weight`` here, so the
trainer just sums).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ... import dtypes as _dtypes
from .inputs import InputType
from .layers import Layer, register_layer
from ..weights import init_weights


@register_layer("moe")
@dataclasses.dataclass
class MoELayer(Layer):
    """Top-k routed mixture-of-experts FFN: [b, t, f] → [b, t, f] (or
    [b, f] → [b, f]).

    Params: ``router`` [n_in, E], expert-stacked ``w1`` [E, n_in,
    d_hidden], ``b1`` [E, d_hidden], ``w2`` [E, d_hidden, n_out], ``b2``
    [E, n_out] — the leading E dim is what expert parallelism shards.
    """

    n_in: Optional[int] = None
    n_out: Optional[int] = None          # defaults to n_in
    d_hidden: int = 256
    n_experts: int = 8
    top_k: int = 2
    aux_weight: float = 0.01
    _trace_scope = "ffn"

    def output_type(self, input_type: InputType) -> InputType:
        n = self.n_out or self.n_in
        if input_type.kind == "recurrent":
            return InputType.recurrent(n, input_type.timesteps)
        return InputType.feed_forward(n)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in
        if self.top_k > self.n_experts:
            raise ValueError(f"top_k={self.top_k} > "
                             f"n_experts={self.n_experts}")

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        e, h = self.n_experts, self.d_hidden
        return {"router": (self.n_in, e),
                "w1": (e, self.n_in, h), "b1": (e, h),
                "w2": (e, h, self.n_out), "b2": (e, self.n_out)}

    def regularized_params(self) -> Tuple[str, ...]:
        return ("w1", "w2")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        e, h = self.n_experts, self.d_hidden
        kr, k1, k2 = jax.random.split(key, 3)
        wi = self.weight_init or "XAVIER"

        def stack(k, shape, fan_in, fan_out):
            ks = jax.random.split(k, e)
            return jnp.stack([
                init_weights(ks[i], shape, wi, fan_in=fan_in,
                             fan_out=fan_out, distribution=self.dist,
                             dtype=dt) for i in range(e)])

        return {
            "router": init_weights(kr, (self.n_in, e), wi,
                                   fan_in=self.n_in, fan_out=e, dtype=dt),
            "w1": stack(k1, (self.n_in, h), self.n_in, h),
            "b1": jnp.zeros((e, h), dt),
            "w2": stack(k2, (h, self.n_out), h, self.n_out),
            "b2": jnp.zeros((e, self.n_out), dt),
        }

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]                       # [b, 1, f]
        xc, router = policy.cast_to_compute(x, params["router"])
        e = self.n_experts
        logits = jnp.einsum("btd,de->bte", xc, router)
        # routing numerics at >= f32 (and f64 under an x64 policy, so the
        # gradient-check suite sees the true derivative)
        gate_dt = jnp.promote_types(logits.dtype, jnp.float32)
        gates = jax.nn.softmax(logits.astype(gate_dt), axis=-1)
        if self.top_k < e:
            # lax.top_k breaks ties deterministically (lowest index), so
            # EXACTLY top_k experts fire even for uniform gates
            _, idx = jax.lax.top_k(gates, self.top_k)       # [b, t, k]
            keep = jax.nn.one_hot(idx, e).sum(axis=2) > 0   # [b, t, E]
            masked = jnp.where(keep, gates, 0.0)
            weights = masked / jnp.maximum(
                masked.sum(-1, keepdims=True), 1e-9)
        else:
            keep = jnp.ones_like(gates, bool)
            weights = gates
        w1 = params["w1"].astype(xc.dtype)
        w2 = params["w2"].astype(xc.dtype)
        # dense dispatch, time axis preserved: [E, b, t, h] hidden
        h = jax.nn.relu(jnp.einsum("btd,edh->ebth", xc, w1)
                        + params["b1"].astype(xc.dtype)[:, None, None, :])
        y_e = (jnp.einsum("ebth,ehd->ebtd", h, w2)
               + params["b2"].astype(xc.dtype)[:, None, None, :])
        y = jnp.einsum("bte,ebtd->btd", weights.astype(xc.dtype), y_e)
        # Shazeer-style load-balancing aux: E * sum_e mean_gate * mean_keep
        if mask is not None:
            m = mask.astype(gate_dt)[:, :, None]
            denom = jnp.maximum(jnp.sum(m), 1.0)
            gate_frac = jnp.sum(gates * m, axis=(0, 1)) / denom
            keep_frac = jnp.sum(keep.astype(gate_dt) * m,
                                axis=(0, 1)) / denom
            y = y * m.astype(y.dtype)
        else:
            gate_frac = jnp.mean(gates, axis=(0, 1))
            keep_frac = jnp.mean(keep.astype(gate_dt), axis=(0, 1))
        aux = e * jnp.sum(gate_frac * keep_frac)
        if squeeze:
            y = y[:, 0, :]
        out_state = dict(state or {})
        out_state["aux_loss"] = self.aux_weight * aux
        return y, out_state
