"""Recurrent layer configs: GravesLSTM, GravesBidirectionalLSTM.

Parity: reference ``nn/conf/layers/GravesLSTM.java`` (forgetGateBiasInit
default 1.0, ``:115``), ``GravesBidirectionalLSTM.java``, runtime
``nn/layers/recurrent/LSTMHelpers.java`` (hand-written per-timestep fwd loop
``:146`` / bwd loop ``:287``) and param layout
``nn/params/GravesLSTMParamInitializer.java:85-86`` (W: [nIn, 4nL],
RW: [nL, 4nL+3] — recurrent weights with 3 peephole columns appended).

TPU-native design:
  - the time loop is ``lax.scan`` (compiled once, no per-step dispatch);
    gates for all 4 blocks computed as ONE [.., 4n] matmul per step (MXU);
    the input projection for ALL timesteps is hoisted out of the scan into a
    single batched matmul — the big win over the reference's per-step gemms.
  - backprop-through-time is ``jax.grad`` of the scan (no hand-written BPTT).
  - peepholes are a separate "P" [3, n] param (cleaner pytree than the
    reference's RW-appended columns; same degrees of freedom).
  - gate order in the fused 4n axis: [a (block input), i, f, o].
  - masking: timesteps with mask==0 carry state through unchanged and output 0.

Streaming inference (``rnnTimeStep``, reference MultiLayerNetwork.java:2274)
uses ``step()`` with explicit (h, c) state.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ... import dtypes as _dtypes
from ..weights import init_weights
from .inputs import InputType
from .layers import Layer, register_layer
from .preprocessors import CnnToRnnPreProcessor, FeedForwardToRnnPreProcessor


@dataclasses.dataclass
class BaseRecurrentLayer(Layer):
    """Parity: nn/conf/layers/BaseRecurrentLayer.java."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()

    def preprocessor_for(self, input_type: InputType):
        if input_type.kind == "feedforward":
            return FeedForwardToRnnPreProcessor()
        if input_type.kind == "convolutional":
            return CnnToRnnPreProcessor(height=input_type.height,
                                        width=input_type.width,
                                        channels=input_type.channels)
        return None

    def has_params(self) -> bool:
        return True


def _lstm_init(key, n_in, n_out, weight_init, dist, forget_bias, dtype):
    k1 = jax.random.fold_in(key, 1)
    k2 = jax.random.fold_in(key, 2)
    fan_in, fan_out = n_in, n_out
    W = init_weights(k1, (n_in, 4 * n_out), weight_init, fan_in=fan_in,
                     fan_out=fan_out, distribution=dist, dtype=dtype)
    RW = init_weights(k2, (n_out, 4 * n_out), weight_init, fan_in=n_out,
                      fan_out=n_out, distribution=dist, dtype=dtype)
    P = jnp.zeros((3, n_out), dtype)
    # bias layout [a,i,f,o]; forget-gate slice initialized to forget_bias
    # (parity: GravesLSTMParamInitializer biasView forget-gate init).
    b = jnp.zeros((4 * n_out,), dtype).at[2 * n_out:3 * n_out].set(forget_bias)
    return {"W": W, "RW": RW, "P": P, "b": b}


def _lstm_scan(params, x, act, gate_act, h0, c0, mask, policy):
    """Run an LSTM over [b, t, n_in] -> [b, t, n_out], returning final state."""
    n = params["RW"].shape[0]
    cdt = policy.compute_dtype
    W = params["W"].astype(cdt)
    RW = params["RW"].astype(cdt)
    P = params["P"].astype(cdt)
    b = params["b"].astype(cdt)
    xb = x.astype(cdt)

    # hoist the input projection out of the scan: [b,t,4n] in one matmul
    zx = jnp.einsum("bti,ij->btj", xb, W) + b

    def step(carry, inp):
        h, c = carry
        zx_t, m_t = inp
        z = zx_t + h @ RW
        a = act(z[:, :n])
        i = gate_act(z[:, n:2 * n] + c * P[0])
        f = gate_act(z[:, 2 * n:3 * n] + c * P[1])
        c_new = f * c + i * a
        o = gate_act(z[:, 3 * n:] + c_new * P[2])
        h_new = o * act(c_new)
        if m_t is not None:
            m = m_t[:, None].astype(h_new.dtype)
            h_new = m * h_new + (1 - m) * h
            c_new = m * c_new + (1 - m) * c
        return (h_new, c_new), h_new

    zx_t = jnp.swapaxes(zx, 0, 1)          # [t, b, 4n]
    m_seq = None if mask is None else jnp.swapaxes(mask, 0, 1)
    if m_seq is None:
        (h, c), hs = lax.scan(lambda cr, z: step(cr, (z, None)), (h0, c0), zx_t)
    else:
        (h, c), hs = lax.scan(step, (h0, c0), (zx_t, m_seq))
    out = jnp.swapaxes(hs, 0, 1)           # [b, t, n]
    if mask is not None:
        out = out * mask[..., None].astype(out.dtype)
    return out, (h, c)


@register_layer("graves_lstm")
@dataclasses.dataclass
class GravesLSTM(BaseRecurrentLayer):
    """LSTM with peepholes (Graves 2013 formulation), lax.scan over time."""

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    def regularized_params(self):
        # l1/l2 apply to input + recurrent weights, not bias/peepholes
        # (parity: GravesLSTM.getL1ByParam — weights only).
        return ("W", "RW")

    def param_shapes(self, policy=None):
        return {"W": (self.n_in, 4 * self.n_out),
                "RW": (self.n_out, 4 * self.n_out),
                "P": (3, self.n_out),
                "b": (4 * self.n_out,)}

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        return _lstm_init(key, self.n_in, self.n_out,
                          self.weight_init or "XAVIER", self.dist,
                          self.forget_gate_bias_init, policy.param_dtype)

    def _zero_state(self, batch, policy):
        dt = policy.compute_dtype
        return (jnp.zeros((batch, self.n_out), dt),
                jnp.zeros((batch, self.n_out), dt))

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        if state and "h" in state:
            h0, c0 = (state["h"].astype(policy.compute_dtype),
                      state["c"].astype(policy.compute_dtype))
        else:
            h0, c0 = self._zero_state(x.shape[0], policy)
        act = self._act("tanh" if self.activation is None else self.activation)
        gact = self._act(self.gate_activation)
        out, (h, c) = _lstm_scan(params, x, act, gact, h0, c0, mask, policy)
        return out, {"h": h, "c": c}

    def step(self, params, x_t, state, *, policy=None):
        """Single timestep for streaming inference (rnnTimeStep parity)."""
        policy = policy or _dtypes.default_policy()
        out, new_state = self.apply(params, x_t[:, None, :], state=state,
                                    policy=policy)
        return out[:, 0, :], new_state


@register_layer("graves_bidirectional_lstm")
@dataclasses.dataclass
class GravesBidirectionalLSTM(BaseRecurrentLayer):
    """Bidirectional Graves LSTM; forward and backward passes are summed
    (parity: nn/layers/recurrent/GravesBidirectionalLSTM.java — activate
    adds fwd + bwd outputs). Params: F (forward) and B (backward) LSTM trees.
    """

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    def param_shapes(self, policy=None):
        base = {"W": (self.n_in, 4 * self.n_out),
                "RW": (self.n_out, 4 * self.n_out),
                "P": (3, self.n_out),
                "b": (4 * self.n_out,)}
        return {f"F_{k}": v for k, v in base.items()} | {
            f"B_{k}": v for k, v in base.items()}

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        f = _lstm_init(jax.random.fold_in(key, 0), self.n_in, self.n_out,
                       self.weight_init or "XAVIER", self.dist,
                       self.forget_gate_bias_init, policy.param_dtype)
        b = _lstm_init(jax.random.fold_in(key, 1), self.n_in, self.n_out,
                       self.weight_init or "XAVIER", self.dist,
                       self.forget_gate_bias_init, policy.param_dtype)
        return {f"F_{k}": v for k, v in f.items()} | {
            f"B_{k}": v for k, v in b.items()}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        act = self._act("tanh" if self.activation is None else self.activation)
        gact = self._act(self.gate_activation)
        bsz = x.shape[0]
        dt = policy.compute_dtype
        zeros = (jnp.zeros((bsz, self.n_out), dt), jnp.zeros((bsz, self.n_out), dt))
        fp = {k[2:]: v for k, v in params.items() if k.startswith("F_")}
        bp = {k[2:]: v for k, v in params.items() if k.startswith("B_")}
        out_f, _ = _lstm_scan(fp, x, act, gact, *zeros, mask, policy)
        x_rev = jnp.flip(x, axis=1)
        mask_rev = None if mask is None else jnp.flip(mask, axis=1)
        out_b, _ = _lstm_scan(bp, x_rev, act, gact, *zeros, mask_rev, policy)
        out = out_f + jnp.flip(out_b, axis=1)
        return out, state

    def regularized_params(self):
        return ("F_W", "F_RW", "B_W", "B_RW")


@register_layer("last_time_step")
@dataclasses.dataclass
class LastTimeStepLayer(Layer):
    """[b, t, f] → [b, f] at the last unmasked step (parity: the reference's
    ``LastTimeStepVertex`` as a sequential layer; used by Keras import for
    ``return_sequences=False`` recurrent layers)."""

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(input_type.size)

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        if mask is None:
            return x[:, -1, :], state
        t = x.shape[1]
        idx = t - 1 - jnp.argmax(jnp.flip(mask > 0, axis=1), axis=1)
        return x[jnp.arange(x.shape[0]), idx], state


@register_layer("time_distributed_dense")
@dataclasses.dataclass
class TimeDistributedDenseLayer(BaseRecurrentLayer):
    """Dense applied independently at every timestep: [b, t, n_in] →
    [b, t, n_out] (parity: the reference's Keras ``TimeDistributedDense``
    import, ``modelimport/keras/LayerConfiguration.java:43``, which it
    realizes as a DenseLayer in an RnnToFeedForward/FeedForwardToRnn
    sandwich). TPU-native: no reshape sandwich — one batched einsum keeps
    the time axis so XLA sees a single [b*t, n_in]×[n_in, n_out] MXU
    matmul without layout round-trips. Inherits BaseRecurrentLayer's
    input handling (FeedForwardToRnn / CnnToRnn preprocessors)."""

    _trace_scope = "ffn"      # a position-wise dense layer, wherever it sits

    def param_shapes(self, policy=None):
        return {"W": (self.n_in, self.n_out), "b": (self.n_out,)}

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        w = init_weights(key, (self.n_in, self.n_out),
                         self.weight_init or "XAVIER",
                         fan_in=self.n_in, fan_out=self.n_out,
                         distribution=self.dist, dtype=dt)
        b = jnp.full((self.n_out,), float(self.bias_init or 0.0), dt)
        return {"W": w, "b": b}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        x = self._dropout_in(x, train, rng)
        xc, wc = policy.cast_to_compute(x, params["W"])
        z = jnp.einsum("bti,io->bto", xc, wc) + params["b"].astype(xc.dtype)
        return self._act()(z), state
