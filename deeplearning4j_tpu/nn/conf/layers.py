"""Layer configurations + their functional implementations.

Parity target: reference ``nn/conf/layers/`` (19 config classes, each knowing
how to ``instantiate()`` a runtime impl, report its output type, infer nIn,
and pick a preprocessor — ``nn/conf/layers/Layer.java:130-185``) plus the
runtime impls in ``nn/layers/`` (``BaseLayer.java``, ``ConvolutionLayer.java``,
``BatchNormalization.java``, …).

TPU-native design: config and implementation are unified — each config class
IS the pure-functional layer:

    params          = conf.init_params(key, policy)   # pytree
    state           = conf.init_state(policy)         # e.g. BN running stats
    y, new_state    = conf.apply(params, x, state=..., train=..., rng=...)

Backprop is ``jax.grad`` through ``apply`` — there are no hand-written
``backpropGradient`` methods (reference ``BaseLayer.java:143-167`` has no
analog by design). Dropout is applied to the layer *input* during training,
matching reference ``BaseLayer.preOutput`` → ``Dropout.applyDropout``.

Recurrent layers (GravesLSTM, …) live in ``recurrent.py``; pretrain layers
(AutoEncoder, RBM) in ``pretrain.py``. All register into the same serde
registry here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from ... import dtypes as _dtypes
from ...ops import common as _common
from ...ops import convops as _convops
from .. import activations as _activations
from ..weights import Distribution, init_weights
from .inputs import InputType
from .preprocessors import (
    CnnToFeedForwardPreProcessor,
    CnnToRnnPreProcessor,
    FeedForwardToCnnPreProcessor,
    FeedForwardToRnnPreProcessor,
    InputPreProcessor,
    RnnToFeedForwardPreProcessor,
)

# --------------------------------------------------------------------------
# serde registry (polymorphic configs, parity with Jackson subtype registry —
# reference NeuralNetConfiguration.reinitMapperWithSubtypes)
# --------------------------------------------------------------------------

LAYER_REGISTRY: Dict[str, Type["Layer"]] = {}


def register_layer(name: str):
    def deco(cls):
        cls._type_name = name
        LAYER_REGISTRY[name] = cls
        return cls
    return deco


def layer_to_dict(layer: "Layer") -> dict:
    d = {"type": layer._type_name}
    for f in dataclasses.fields(layer):
        v = getattr(layer, f.name)
        if isinstance(v, Distribution):
            v = v.to_dict()
        elif isinstance(v, tuple):
            v = list(v)
        d[f.name] = v
    return d


def layer_from_dict(d: dict) -> "Layer":
    d = dict(d)
    typ = d.pop("type")
    cls = LAYER_REGISTRY[typ]
    field_map = {f.name: f for f in dataclasses.fields(cls)}
    kwargs = {}
    for k, v in d.items():
        if k not in field_map:
            continue
        if k == "dist" and isinstance(v, dict):
            v = Distribution.from_dict(v)
        elif isinstance(v, list):
            v = tuple(v)
        kwargs[k] = v
    return cls(**kwargs)


# --------------------------------------------------------------------------
# base classes
# --------------------------------------------------------------------------

# Sentinel meaning "inherit from the global builder defaults".
INHERIT = None


@dataclasses.dataclass
class Layer:
    """Base layer config. Fields left as None inherit global builder defaults
    (parity: reference Layer.Builder fields overriding NeuralNetConfiguration
    globals at clone time)."""

    name: Optional[str] = None
    activation: Optional[str] = None          # default "sigmoid" via builder
    weight_init: Optional[str] = None         # default "XAVIER" via builder
    bias_init: Optional[float] = None         # default 0.0
    dist: Optional[Distribution] = None
    dropout: Optional[float] = None           # drop probability (0 disables)
    l1: Optional[float] = None
    l2: Optional[float] = None
    learning_rate: Optional[float] = None     # per-layer LR override
    bias_learning_rate: Optional[float] = None

    _type_name = "base"
    # jax.named_scope that LayerVertex.apply puts around this layer's
    # forward (metadata only: a stable name in lowered programs and device
    # traces); None for layers that scope their own parts
    _trace_scope = None

    # ---- shape inference hooks (parity Layer.java:130-185) ----
    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        pass

    def preprocessor_for(self, input_type: InputType) -> Optional[InputPreProcessor]:
        return None

    # ---- params ----
    def has_params(self) -> bool:
        return False

    def init_params(self, key, policy=None) -> Dict[str, jax.Array]:
        return {}

    def init_state(self, policy=None) -> Dict[str, jax.Array]:
        return {}

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        """Static param shapes (for sharding specs / counting)."""
        return {}

    def regularized_params(self) -> Tuple[str, ...]:
        """Params l1/l2 apply to (parity: Layer.getL1ByParam — weights only)."""
        return ("W",)

    # ---- forward ----
    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        raise NotImplementedError

    # ---- misc ----
    def _act(self, name_override=None):
        return _activations.get(name_override or self.activation or "sigmoid")

    def _dropout_in(self, x, train, rng):
        if train and (self.dropout or 0.0) > 0.0 and rng is not None:
            return _common.apply_dropout(rng, x, float(self.dropout), train)
        return x

    def clone(self, **updates) -> "Layer":
        return dataclasses.replace(self, **updates)


@dataclasses.dataclass
class FeedForwardLayer(Layer):
    """Base for layers with [n_in, n_out] dense weights
    (parity: nn/conf/layers/FeedForwardLayer.java)."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    # False leaves the bias out of the parameters and of the product (the
    # heads and projections of models published without one)
    has_bias: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()

    def preprocessor_for(self, input_type: InputType):
        # parity: InputTypeUtil/FeedForwardLayer.getPreProcessorForInputType
        if input_type.kind == "recurrent":
            return RnnToFeedForwardPreProcessor()
        if input_type.kind == "convolutional":
            return CnnToFeedForwardPreProcessor(
                height=input_type.height, width=input_type.width,
                channels=input_type.channels)
        return None

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None):
        shapes = {"W": (self.n_in, self.n_out)}
        if self.has_bias:
            shapes["b"] = (self.n_out,)
        return shapes

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        w = init_weights(key, (self.n_in, self.n_out),
                         self.weight_init or "XAVIER",
                         fan_in=self.n_in, fan_out=self.n_out,
                         distribution=self.dist, dtype=dt)
        if not self.has_bias:
            return {"W": w}
        b = jnp.full((self.n_out,), float(self.bias_init or 0.0), dt)
        return {"W": w, "b": b}

    def pre_output(self, params, x, *, policy=None):
        policy = policy or _dtypes.default_policy()
        xc, wc = policy.cast_to_compute(x, params["W"])
        z = xc @ wc
        return z + params["b"].astype(xc.dtype) if self.has_bias else z

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        x = self._dropout_in(x, train, rng)
        z = self.pre_output(params, x, policy=policy)
        return self._act()(z), state


# --------------------------------------------------------------------------
# concrete feedforward layers
# --------------------------------------------------------------------------


@register_layer("dense")
@dataclasses.dataclass
class DenseLayer(FeedForwardLayer):
    """Fully-connected layer (parity: nn/conf/layers/DenseLayer.java)."""


@dataclasses.dataclass
class BaseOutputLayer(FeedForwardLayer):
    """Output layer with a loss fn (parity: nn/conf/layers/BaseOutputLayer.java,
    runtime nn/layers/BaseOutputLayer.java:92-115 — score via ILossFunction)."""

    loss: str = "negativeloglikelihood"
    _trace_scope = "head"

    def compute_score_array(self, params, x, labels, *, mask=None, policy=None):
        from ... import losses as _losses
        with jax.named_scope("head"):
            pre = self.pre_output(params, x, policy=policy)
        with jax.named_scope("loss"):
            return _losses.score_array(self.loss, labels, pre,
                                       self.activation or "sigmoid", mask)


@register_layer("output")
@dataclasses.dataclass
class OutputLayer(BaseOutputLayer):
    """Standard 2D output layer (parity: nn/conf/layers/OutputLayer.java)."""


@register_layer("rnn_output")
@dataclasses.dataclass
class RnnOutputLayer(BaseOutputLayer):
    """Time-distributed output for [b,t,f] activations
    (parity: nn/conf/layers/RnnOutputLayer.java)."""

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def preprocessor_for(self, input_type: InputType):
        if input_type.kind == "feedforward":
            return FeedForwardToRnnPreProcessor()
        return None

    # the head's product in float32 at full precision whatever the policy
    # (EvaByte's ``fp32_logits``)
    float32_logits: bool = False

    def pre_output(self, params, x, *, policy=None):
        # x: [b, t, n_in] — einsum keeps the time axis, one big MXU matmul
        policy = policy or _dtypes.default_policy()
        if self.float32_logits:
            xc, wc = x.astype(jnp.float32), params["W"].astype(jnp.float32)
            z = jnp.einsum("bti,io->bto", xc, wc,
                           precision=jax.lax.Precision.HIGHEST)
        else:
            xc, wc = policy.cast_to_compute(x, params["W"])
            z = jnp.einsum("bti,io->bto", xc, wc)
        return z + params["b"].astype(xc.dtype) if self.has_bias else z


@register_layer("loss")
@dataclasses.dataclass
class LossLayer(Layer):
    """Parameter-free loss layer (parity: nn/conf/layers/LossLayer.java)."""

    loss: str = "mse"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        return self._act("identity" if self.activation is None else self.activation)(x), state

    def pre_output(self, params, x, *, policy=None):
        return x

    def compute_score_array(self, params, x, labels, *, mask=None, policy=None):
        from ... import losses as _losses
        return _losses.score_array(self.loss, labels, x,
                                   self.activation or "identity", mask)


@register_layer("activation")
@dataclasses.dataclass
class ActivationLayer(Layer):
    """Activation-only layer (parity: nn/conf/layers/ActivationLayer.java)."""

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        x = self._dropout_in(x, train, rng)
        return self._act()(x), state


@register_layer("dropout")
@dataclasses.dataclass
class DropoutLayer(Layer):
    """Standalone dropout layer."""

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        return self._dropout_in(x, train, rng), state


@register_layer("embedding")
@dataclasses.dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Embedding lookup: int indices [b] or [b,1] -> vectors [b, n_out]
    (parity: nn/conf/layers/EmbeddingLayer.java — W lookup + bias + activation;
    on TPU this lowers to a one-hot matmul or dynamic-gather, both MXU/VMEM
    friendly for the batched case)."""

    has_bias: bool = True

    def param_shapes(self, policy=None):
        shapes = {"W": (self.n_in, self.n_out)}
        if self.has_bias:
            shapes["b"] = (self.n_out,)
        return shapes

    def init_params(self, key, policy=None):
        params = super().init_params(key, policy)
        if not self.has_bias:
            params.pop("b", None)
        return params

    def pre_output(self, params, x, *, policy=None):
        policy = policy or _dtypes.default_policy()
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        emb = jnp.take(params["W"], idx, axis=0).astype(policy.compute_dtype)
        if self.has_bias:
            emb = emb + params["b"].astype(emb.dtype)
        return emb

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        z = self.pre_output(params, x, policy=policy)
        return self._act("identity" if self.activation is None else self.activation)(z), state


@register_layer("embedding_sequence")
@dataclasses.dataclass
class EmbeddingSequenceLayer(FeedForwardLayer):
    """Token-id sequence embedding: int indices [b, t] (or [b, t, 1]) →
    [b, t, n_out] vectors (parity: nn/conf/layers/EmbeddingSequenceLayer.java).

    The realistic-vocab LM input path: at V ≫ 1k a one-hot [b, t, V] input
    cannot survive host memory, so the network takes raw ids and this
    layer gathers rows of W — on TPU a dynamic-gather, VMEM-friendly and
    free of the one-hot matmul's V-wide FLOPs. ``n_in`` is the VOCAB size
    and must be given explicitly (the [b, t] id input carries no feature
    dim to infer it from). Ids must stay integer-typed end to end — never
    cast through a compute dtype (bf16 rounds ids past 256)."""

    has_bias: bool = False
    _trace_scope = "embed"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None:
            raise ValueError(
                "EmbeddingSequenceLayer needs n_in=<vocab size> set "
                "explicitly — the [b, t] id input has no feature dim to "
                "infer it from")

    def preprocessor_for(self, input_type: InputType):
        return None     # ids are consumed raw — never reshaped/cast

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        emb = jnp.take(params["W"], idx, axis=0).astype(policy.compute_dtype)
        if self.has_bias:
            emb = emb + params["b"].astype(emb.dtype)
        out = self._act("identity" if self.activation is None
                        else self.activation)(emb)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, state


# --------------------------------------------------------------------------
# convolutional family
# --------------------------------------------------------------------------


@register_layer("convolution")
@dataclasses.dataclass
class ConvolutionLayer(Layer):
    """2D convolution, NHWC/HWIO (parity: nn/conf/layers/ConvolutionLayer.java;
    runtime nn/layers/convolution/ConvolutionLayer.java + the cuDNN helper —
    here a single XLA conv_general_dilated HLO, MXU-tiled)."""

    n_in: Optional[int] = None      # input channels (inferred)
    n_out: Optional[int] = None     # filters
    kernel_size: Tuple[int, int] = (5, 5)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    border_mode: Optional[str] = None   # None=explicit pad | "same" | "valid"
    groups: int = 1
    has_bias: bool = True           # False for conv+BN pairs (bias is
                                    # redundant before BN's shift)

    def __post_init__(self):
        # ergonomic: padding="same"/"valid" routes to border_mode
        if isinstance(self.padding, str):
            self.border_mode = self.padding
            self.padding = (0, 0)

    def _pad_arg(self):
        if self.border_mode:
            return self.border_mode
        return tuple(self.padding)

    def output_type(self, input_type: InputType) -> InputType:
        h, w = input_type.height, input_type.width
        if self.border_mode == "same":
            oh, ow = -(-h // self.stride[0]), -(-w // self.stride[1])
        else:
            ph, pw = (0, 0) if self.border_mode == "valid" else self.padding
            oh = _convops.conv_output_size(h, self.kernel_size[0], self.stride[0], ph, self.dilation[0])
            ow = _convops.conv_output_size(w, self.kernel_size[1], self.stride[1], pw, self.dilation[1])
        return InputType.convolutional(oh, ow, self.n_out)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.channels

    def preprocessor_for(self, input_type: InputType):
        if input_type.kind == "convolutional_flat":
            return FeedForwardToCnnPreProcessor(
                height=input_type.height, width=input_type.width,
                channels=input_type.channels)
        return None

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None):
        kh, kw = self.kernel_size
        shapes = {"W": (kh, kw, self.n_in // self.groups, self.n_out)}
        if self.has_bias:
            shapes["b"] = (self.n_out,)
        return shapes

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        kh, kw = self.kernel_size
        fan_in = self.n_in * kh * kw
        fan_out = self.n_out * kh * kw
        w = init_weights(key, (kh, kw, self.n_in // self.groups, self.n_out),
                         self.weight_init or "XAVIER", fan_in=fan_in,
                         fan_out=fan_out, distribution=self.dist, dtype=dt)
        params = {"W": w}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), float(self.bias_init or 0.0),
                                   dt)
        return params

    def pre_output(self, params, x, *, policy=None):
        policy = policy or _dtypes.default_policy()
        xc, wc = policy.cast_to_compute(x, params["W"])
        z = _convops.conv2d(xc, wc, self.stride, self._pad_arg(), self.dilation,
                            self.groups)
        if self.has_bias:
            z = z + params["b"].astype(z.dtype)
        return z

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        x = self._dropout_in(x, train, rng)
        z = self.pre_output(params, x, policy=policy)
        return self._act()(z), state


@register_layer("subsampling")
@dataclasses.dataclass
class SubsamplingLayer(Layer):
    """Pooling (parity: nn/conf/layers/SubsamplingLayer.java,
    PoolingType MAX/AVG/SUM/PNORM; runtime SubsamplingLayer + cuDNN helper —
    here lax.reduce_window)."""

    pooling_type: str = "max"
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    pnorm: int = 2
    border_mode: Optional[str] = None

    def output_type(self, input_type: InputType) -> InputType:
        h, w = input_type.height, input_type.width
        if self.border_mode == "same":
            oh, ow = -(-h // self.stride[0]), -(-w // self.stride[1])
        else:
            ph, pw = (0, 0) if self.border_mode == "valid" else self.padding
            oh = _convops.conv_output_size(h, self.kernel_size[0], self.stride[0], ph)
            ow = _convops.conv_output_size(w, self.kernel_size[1], self.stride[1], pw)
        return InputType.convolutional(oh, ow, input_type.channels)

    def preprocessor_for(self, input_type: InputType):
        if input_type.kind == "convolutional_flat":
            return FeedForwardToCnnPreProcessor(
                height=input_type.height, width=input_type.width,
                channels=input_type.channels)
        return None

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        pad = self.border_mode if self.border_mode else tuple(self.padding)
        return _convops.pool2d(x, self.pooling_type, self.kernel_size,
                               self.stride, pad, self.pnorm), state


@register_layer("space_to_depth")
@dataclasses.dataclass
class SpaceToDepthLayer(Layer):
    """NHWC space-to-depth: [b, h, w, c] → [b, h/bs, w/bs, bs²·c], channel
    order (di, dj, c) over the bs×bs block.

    Parity: the reference line later ships ``SpaceToDepthLayer``; here it
    doubles as the TPU stem lowering — a 7×7/2 conv on 3 input channels
    (3-deep contracting dim starves the 128-lane MXU) becomes an equivalent
    4×4/1 conv on 12 channels after 2×2 space-to-depth
    (``models.resnet.fold_stem_7x7_to_s2d`` maps the weights exactly).
    """

    block_size: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        bs = self.block_size
        if input_type.height % bs or input_type.width % bs:
            raise ValueError(
                f"space_to_depth block {bs} does not divide "
                f"{input_type.height}x{input_type.width}")
        return InputType.convolutional(
            input_type.height // bs, input_type.width // bs,
            input_type.channels * bs * bs)

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        bs = self.block_size
        b, h, w, c = x.shape
        x = x.reshape(b, h // bs, bs, w // bs, bs, c)
        x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
        return x.reshape(b, h // bs, w // bs, bs * bs * c), state


@register_layer("batch_norm")
@dataclasses.dataclass
class BatchNormalization(Layer):
    """Batch normalization over the channel/feature axis.

    Parity: nn/conf/layers/BatchNormalization.java:28-33 (decay=0.9, eps=1e-5,
    gamma=1, beta=0, lockGammaBeta) and runtime
    nn/layers/normalization/BatchNormalization.java (+ cuDNN helper).
    Works on [b,f] and NHWC [b,h,w,c]; stats reduce over all non-channel axes.
    """

    n_out: Optional[int] = None          # feature/channel count (inferred)
    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0
    beta: float = 0.0
    lock_gamma_beta: bool = False

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_out is None or override:
            if input_type.kind == "convolutional":
                self.n_out = input_type.channels
            else:
                self.n_out = input_type.flat_size()

    def preprocessor_for(self, input_type: InputType):
        if input_type.kind == "convolutional_flat":
            return FeedForwardToCnnPreProcessor(
                height=input_type.height, width=input_type.width,
                channels=input_type.channels)
        return None

    def has_params(self) -> bool:
        return not self.lock_gamma_beta

    def regularized_params(self) -> Tuple[str, ...]:
        return ()

    def param_shapes(self, policy=None):
        if self.lock_gamma_beta:
            return {}
        return {"gamma": (self.n_out,), "beta": (self.n_out,)}

    def init_params(self, key, policy=None):
        if self.lock_gamma_beta:
            return {}
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        return {"gamma": jnp.full((self.n_out,), self.gamma, dt),
                "beta": jnp.full((self.n_out,), self.beta, dt)}

    def init_state(self, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        return {"mean": jnp.zeros((self.n_out,), dt),
                "var": jnp.ones((self.n_out,), dt)}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        from ...ops import batchnorm as _bn
        if not state:
            state = self.init_state(policy)
        # statistics accumulate in the state dtype (f32 under mixed policy)
        # but the normalize+scale math stays in the activation dtype so
        # bf16 activations don't get promoted to f32 between conv blocks
        stat_dtype = state["mean"].dtype
        if self.lock_gamma_beta:
            g = jnp.full((x.shape[-1],), self.gamma, stat_dtype)
            b = jnp.full((x.shape[-1],), self.beta, stat_dtype)
        else:
            g = params["gamma"].astype(stat_dtype)
            b = params["beta"].astype(stat_dtype)
        if train:
            # fused two-pass BN with a hand-written VJP (ops/batchnorm.py) —
            # the autodiff backward of the naive form costs several extra HBM
            # passes over the activation (the dominant ResNet train cost)
            y, mean, var = _bn.batch_norm_train(x, g, b, self.eps)
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
            return y, new_state
        return _bn.batch_norm_inference(
            x, g, b, state["mean"], state["var"], self.eps), state


@register_layer("layer_norm")
@dataclasses.dataclass
class LayerNormalization(Layer):
    """Layer normalization over the feature (last) axis.

    No reference analog (the reference predates transformers); included as
    the normalization the attention stack needs (``SelfAttentionLayer`` /
    ``models/transformer.py``). Stateless — per-example statistics, no
    running averages — and shape-preserving on [b, f], [b, t, f], NHWC.
    """

    _trace_scope = "ln"

    n_out: Optional[int] = None          # feature count (inferred)
    eps: float = 1e-5

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_out is None or override:
            if input_type.kind == "convolutional":
                self.n_out = input_type.channels
            else:
                self.n_out = (input_type.size
                              if input_type.kind == "recurrent"
                              else input_type.flat_size())

    def has_params(self) -> bool:
        return True

    def regularized_params(self) -> Tuple[str, ...]:
        return ()

    def param_shapes(self, policy=None):
        return {"gamma": (self.n_out,), "beta": (self.n_out,)}

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        dt = policy.param_dtype
        return {"gamma": jnp.ones((self.n_out,), dt),
                "beta": jnp.zeros((self.n_out,), dt)}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        # normalize in at least f32 (bf16 variance over wide features
        # underflows; f64 stays f64 for gradient checking), return in the
        # activation dtype
        cdt = jnp.promote_types(x.dtype, jnp.float32)
        xf = x.astype(cdt)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["gamma"].astype(cdt) + params["beta"].astype(cdt)
        return y.astype(x.dtype), state


@register_layer("rms_norm")
@dataclasses.dataclass
class RMSNorm(Layer):
    """Root-mean-square normalization over the feature (last) axis:
    ``x / sqrt(mean(x^2) + eps) * gamma`` (Zhang & Sennrich 2019; the norm
    of ``nemotron_h`` and most of today's decoder blocks). No mean is
    subtracted and there is no offset. Statistics in at least float32.
    ``unit_offset`` stores the gain less one (``gamma`` 0 is the identity,
    EvaByte's ``norm_add_unit_offset``), so that a stored bfloat16 gain
    keeps its small departures from 1."""

    _trace_scope = "ln"

    n_out: Optional[int] = None          # feature count (inferred)
    eps: float = 1e-5
    unit_offset: bool = False

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_out is None or override:
            self.n_out = (input_type.size if input_type.kind == "recurrent"
                          else input_type.flat_size())

    def has_params(self) -> bool:
        return True

    def regularized_params(self) -> Tuple[str, ...]:
        return ()

    def param_shapes(self, policy=None):
        return {"gamma": (self.n_out,)}

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        make = jnp.zeros if self.unit_offset else jnp.ones
        return {"gamma": make((self.n_out,), policy.param_dtype)}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        gamma = params["gamma"]
        if self.unit_offset:
            gamma = gamma.astype(jnp.promote_types(gamma.dtype,
                                                   jnp.float32)) + 1.0
        return rms_normalize(x, gamma, self.eps), state


def rms_normalize(x, gamma, eps: float):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, computed
    in at least float32 and returned in ``x``'s type."""
    cdt = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(cdt)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    return (y * gamma.astype(cdt)).astype(x.dtype)


def gated_ffn(x, w_gate, w_up, w_down):
    """``(silu(x @ w_gate) * (x @ w_up)) @ w_down`` over the last axis, the
    matrices cast to ``x``'s type: the gated feed-forward of today's
    decoder blocks (SwiGLU), a dense layer's and a shared expert's alike.
    Scopes ``ffn.gate_up`` and ``ffn.down``."""
    with jax.named_scope("ffn.gate_up"):
        h = (jax.nn.silu(x @ w_gate.astype(x.dtype))
             * (x @ w_up.astype(x.dtype)))
    with jax.named_scope("ffn.down"):
        return h @ w_down.astype(x.dtype)


@register_layer("gated_ffn")
@dataclasses.dataclass
class GatedFFNLayer(Layer):
    """Position-wise gated feed-forward, [b, t, n_in] -> [b, t, n_out]:
    ``(silu(x @ W_gate) * (x @ W_up)) @ W_down`` with ``d_hidden`` columns
    in the middle and no bias (:func:`gated_ffn`)."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None          # defaults to n_in
    d_hidden: int = 256

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out or self.n_in,
                                   input_type.timesteps)

    def set_n_in(self, input_type: InputType, override: bool = False) -> None:
        if self.n_in is None or override:
            self.n_in = input_type.flat_size()
        if self.n_out is None:
            self.n_out = self.n_in

    def has_params(self) -> bool:
        return True

    def param_shapes(self, policy=None):
        return {"W_gate": (self.n_in, self.d_hidden),
                "W_up": (self.n_in, self.d_hidden),
                "W_down": (self.d_hidden, self.n_out)}

    def regularized_params(self) -> Tuple[str, ...]:
        return ("W_gate", "W_up", "W_down")

    def init_params(self, key, policy=None):
        policy = policy or _dtypes.default_policy()
        return {name: init_weights(
            jax.random.fold_in(key, n), shape, self.weight_init or "XAVIER",
            fan_in=shape[0], fan_out=shape[1], distribution=self.dist,
            dtype=policy.param_dtype)
            for n, (name, shape) in enumerate(
                sorted(self.param_shapes().items()))}

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        policy = policy or _dtypes.default_policy()
        xc = policy.cast_to_compute(self._dropout_in(x, train, rng))
        out = gated_ffn(xc, params["W_gate"], params["W_up"],
                        params["W_down"])
        return self._act(self.activation or "identity")(out), state


@register_layer("lrn")
@dataclasses.dataclass
class LocalResponseNormalization(Layer):
    """Cross-channel LRN (parity: nn/conf/layers/LocalResponseNormalization.java
    defaults n=5, k=2, alpha=1e-4, beta=0.75)."""

    n: int = 5
    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        return _convops.lrn(x, self.k, self.n, self.alpha, self.beta), state


@register_layer("global_pooling")
@dataclasses.dataclass
class GlobalPoolingLayer(Layer):
    """Global pooling over spatial or time axes (max/avg/sum/pnorm),
    mask-aware for variable-length sequences."""

    pooling_type: str = "avg"
    pnorm: int = 2

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "convolutional":
            return InputType.feed_forward(input_type.channels)
        return InputType.feed_forward(input_type.size)

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None, policy=None):
        if x.ndim == 4:      # NHWC -> [b, c]
            axes = (1, 2)
        elif x.ndim == 3:    # [b, t, f] -> [b, f]
            axes = (1,)
        else:
            return x, state
        kind = self.pooling_type.lower()
        if x.ndim == 3 and mask is not None:
            m = mask[..., None].astype(x.dtype)
            if kind == "avg":
                s = jnp.sum(x * m, axis=axes)
                return s / jnp.maximum(jnp.sum(m, axis=axes), 1.0), state
            if kind == "max":
                neg = jnp.where(m > 0, x, -jnp.inf)
                return jnp.max(neg, axis=axes), state
            if kind == "sum":
                return jnp.sum(x * m, axis=axes), state
        if kind == "avg":
            return jnp.mean(x, axis=axes), state
        if kind == "max":
            return jnp.max(x, axis=axes), state
        if kind == "sum":
            return jnp.sum(x, axis=axes), state
        if kind == "pnorm":
            p = float(self.pnorm)
            if x.ndim == 3 and mask is not None:
                # zero masked timesteps so they don't contribute to the p-norm
                # (parity: reference MaskedReductionUtil PNORM handling)
                x = x * mask[..., None].astype(x.dtype)
            return jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1.0 / p), state
        raise ValueError(f"unknown pooling type {kind!r}")
