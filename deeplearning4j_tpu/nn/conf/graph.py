"""ComputationGraph configuration: DAG of named vertices.

Parity: reference ``nn/conf/ComputationGraphConfiguration.java``
(``GraphBuilder.addLayer/addVertex/addInputs/setOutputs``), graph vertex
configs in ``nn/conf/graph/`` (``MergeVertex``, ``ElementWiseVertex``,
``SubsetVertex``, ``StackVertex``, ``UnstackVertex``, ``L2Vertex``,
``ScaleVertex``, ``PreprocessorVertex``, ``rnn/LastTimeStepVertex``,
``rnn/DuplicateToTimeSeriesVertex``) and the topological sort at
``nn/graph/ComputationGraph.java:810``.

TPU-native design: vertices are pure functions over activations; the runtime
(``nn/graph_runtime.py``) traces the whole topo-ordered DAG into ONE jitted
program, so "vertex dispatch" has zero runtime cost — XLA fuses across vertex
boundaries. Mask propagation follows the activations (each vertex maps input
masks to an output mask).
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp

from .inputs import InputType
from .layers import Layer, layer_from_dict, layer_to_dict
from .preprocessors import (InputPreProcessor, call_preprocessor,
                            preprocessor_from_dict)
from .training import TrainingConfig

# ensure recurrent layer types are registered for serde
from . import recurrent as _recurrent  # noqa: F401

# --------------------------------------------------------------------------
# vertex registry (polymorphic serde, same pattern as layers)
# --------------------------------------------------------------------------

VERTEX_REGISTRY: Dict[str, Type["GraphVertex"]] = {}


def register_vertex(name: str):
    def deco(cls):
        cls._type_name = name
        VERTEX_REGISTRY[name] = cls
        return cls
    return deco


def vertex_to_dict(v: "GraphVertex") -> dict:
    d = {"type": v._type_name}
    for f in dataclasses.fields(v):
        val = getattr(v, f.name)
        if isinstance(val, Layer):
            val = {"__layer__": layer_to_dict(val)}
        elif isinstance(val, InputPreProcessor):
            val = {"__preprocessor__": val.to_dict()}
        elif isinstance(val, tuple):
            val = list(val)
        d[f.name] = val
    return d


def vertex_from_dict(d: dict) -> "GraphVertex":
    d = dict(d)
    typ = d.pop("type")
    cls = VERTEX_REGISTRY[typ]
    field_map = {f.name: f for f in dataclasses.fields(cls)}
    kwargs = {}
    for k, v in d.items():
        if k not in field_map:
            continue
        if isinstance(v, dict) and "__layer__" in v:
            v = layer_from_dict(v["__layer__"])
        elif isinstance(v, dict) and "__preprocessor__" in v:
            v = preprocessor_from_dict(v["__preprocessor__"])
        elif isinstance(v, list):
            v = tuple(v)
        kwargs[k] = v
    return cls(**kwargs)


# --------------------------------------------------------------------------
# vertex base + impls
# --------------------------------------------------------------------------


@dataclasses.dataclass
class GraphVertex:
    """A pure function over one or more input activations."""

    _type_name = "base"

    # ---- params (layer vertices override) ----
    def has_params(self) -> bool:
        return False

    def init_params(self, key, policy=None) -> Dict[str, Any]:
        return {}

    def init_state(self, policy=None) -> Dict[str, Any]:
        return {}

    def param_shapes(self, policy=None) -> Dict[str, Tuple[int, ...]]:
        return {}

    # ---- shape inference ----
    def output_type(self, input_types: List[InputType]) -> InputType:
        raise NotImplementedError

    def set_n_in(self, input_types: List[InputType], override: bool = False) -> None:
        pass

    # ---- forward: (params, [x...], state, train, rng, [mask...]) ----
    def apply(self, params, xs: List[jax.Array], *, state=None, train=False,
              rng=None, masks=None, policy=None, minibatch=None):
        raise NotImplementedError

    def output_mask(self, masks: Optional[List[Optional[jax.Array]]],
                    minibatch: Optional[int] = None):
        """Propagate masks (default: first non-None input mask).
        `minibatch` is the batch size of this vertex's input activations,
        for mask-reshaping vertices."""
        if not masks:
            return None
        for m in masks:
            if m is not None:
                return m
        return None

    def output_minibatch(self, in_mbs: List[int]) -> int:
        """The EXAMPLE count of this vertex's output. Time-flattened
        activations make shape[0] = b·t, so the runtime tracks the true
        example count along the DAG; batch-axis vertices (Stack/Unstack)
        override."""
        return in_mbs[0]


@register_vertex("layer")
@dataclasses.dataclass
class LayerVertex(GraphVertex):
    """Wraps a Layer config (+ optional preprocessor) as a single-input vertex
    (parity: ``nn/graph/vertex/impl/LayerVertex.java``)."""

    layer: Layer = None
    preprocessor: Optional[InputPreProcessor] = None

    def has_params(self) -> bool:
        return self.layer.has_params()

    def init_params(self, key, policy=None):
        return self.layer.init_params(key, policy)

    def init_state(self, policy=None):
        return self.layer.init_state(policy)

    def param_shapes(self, policy=None):
        return self.layer.param_shapes(policy)

    def output_type(self, input_types):
        it = input_types[0]
        if self.preprocessor is not None:
            it = self.preprocessor.output_type(it)
        return self.layer.output_type(it)

    def set_n_in(self, input_types, override=False):
        it = input_types[0]
        if self.preprocessor is not None:
            it = self.preprocessor.output_type(it)
        self.layer.set_n_in(it, override)

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        x = xs[0]
        mask = masks[0] if masks else None
        if self.preprocessor is not None:
            # the NETWORK minibatch, not x.shape[0]: time-flattened inputs
            # arrive as [b*t, f] and FeedForwardToRnn must rebuild [b, t, f]
            mb = minibatch if minibatch is not None else x.shape[0]
            x = call_preprocessor(self.preprocessor, x, minibatch_size=mb,
                                  rng=rng)
            mask = self.preprocessor.transform_mask(mask, minibatch_size=mb)
        scope = self.layer._trace_scope
        with (contextlib.nullcontext() if scope is None
              else jax.named_scope(scope)):
            return self.layer.apply(params, x, state=state, train=train,
                                    rng=rng, mask=mask, policy=policy)


@register_vertex("merge")
@dataclasses.dataclass
class MergeVertex(GraphVertex):
    """Concatenate along the feature (last) axis
    (parity: ``nn/conf/graph/MergeVertex.java`` — NHWC makes depth concat the
    last axis for CNN activations too)."""

    def output_type(self, input_types):
        first = input_types[0]
        if first.kind == "convolutional":
            return InputType.convolutional(
                first.height, first.width,
                sum(t.channels for t in input_types))
        if first.kind == "recurrent":
            return InputType.recurrent(sum(t.size for t in input_types),
                                       first.timesteps)
        return InputType.feed_forward(sum(t.flat_size() for t in input_types))

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        return jnp.concatenate(xs, axis=-1), state


@register_vertex("elementwise")
@dataclasses.dataclass
class ElementWiseVertex(GraphVertex):
    """Pointwise add/subtract/product/average/max over equal-shaped inputs
    (parity: ``nn/conf/graph/ElementWiseVertex.java``; the residual-sum
    building block of ResNet)."""

    op: str = "add"   # add | subtract | product | average | max
    # every input cast to float32 first: a float32 residual stream under a
    # bfloat16 policy (EvaByte's ``fp32_skip_add``)
    float32: bool = False

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        op = self.op.lower()
        if self.float32:
            xs = [x.astype(jnp.float32) for x in xs]
        if op == "add":
            out = xs[0]
            for x in xs[1:]:
                out = out + x
        elif op == "subtract":
            if len(xs) != 2:
                raise ValueError("subtract needs exactly 2 inputs")
            out = xs[0] - xs[1]
        elif op == "product":
            out = xs[0]
            for x in xs[1:]:
                out = out * x
        elif op == "average":
            out = sum(xs) / float(len(xs))
        elif op == "max":
            out = xs[0]
            for x in xs[1:]:
                out = jnp.maximum(out, x)
        else:
            raise ValueError(f"unknown elementwise op {self.op!r}")
        return out, state


@register_vertex("subset")
@dataclasses.dataclass
class SubsetVertex(GraphVertex):
    """Feature range [from_idx, to_idx] inclusive (parity:
    ``nn/conf/graph/SubsetVertex.java``)."""

    from_idx: int = 0
    to_idx: int = 0

    def output_type(self, input_types):
        n = self.to_idx - self.from_idx + 1
        it = input_types[0]
        if it.kind == "recurrent":
            return InputType.recurrent(n, it.timesteps)
        if it.kind == "convolutional":   # subset over channels (last axis)
            return InputType.convolutional(it.height, it.width, n)
        return InputType.feed_forward(n)

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        return xs[0][..., self.from_idx:self.to_idx + 1], state


@register_vertex("stack")
@dataclasses.dataclass
class StackVertex(GraphVertex):
    """Stack inputs along the batch axis (parity:
    ``nn/conf/graph/StackVertex.java`` — used for weight-shared towers)."""

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        return jnp.concatenate(xs, axis=0), state

    def output_mask(self, masks, minibatch=None):
        if not masks or all(m is None for m in masks):
            return None
        if any(m is None for m in masks):
            raise ValueError("StackVertex: either all or no inputs may be masked")
        return jnp.concatenate(masks, axis=0)

    def output_minibatch(self, in_mbs):
        return sum(in_mbs)


@register_vertex("unstack")
@dataclasses.dataclass
class UnstackVertex(GraphVertex):
    """Take batch slice `from_idx` of `stack_size` equal slices (parity:
    ``nn/conf/graph/UnstackVertex.java``)."""

    from_idx: int = 0
    stack_size: int = 1

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        x = xs[0]
        step = x.shape[0] // self.stack_size
        return x[self.from_idx * step:(self.from_idx + 1) * step], state

    def output_mask(self, masks, minibatch=None):
        m = super().output_mask(masks)
        if m is None:
            return None
        step = m.shape[0] // self.stack_size
        return m[self.from_idx * step:(self.from_idx + 1) * step]

    def output_minibatch(self, in_mbs):
        return in_mbs[0] // self.stack_size


@register_vertex("scale")
@dataclasses.dataclass
class ScaleVertex(GraphVertex):
    """Multiply by a fixed scalar (parity: ``nn/conf/graph/ScaleVertex.java``)."""

    scale: float = 1.0

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        return xs[0] * self.scale, state


@register_vertex("shift")
@dataclasses.dataclass
class ShiftVertex(GraphVertex):
    """Add a fixed scalar (parity: ``nn/conf/graph/ShiftVertex.java``)."""

    shift: float = 0.0

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        return xs[0] + self.shift, state


@register_vertex("l2")
@dataclasses.dataclass
class L2Vertex(GraphVertex):
    """Pairwise L2 distance between two inputs → [b, 1] (parity:
    ``nn/conf/graph/L2Vertex.java``; used by siamese/triplet nets)."""

    epsilon: float = 1e-8

    def output_type(self, input_types):
        return InputType.feed_forward(1)

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        a = xs[0].reshape(xs[0].shape[0], -1)
        b = xs[1].reshape(xs[1].shape[0], -1)
        d2 = jnp.sum(jnp.square(a - b), axis=1, keepdims=True)
        return jnp.sqrt(d2 + self.epsilon), state


@register_vertex("l2normalize")
@dataclasses.dataclass
class L2NormalizeVertex(GraphVertex):
    """x / ||x||_2 over the feature axes (parity:
    ``nn/conf/graph/L2NormalizeVertex.java``)."""

    epsilon: float = 1e-8

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        x = xs[0]
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True)
                        + self.epsilon)
        return x / norm, state


@register_vertex("preprocessor")
@dataclasses.dataclass
class PreprocessorVertex(GraphVertex):
    """Standalone shape-adapter vertex (parity:
    ``nn/conf/graph/PreprocessorVertex.java``)."""

    preprocessor: InputPreProcessor = None

    def output_type(self, input_types):
        return self.preprocessor.output_type(input_types[0])

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        x = xs[0]
        mb = minibatch if minibatch is not None else x.shape[0]
        return call_preprocessor(self.preprocessor, x, minibatch_size=mb,
                                 rng=rng), state

    def output_mask(self, masks, minibatch: Optional[int] = None):
        m = masks[0] if masks else None
        if m is None:
            return None
        return self.preprocessor.transform_mask(m, minibatch_size=minibatch)


@register_vertex("last_time_step")
@dataclasses.dataclass
class LastTimeStepVertex(GraphVertex):
    """[b, t, f] → [b, f] at the last unmasked step (parity:
    ``nn/conf/graph/rnn/LastTimeStepVertex.java``)."""

    def output_type(self, input_types):
        return InputType.feed_forward(input_types[0].size)

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        x = xs[0]
        mask = masks[0] if masks else None
        if mask is None:
            return x[:, -1, :], state
        # index of last step with mask > 0, per example
        t = x.shape[1]
        idx = t - 1 - jnp.argmax(jnp.flip(mask > 0, axis=1), axis=1)
        return x[jnp.arange(x.shape[0]), idx], state

    def output_mask(self, masks, minibatch=None):
        return None  # output is per-example, fully active


@register_vertex("duplicate_to_time_series")
@dataclasses.dataclass
class DuplicateToTimeSeriesVertex(GraphVertex):
    """[b, f] broadcast to [b, t, f]; t taken from a reference input by name
    (parity: ``nn/conf/graph/rnn/DuplicateToTimeSeriesVertex.java``). The
    runtime passes the reference activation as second input."""

    reference_input: str = ""

    def output_type(self, input_types):
        ref = input_types[1] if len(input_types) > 1 else None
        return InputType.recurrent(input_types[0].flat_size(),
                                   ref.timesteps if ref else None)

    def apply(self, params, xs, *, state=None, train=False, rng=None,
              masks=None, policy=None, minibatch=None):
        x, ref = xs[0], xs[1]
        t = ref.shape[1]
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], t, x.shape[1])), state

    def output_mask(self, masks, minibatch=None):
        return masks[1] if masks and len(masks) > 1 else None


# --------------------------------------------------------------------------
# configuration + builder
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ComputationGraphConfiguration:
    """Named DAG: vertices, their input edges, network inputs/outputs.

    Parity: ``nn/conf/ComputationGraphConfiguration.java``.
    """

    vertices: Dict[str, GraphVertex]
    vertex_inputs: Dict[str, List[str]]
    network_inputs: List[str]
    network_outputs: List[str]
    training: TrainingConfig = dataclasses.field(default_factory=TrainingConfig)
    input_types: Optional[List[InputType]] = None
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20

    # ---- topology ----
    def topological_order(self) -> List[str]:
        """Kahn topo sort, deterministic (insertion order among ready nodes).
        Parity: ``ComputationGraph.java:810``."""
        indeg = {name: 0 for name in self.vertices}
        children: Dict[str, List[str]] = {name: [] for name in self.vertices}
        for name, inputs in self.vertex_inputs.items():
            for inp in inputs:
                if inp in self.vertices:
                    indeg[name] += 1
                    children[inp].append(name)
                elif inp not in self.network_inputs:
                    raise ValueError(
                        f"vertex {name!r} references unknown input {inp!r}")
        ready = [n for n in self.vertices if indeg[n] == 0]
        order: List[str] = []
        while ready:
            n = ready.pop(0)
            order.append(n)
            for c in children[n]:
                indeg[c] -= 1
                if indeg[c] == 0:
                    ready.append(c)
        if len(order) != len(self.vertices):
            cyc = sorted(set(self.vertices) - set(order))
            raise ValueError(f"graph has a cycle involving {cyc}")
        return order

    def validate(self) -> None:
        for out in self.network_outputs:
            if out not in self.vertices:
                raise ValueError(f"network output {out!r} is not a vertex")
        for name in self.vertices:
            if name in self.network_inputs:
                raise ValueError(f"{name!r} is both a vertex and a network input")
            if not self.vertex_inputs.get(name):
                raise ValueError(f"vertex {name!r} has no inputs")
        self.topological_order()

    # ---- shape inference over the DAG ----
    def infer_shapes(self) -> Dict[str, InputType]:
        if self.input_types is None:
            return {}
        types: Dict[str, InputType] = dict(
            zip(self.network_inputs, self.input_types))
        for name in self.topological_order():
            v = self.vertices[name]
            in_types = [types[i] for i in self.vertex_inputs[name]]
            v.set_n_in(in_types, override=False)
            types[name] = v.output_type(in_types)
        return types

    # ---- serde ----
    def to_dict(self) -> dict:
        return {
            "format_version": 1,
            "framework": "deeplearning4j_tpu",
            "model": "computation_graph",
            "vertices": {n: vertex_to_dict(v) for n, v in self.vertices.items()},
            "vertex_inputs": self.vertex_inputs,
            "network_inputs": self.network_inputs,
            "network_outputs": self.network_outputs,
            "training": self.training.to_dict(),
            "input_types": ([t.to_dict() for t in self.input_types]
                            if self.input_types else None),
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
        }

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration(
            vertices={n: vertex_from_dict(v)
                      for n, v in d["vertices"].items()},
            vertex_inputs={n: list(v) for n, v in d["vertex_inputs"].items()},
            network_inputs=list(d["network_inputs"]),
            network_outputs=list(d["network_outputs"]),
            training=TrainingConfig.from_dict(d.get("training", {})),
            input_types=([InputType.from_dict(t) for t in d["input_types"]]
                         if d.get("input_types") else None),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
        )

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))

    def to_yaml(self) -> str:
        """(parity: the reference's ``toYaml`` Jackson mapper)"""
        import yaml
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        import yaml
        return ComputationGraphConfiguration.from_dict(yaml.safe_load(s))


class GraphBuilder:
    """Fluent DAG builder (parity: ``ComputationGraphConfiguration.GraphBuilder``
    reached via ``NeuralNetConfiguration.Builder.graphBuilder()`` ``:613``).

    Usage::

        conf = (NeuralNetConfiguration.builder().updater("adam")
                .graph_builder()
                .add_inputs("in")
                .add_layer("conv1", ConvolutionLayer(...), "in")
                .add_vertex("res", ElementWiseVertex(op="add"), "conv1", "in")
                .add_layer("out", OutputLayer(...), "res")
                .set_outputs("out")
                .set_input_types(InputType.convolutional(32, 32, 3))
                .build())
    """

    def __init__(self, base):
        self._base = base
        self._vertices: Dict[str, GraphVertex] = {}
        self._vertex_inputs: Dict[str, List[str]] = {}
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._input_types: Optional[List[InputType]] = None
        self._backprop_type = "standard"
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str,
                  preprocessor: Optional[InputPreProcessor] = None) -> "GraphBuilder":
        layer = self._base._apply_defaults(layer)
        return self.add_vertex(
            name, LayerVertex(layer=layer, preprocessor=preprocessor), *inputs)

    def add_vertex(self, name: str, vertex: GraphVertex,
                   *inputs: str) -> "GraphBuilder":
        if name in self._vertices or name in self._inputs:
            raise ValueError(f"duplicate vertex name {name!r}")
        if not inputs:
            raise ValueError(f"vertex {name!r} needs at least one input")
        self._vertices[name] = vertex
        self._vertex_inputs[name] = list(inputs)
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        self._input_types = list(types)
        return self

    def backprop_type(self, kind: str) -> "GraphBuilder":
        self._backprop_type = kind.lower()
        return self

    def t_bptt_forward_length(self, n: int) -> "GraphBuilder":
        self._tbptt_fwd = int(n)
        return self

    def t_bptt_backward_length(self, n: int) -> "GraphBuilder":
        self._tbptt_back = int(n)
        return self

    def build(self) -> ComputationGraphConfiguration:
        conf = ComputationGraphConfiguration(
            vertices=self._vertices,
            vertex_inputs=self._vertex_inputs,
            network_inputs=list(self._inputs),
            network_outputs=list(self._outputs),
            training=copy.deepcopy(self._base._t),
            input_types=self._input_types,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
        )
        conf.validate()
        # auto-insert preprocessors + infer nIn along the DAG
        if conf.input_types is not None:
            types: Dict[str, InputType] = dict(
                zip(conf.network_inputs, conf.input_types))
            for name in conf.topological_order():
                v = conf.vertices[name]
                in_types = [types[i] for i in conf.vertex_inputs[name]]
                if isinstance(v, LayerVertex) and v.preprocessor is None:
                    v.preprocessor = v.layer.preprocessor_for(in_types[0])
                v.set_n_in(in_types, override=False)
                types[name] = v.output_type(in_types)
        return conf
