"""ComputationGraph: the DAG runtime model.

Parity: reference ``nn/graph/ComputationGraph.java`` — ``init`` (``:278``,
topo sort + params), ``fit`` (``:614-760``), ``computeGradientAndScore``
(``:912``), forward over ``topologicalOrder`` (``:1007``), ``output``
(``:1058``); multi-input/multi-output, loss summed over all output layers.

TPU-native design: the whole topo-ordered DAG forward + loss + ``jax.grad``
backward + updater apply traces into ONE jitted XLA program (donated params).
The reference's per-vertex ``doForward``/``doBackward`` dispatch loop has no
runtime analog — vertex boundaries disappear into XLA fusion. That program
is built by the engine this class inherits (``nn/trainable.py``: the jitted
step, its scanned and repeated forms, TBPTT, listeners); what is defined
here is the walk over the DAG and the loss.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import losses as _losses
from .. import rng as _rng
from ..optimize import updaters as _updaters
from ..util import health as _health
from ..util import xla as _xla
from ..util.netutil import note_streamed_steps as _note_streamed_steps
from ..util.netutil import precheck_streamed_steps as _precheck_streamed_steps
from .conf.graph import ComputationGraphConfiguration, LayerVertex
from .conf.preprocessors import call_preprocessor
from .trainable import TrainableNetwork

Pytree = Any


def _as_list(v) -> List[Any]:
    return list(v) if isinstance(v, (list, tuple)) else [v]


class ComputationGraph(TrainableNetwork):
    """Runtime DAG network over a :class:`ComputationGraphConfiguration`."""

    def __init__(self, conf: ComputationGraphConfiguration):
        conf.validate()
        super().__init__(conf)
        self.topo_order = conf.topological_order()
        self._output_layer_names = [
            n for n in conf.network_outputs
            if hasattr(self._vertex_layer(n), "compute_score_array")]

    def _vertex_layer(self, name: str):
        v = self.conf.vertices[name]
        return v.layer if isinstance(v, LayerVertex) else None

    # ------------------------------------------------------------------
    # init (parity: ComputationGraph.init :278)
    # ------------------------------------------------------------------

    def init(self, key: Optional[jax.Array] = None) -> "ComputationGraph":
        with _xla.init_region(self):
            if key is None:
                key = _rng.key(self.training.seed)
            params, state = {}, {}
            for name in self.topo_order:
                v = self.conf.vertices[name]
                vk = _rng.fold_name(key, name)
                params[name] = v.init_params(vk, self.policy)
                state[name] = v.init_state(self.policy)
            self.params = params
            self.state = state
            self._persistent_keys = {
                name: tuple(
                    self.conf.vertices[name].init_state(self.policy).keys())
                for name in self.topo_order}
            self._updater = _updaters.make_updater(
                self.training, self._lr_multipliers())
            self.updater_state = self._updater.init(params)
        return self

    def _lr_multipliers(self) -> Pytree:
        base = float(self.training.learning_rate)
        mults = {}
        for name in self.topo_order:
            v = self.conf.vertices[name]
            layer = v.layer if isinstance(v, LayerVertex) else None
            shapes = v.param_shapes(self.policy)
            if layer is None or not shapes:
                mults[name] = {k: 1.0 for k in shapes}
                continue
            layer_lr = (layer.learning_rate
                        if layer.learning_rate is not None else base)
            bias_lr = (layer.bias_learning_rate
                       if layer.bias_learning_rate is not None else layer_lr)
            if base == 0.0:
                if layer_lr != 0.0 or bias_lr != 0.0:
                    raise ValueError(
                        f"vertex {name!r} sets a per-layer learning rate but "
                        "the global learning_rate is 0.0")
                mults[name] = {k: 1.0 for k in shapes}
            else:
                mults[name] = {k: (bias_lr / base if k == "b" else layer_lr / base)
                               for k in shapes}
        return mults

    def _param_layers(self):
        """Vertices that hold a layer (the others carry no l1/l2)."""
        pairs = ((name, self._vertex_layer(name)) for name in self.topo_order)
        return ((name, layer) for name, layer in pairs if layer is not None)

    # ------------------------------------------------------------------
    # functional forward over the DAG
    # ------------------------------------------------------------------

    def _states_map(self, rnn_state=None) -> Dict[str, Dict[str, jax.Array]]:
        out = {}
        for n in self.topo_order:
            st = dict(self.state.get(n, {}))
            if rnn_state is not None and rnn_state.get(n):
                st.update(rnn_state[n])
            out[n] = st
        return out

    _states = _states_map   # the name the shared engine asks under

    def _persist_states(self, new_states: Dict[str, Dict[str, jax.Array]]) -> None:
        for name, keys in self._persistent_keys.items():
            if keys:
                self.state[name] = {
                    k: new_states[name][k] for k in keys if k in new_states[name]}

    def _minibatch_map(self, batch: int) -> Dict[str, int]:
        """True EXAMPLE count at every vertex (batch-axis vertices like
        Stack/Unstack change it; time-flattening does not). Host-side ints,
        cached per input batch size."""
        cache = self._jit_cache.setdefault("_mb_maps", {})
        mbs = cache.get(batch)
        if mbs is None:
            mbs = {n: batch for n in self.conf.network_inputs}
            for name in self.topo_order:
                mbs[name] = self.conf.vertices[name].output_minibatch(
                    [mbs[i] for i in self.conf.vertex_inputs[name]])
            cache[batch] = mbs
        return mbs

    def _forward(self, params, states, inputs: List[jax.Array], *,
                 train: bool, rng=None, masks=None):
        """Walk the topo order; returns ({vertex: activation}, new_states)."""
        mbs = self._minibatch_map(inputs[0].shape[0])
        acts: Dict[str, jax.Array] = dict(zip(self.conf.network_inputs, inputs))
        mask_map: Dict[str, Optional[jax.Array]] = dict(
            zip(self.conf.network_inputs,
                masks if masks is not None else [None] * len(inputs)))
        new_states: Dict[str, Dict[str, jax.Array]] = {}
        for name in self.topo_order:
            in_names = self.conf.vertex_inputs[name]
            in_masks = [mask_map.get(i) for i in in_names]
            vrng = None if rng is None else _rng.fold_name(rng, name)
            out, st = self._apply_vertex(name, params[name], acts,
                                         states[name], vrng, train=train,
                                         in_masks=in_masks,
                                         minibatch=mbs[in_names[0]])
            acts[name] = out
            mask_map[name] = self.conf.vertices[name].output_mask(
                in_masks, minibatch=acts[in_names[0]].shape[0])
            new_states[name] = st
        return acts, new_states

    def _apply_vertex(self, name, params_n, local_acts, state_n, vrng, *,
                      train, in_masks=None, minibatch=None):
        """Gather inputs + apply for one vertex — the single definition of
        per-vertex forward semantics, shared by the plain and
        remat-segmented paths (so they cannot drift). ``minibatch`` is the
        NETWORK batch size (time-flattened activations make x.shape[0]
        wrong for shape-rebuilding preprocessors)."""
        v = self.conf.vertices[name]
        xs = [local_acts[i] for i in self.conf.vertex_inputs[name]]
        if in_masks is None:
            in_masks = [None] * len(xs)
        out, st = v.apply(params_n, xs, state=state_n, train=train,
                          rng=vrng, masks=in_masks, policy=self.policy,
                          minibatch=minibatch)
        return out, (st if st is not None else {})

    def _segment_plan(self):
        """Partition the topo order into ~sqrt(V) segments and, per segment,
        record which activations cross its boundary. Cached — the plan is
        pure graph structure."""
        plan = getattr(self, "_seg_plan", None)
        if plan is not None:
            return plan
        order = self.topo_order
        n_seg = max(1, int(np.ceil(np.sqrt(len(order)))))
        bounds = np.array_split(np.arange(len(order)), n_seg)
        pos = {name: i for i, name in enumerate(order)}
        # the loss head reads the output-layer vertices' INPUTS (hidden
        # activations feed compute_score_array), so those must be published
        # as segment boundaries; output-layer vertices nothing downstream
        # consumes are skipped entirely (their activation is never read —
        # same rule as the unsegmented loss walk)
        consumed = {i for ins in self.conf.vertex_inputs.values()
                    for i in ins}
        skip = {n for n in self._output_layer_names if n not in consumed}
        required = set(self.conf.network_outputs) - skip
        for name in self._output_layer_names:
            required.update(self.conf.vertex_inputs[name])
        segments = []
        for idx in bounds:
            seg = [order[i] for i in idx if order[i] not in skip]
            if not seg:
                continue
            seg_set = set(seg)
            ext_in, seen = [], set()
            for vname in seg:
                for src in self.conf.vertex_inputs[vname]:
                    if src not in seg_set and src not in seen:
                        seen.add(src)
                        ext_in.append(src)
            last = pos[seg[-1]]
            outs = [vname for vname in seg
                    if vname in required
                    or any(pos[w] > last
                           for w in order
                           if vname in self.conf.vertex_inputs[w])]
            segments.append((seg, ext_in, outs))
        self._seg_plan = (segments, skip)
        return self._seg_plan

    def _forward_segmented(self, params, states, inputs: List[jax.Array],
                           *, rng=None):
        """Training forward with segment-level rematerialization: only
        segment-boundary activations stay live for the backward pass; each
        segment's interior (conv pre-activations, BN intermediates, ...) is
        recomputed under ``jax.checkpoint``. ~sqrt(V) segments — the
        standard memory/compute trade (brief: jax.checkpoint for HBM).
        Masked inputs fall back to the unsegmented path (mask plumbing is
        host-side Python, incompatible with a traced segment boundary)."""
        mbs = self._minibatch_map(inputs[0].shape[0])
        acts: Dict[str, jax.Array] = dict(
            zip(self.conf.network_inputs, inputs))
        segments, skip = self._segment_plan()
        # skipped (unconsumed) output-layer vertices still need a state
        # entry: downstream carry structures index every vertex name
        new_states: Dict[str, Dict[str, jax.Array]] = {n: {} for n in skip}
        for seg, ext_in, outs_needed in segments:
            seg_params = {n: params[n] for n in seg}
            seg_states = {n: states[n] for n in seg}
            seg_rngs = {n: (None if rng is None else _rng.fold_name(rng, n))
                        for n in seg}

            def seg_fn(p, ext_acts, st, rngs, _seg=tuple(seg),
                       _ext=tuple(ext_in), _outs=tuple(outs_needed)):
                local = dict(zip(_ext, ext_acts))
                st_out = {}
                for vname in _seg:
                    out, vst = self._apply_vertex(
                        vname, p[vname], local, st[vname], rngs[vname],
                        train=True,
                        minibatch=mbs[self.conf.vertex_inputs[vname][0]])
                    local[vname] = out
                    st_out[vname] = vst
                return [local[o] for o in _outs], st_out

            outs, seg_new = jax.checkpoint(seg_fn)(
                seg_params, [acts[n] for n in ext_in], seg_states, seg_rngs)
            acts.update(zip(outs_needed, outs))
            new_states.update(seg_new)
        return acts, new_states

    # ------------------------------------------------------------------
    # inference (parity: output :1058)
    # ------------------------------------------------------------------

    def output(self, *inputs, train: bool = False):
        """Activations of the network outputs. Returns a single array when
        there is one output, else a list."""
        inputs = [jnp.asarray(x) for x in _as_list(
            inputs[0] if len(inputs) == 1 and isinstance(inputs[0], (list, tuple))
            else list(inputs))]
        # trace_env_key: flash-attention routing flags are read at trace
        # time, so the compiled program is only reused while they match
        cache_key = f"output_train={train}@{_xla.trace_env_key()}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            @jax.jit
            def fn(params, states, inputs, rng):
                acts, _ = self._forward(params, states, inputs,
                                        train=train,
                                        rng=rng if train else None)
                return [acts[n] for n in self.conf.network_outputs]
            fn = _xla.retrace_guard(fn, "ComputationGraph.output")
            self._jit_cache[cache_key] = fn
        rng = (_rng.fold_name(_rng.key(self.training.seed),
                              f"output_{self.iteration_count}")
               if train else None)
        outs = fn(self.params, self._states_map(), inputs, rng)
        return outs[0] if len(outs) == 1 else outs

    def rnn_time_step(self, *inputs):
        """Streaming inference: feed one (or a few) timesteps, carrying each
        recurrent vertex's h/c between calls (parity: the reference
        ComputationGraph's ``rnnTimeStep`` with per-vertex state maps).
        Inputs: [b, f] (single step, output squeezed back) or [b, t, f]."""
        inputs = [jnp.asarray(x) for x in _as_list(
            inputs[0] if len(inputs) == 1 and isinstance(inputs[0], (list, tuple))
            else list(inputs))]
        squeeze = inputs[0].ndim == 2
        if squeeze:
            inputs = [x[:, None, :] for x in inputs]
        if self._rnn_state is None:
            # seed the streaming carries (LSTM h/c zeros; attention K/V
            # caches when max_cache_t is set) — apply() distinguishes a
            # streaming call from plain output() by the presence of the
            # carried cache
            self._rnn_state = self._zero_rnn_carry(inputs[0].shape[0])
            self._rnn_steps_fed = 0
        # strict-mode streaming caches refuse the overflowing chunk
        # host-side, before it can touch the cache
        _precheck_streamed_steps(self, inputs[0].shape[1])
        cache_key = f"rnn_time_step@{_xla.trace_env_key()}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            @jax.jit
            def fn(params, states, inputs):
                acts, new_states = self._forward(params, states, inputs,
                                                 train=False)
                carry = {name: {k: v for k, v in st.items()
                                if k in ("h", "c")}
                         for name, st in new_states.items()}
                return [acts[n] for n in self.conf.network_outputs], carry
            fn = _xla.retrace_guard(fn, "ComputationGraph.rnn_time_step")
            self._jit_cache[cache_key] = fn
        outs, self._rnn_state = fn(self.params,
                                   self._states_map(self._rnn_state), inputs)
        # count only steps the cache actually absorbed (a rejected chunk
        # raised above and never touched it)
        _note_streamed_steps(self, inputs[0].shape[1])
        if squeeze:
            outs = [o[:, 0, :] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self) -> None:
        """Reset the streaming rnn carry (parity: ``rnnClearPreviousState``)."""
        self._rnn_state = None
        self._rnn_steps_fed = 0

    def feed_forward(self, *inputs, train: bool = False) -> Dict[str, jax.Array]:
        """All vertex activations keyed by name."""
        inputs = [jnp.asarray(x) for x in _as_list(
            inputs[0] if len(inputs) == 1 and isinstance(inputs[0], (list, tuple))
            else list(inputs))]
        acts, _ = self._forward(self.params, self._states_map(), inputs,
                                train=train)
        return acts

    # ------------------------------------------------------------------
    # loss (parity: computeGradientAndScore :912 — score summed over outputs)
    # ------------------------------------------------------------------

    def _loss_fn(self, params, states, inputs, labels, masks, rng, *,
                 collect_stats=False):
        # collect_stats: falsy = plain loss; True or a health.StatsConfig
        # (whose act_sample bounds the activation reductions) additionally
        # returns per-vertex activation summaries through the aux output
        if not self._output_layer_names:
            raise ValueError(
                "no output vertex has a loss (need OutputLayer/RnnOutputLayer/"
                "LossLayer at a network output to train)")
        # stats collection summarizes every vertex activation in the main
        # walk — it bypasses the remat path (same trade as the sequential
        # runtime: visibility over the memory saving)
        if self.training.gradient_checkpointing and not collect_stats:
            if masks is None or all(m is None for m in masks):
                return self._loss_fn_segmented(params, states, inputs,
                                               labels, rng)
            # masked graphs keep the unsegmented walk (mask bookkeeping is
            # per-vertex host-side state across segment boundaries) — say
            # so loudly rather than silently dropping the memory saving
            import warnings
            warnings.warn(
                "gradient_checkpointing is ignored for masked "
                "ComputationGraph inputs — the full-activation path runs",
                stacklevel=2)
        # forward everything EXCEPT the output-layer vertices' own apply;
        # for those we need the hidden input to compute_score_array
        out_set = set(self._output_layer_names)
        acts: Dict[str, jax.Array] = dict(zip(self.conf.network_inputs, inputs))
        mask_map: Dict[str, Optional[jax.Array]] = dict(
            zip(self.conf.network_inputs,
                masks if masks is not None else [None] * len(inputs)))
        new_states: Dict[str, Dict[str, jax.Array]] = {}
        label_map = dict(zip(self.conf.network_outputs, labels))
        # output-layer vertices that also feed downstream vertices must still
        # publish their activation (reference ComputationGraph supports output
        # layers with consumers); XLA CSE merges the duplicated layer forward
        consumed = {i for ins in self.conf.vertex_inputs.values() for i in ins}
        mbs = self._minibatch_map(inputs[0].shape[0])
        act_stats: Dict[str, Dict[str, jax.Array]] = {}
        total = 0.0
        for name in self.topo_order:
            in_names = self.conf.vertex_inputs[name]
            in_masks = [mask_map.get(i) for i in in_names]
            vrng = None if rng is None else _rng.fold_name(rng, name)
            is_out = name in out_set
            if is_out:
                total = total + self._output_score(
                    params, name, acts[in_names[0]], label_map[name],
                    in_masks[0] if in_masks else None, vrng,
                    minibatch=mbs[in_names[0]])
            if not is_out or name in consumed:
                out, st = self._apply_vertex(name, params[name], acts,
                                             states[name], vrng, train=True,
                                             in_masks=in_masks,
                                             minibatch=mbs[in_names[0]])
                acts[name] = out
                mask_map[name] = self.conf.vertices[name].output_mask(
                    in_masks, minibatch=acts[in_names[0]].shape[0])
                new_states[name] = st
                if collect_stats:
                    act_stats[name] = _health.act_summary(
                        out, getattr(collect_stats, "act_sample", 0))
            else:
                new_states[name] = {}
        total = total + self._reg_penalty(params)
        # layers may surface auxiliary objectives through their state
        # (e.g. MoELayer's load-balancing loss, pre-scaled by aux_weight)
        for st in new_states.values():
            if "aux_loss" in st:
                total = total + st["aux_loss"]
        loss_dtype = (jnp.float64 if self.policy.param_dtype == jnp.float64
                      else jnp.float32)
        if collect_stats:
            return total.astype(loss_dtype), (new_states, act_stats)
        return total.astype(loss_dtype), new_states

    def _output_score(self, params, name, hidden, y, mask, vrng=None,
                      minibatch=None):
        """One output vertex's loss contribution from its HIDDEN input —
        preprocessor, fused score array, masked denominator. Shared by the
        plain and gradient-checkpointed loss paths. ``vrng`` is this
        vertex's rng fold — the SAME one ``_apply_vertex`` uses, so a
        sampling preprocessor on a consumed output vertex draws one sample,
        not two different ones."""
        v = self.conf.vertices[name]
        out_mask = mask
        if v.preprocessor is not None:
            mb = minibatch if minibatch is not None else hidden.shape[0]
            hidden = call_preprocessor(v.preprocessor, hidden,
                                       minibatch_size=mb, rng=vrng)
            out_mask = v.preprocessor.transform_mask(out_mask,
                                                     minibatch_size=mb)
        score_arr = v.layer.compute_score_array(
            params[name], hidden, y, mask=out_mask, policy=self.policy)
        denom = _losses.masked_denominator(
            out_mask, y, score_arr.shape[0],
            sparse=_losses.is_sparse(v.layer.loss))
        return jnp.sum(score_arr) / denom

    def _loss_fn_segmented(self, params, states, inputs, labels, rng):
        """Gradient-checkpointed loss: the DAG runs through
        ``_forward_segmented`` (only ~sqrt(V) boundary activations stay
        live for the backward), then the loss heads score the published
        hidden activations exactly like the unsegmented path."""
        acts, new_states = self._forward_segmented(params, states, inputs,
                                                   rng=rng)
        label_map = dict(zip(self.conf.network_outputs, labels))
        mbs = self._minibatch_map(inputs[0].shape[0])
        total = 0.0
        for name in self._output_layer_names:
            hidden = acts[self.conf.vertex_inputs[name][0]]
            vrng = None if rng is None else _rng.fold_name(rng, name)
            total = total + self._output_score(
                params, name, hidden, label_map[name], None, vrng,
                minibatch=mbs[self.conf.vertex_inputs[name][0]])
        total = total + self._reg_penalty(params)
        # layers may surface auxiliary objectives through their state
        # (e.g. MoELayer's load-balancing loss, pre-scaled by aux_weight)
        for st in new_states.values():
            if "aux_loss" in st:
                total = total + st["aux_loss"]
        loss_dtype = (jnp.float64 if self.policy.param_dtype == jnp.float64
                      else jnp.float32)
        return total.astype(loss_dtype), new_states

    def score_for(self, inputs, labels, masks=None) -> float:
        inputs, labels, masks = self._as_batch(inputs, labels, masks)
        loss, _ = self._loss_fn(self.params, self._states_map(), inputs,
                                labels, masks, None)
        return float(loss)

    # ------------------------------------------------------------------
    # fit: a call's arguments become the lists-of-arrays batch the loss
    # takes; the engine (TrainableNetwork) does the rest
    # ------------------------------------------------------------------

    @staticmethod
    def _as_batch(inputs, labels, masks):
        """Array or list of arrays each (multi-input / multi-output);
        masks: optional list of feature masks."""
        return ([jnp.asarray(x) for x in _as_list(inputs)],
                [jnp.asarray(y) for y in _as_list(labels)],
                None if masks is None else
                [None if m is None else jnp.asarray(m)
                 for m in _as_list(masks)])

    @staticmethod
    def _batch_size(inputs) -> int:
        return int(inputs[0].shape[0])

    def fit(self, data, labels=None, *, epochs: int = 1,
            coalesce: Optional[int] = None, session=None) -> None:
        """Train from (inputs, labels), a DataSet/MultiDataSet, or an iterator
        of either (parity: fit variants :614-760). No ``mask`` keyword: a
        graph's masks ride in its DataSet batches. ``coalesce``, ``session``
        and the loop itself: ``TrainableNetwork._fit``."""
        self._fit(data, labels, None, epochs, coalesce, session)

    def fit_batch(self, inputs, labels, masks=None):
        """One update (tbptt-aware). inputs/labels: array or list of arrays
        (multi-input / multi-output); masks: optional list of feature
        masks."""
        return self._fit_batch(*self._as_batch(inputs, labels, masks))

    def fit_scan(self, xs, ys, masks=None):
        """Train on K pre-staged batches in one dispatch. xs/ys: [k, b, ...]
        arrays or lists of such (multi-input/multi-output); returns [k] losses."""
        return self._fit_scan(*self._as_batch(xs, ys, masks))

    def fit_repeated(self, inputs, labels, k: int, masks=None):
        """Run K optimizer updates on one pre-staged batch in a single device
        dispatch (lax.scan over step indices). The on-chip analog of calling
        ``fit_batch`` K times: same per-update rng folding, iteration counters,
        and listener firing — but one dispatch and one batch of HBM. Used for
        steady-state throughput measurement; returns [k] losses."""
        return self._fit_repeated(*self._as_batch(inputs, labels, masks), k)

    def _tbptt_T(self, inputs):
        """The time-series length for truncated BPTT, scanning ALL inputs
        (the first may be a static [b, f] feature — reference CG scans the
        whole input set). None when tbptt is off or nothing is temporal;
        mixed 3-D lengths are ambiguous and raise."""
        if self.conf.backprop_type != "truncated_bptt":
            return None
        ts = {int(x.shape[1]) for x in inputs if x.ndim == 3}
        if not ts:
            return None
        if len(ts) > 1:
            raise ValueError(
                f"truncated_bptt with differing sequence lengths {sorted(ts)} "
                "across inputs is ambiguous — align or pad them")
        return ts.pop()

    @staticmethod
    def _tbptt_slice(inputs, labels, masks, T, start, end):
        """Only what runs the whole length T is cut: a static [b, f] input,
        a per-sequence label or a mask of another length passes whole."""
        def cut(a, temporal):
            return a[:, start:end] if temporal and a.shape[1] == T else a
        return ([cut(x, x.ndim == 3) for x in inputs],
                [cut(y, y.ndim == 3) for y in labels],
                None if masks is None else
                [cut(m, m is not None and m.ndim >= 2) for m in masks])

    def _zero_rnn_carry(self, batch):
        mbs = self._minibatch_map(batch)
        carry = {}
        for name in self.topo_order:
            layer = self._vertex_layer(name)
            # max_cache_t None = a streaming-capable layer (attention)
            # whose cache is disabled — it carries nothing
            if (layer is not None and hasattr(layer, "_zero_state")
                    and getattr(layer, "max_cache_t", True) is not None):
                mb = mbs[self.conf.vertex_inputs[name][0]]
                h, c = layer._zero_state(mb, self.policy)
                carry[name] = {"h": h, "c": c}
            else:
                carry[name] = {}
        return carry

    # ------------------------------------------------------------------
    # layerwise pretraining (parity: ComputationGraph.pretrain :509-523)
    # ------------------------------------------------------------------

    def pretrain(self, data, labels=None, *, epochs: int = 1,
                 learning_rate: Optional[float] = None) -> None:
        """Greedy layerwise pretraining of AutoEncoder/RBM layer vertices,
        in topological order: each pretrainable vertex trains on its frozen
        upstream activations, then the walk moves deeper."""
        if self.params is None:
            self.init()
        lr = float(learning_rate if learning_rate is not None
                   else self.training.learning_rate)
        pre = [n for n in self.topo_order
               if self._vertex_layer(n) is not None
               and (hasattr(self._vertex_layer(n), "pretrain_loss")
                    or hasattr(self._vertex_layer(n),
                               "contrastive_divergence_grads"))]
        if not pre:
            return
        from .conf.pretrain import make_pretrain_step
        batches = list(self._as_batches(data, labels, None))
        for name in pre:
            step = make_pretrain_step(self._vertex_layer(name), lr,
                                      self.policy)
            # upstream is frozen while this vertex trains, so its input
            # activations are constant across epochs — but holding them all
            # is O(dataset) device memory; only precompute when the reuse
            # (epochs>1) and the footprint (few batches) justify it
            cache_all = epochs > 1 and len(batches) <= 64

            def _hid(ins):
                return self._vertex_input_activation(
                    name, [jnp.asarray(np.asarray(x)) for x in _as_list(ins)])

            hiddens = ([_hid(ins) for ins, _, _ in batches]
                       if cache_all else None)
            for e in range(epochs):
                for bi, (ins, _, _) in enumerate(batches):
                    hidden = hiddens[bi] if cache_all else _hid(ins)
                    rng = _rng.fold_name(_rng.key(self.training.seed),
                                         f"pre_{name}_{e}_{bi}")
                    self.params[name] = step(self.params[name], hidden, rng)

    def _vertex_input_activation(self, name: str, inputs: List[jax.Array]):
        """The (preprocessed) input activation a layer vertex sees, with all
        upstream vertices frozen in eval mode."""
        # trace_env_key: frozen-vertex forwards trace the same attention
        # routing flags as output()/fit — a flag flip must retrace here too
        cache_key = f"pre_acts_{name}@{_xla.trace_env_key()}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            @jax.jit
            def fn(params, states, inputs):
                acts, _ = self._forward(params, states, inputs, train=False)
                x = acts[self.conf.vertex_inputs[name][0]]
                v = self.conf.vertices[name]
                if v.preprocessor is not None:
                    mbs = self._minibatch_map(inputs[0].shape[0])
                    x = v.preprocessor(
                        x,
                        minibatch_size=mbs[self.conf.vertex_inputs[name][0]])
                return x
            self._jit_cache[cache_key] = fn
        return fn(self.params, self._states_map(), inputs)


    # ------------------------------------------------------------------
    # evaluation bridge
    # ------------------------------------------------------------------

    def evaluate(self, data, labels=None):
        """Classification evaluation; DataSet iterators carrying
        ``example_metadata`` flow provenance into the returned Evaluation
        (``get_prediction_errors()`` — parity: ``Evaluation.java:195``)."""
        from ..eval import Evaluation
        from ..util.batching import iter_batches
        ev = Evaluation()
        # fit() no longer resets the source after its final epoch; revive
        # an exhausted resettable iterator instead of evaluating nothing
        if (hasattr(data, "has_next") and not data.has_next()
                and hasattr(data, "reset")):
            data.reset()
        for x, y, m, meta in iter_batches(data, labels, with_meta=True):
            out = self.output(jnp.asarray(np.asarray(x)))
            ev.eval(np.asarray(y), np.asarray(out),
                    mask=None if m is None else np.asarray(m),
                    metadata=meta)
        if hasattr(data, "reset"):
            data.reset()
        return ev
