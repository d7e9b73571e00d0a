"""MultiLayerNetwork: the sequential-stack runtime model.

Parity: reference ``nn/multilayer/MultiLayerNetwork.java`` —
``init`` (``:368``), ``feedForward`` (``:627``), ``output`` (``:1581``),
``fit(DataSetIterator)`` (``:1037``), ``computeGradientAndScore`` (``:1867``),
``doTruncatedBPTT`` (``:1079``), ``rnnTimeStep`` (``:2274``), ``score``
(``:1900``).

TPU-native design (NOT a port):
  - Parameters are a pytree ``{"layer_0": {...}, ...}`` — not the reference's
    single flattened F-order buffer with per-layer views
    (``MultiLayerNetwork.java:368`` flattenedParams). XLA handles memory
    layout; pytrees keep sharding/checkpointing structural.
  - Training runs through the engine this class inherits
    (``nn/trainable.py``: the jitted step, its scanned and repeated forms,
    TBPTT, listeners); what is defined here is the walk over the layer
    list and the loss.
  - Backprop is autodiff through the forward functions; the reference's
    hand-written ``calcBackpropGradients`` reverse loop
    (``MultiLayerNetwork.java:1123-1190``) has no analog by design.
  - Non-param layer state (BatchNorm running stats) and recurrent carry
    (LSTM h/c) are threaded functionally and returned from the step.
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
from .conf.multi_layer import MultiLayerConfiguration
from .conf.preprocessors import call_preprocessor
from .trainable import TrainableNetwork

Pytree = Any


def _layer_key(i: int) -> str:
    return f"layer_{i}"


class MultiLayerNetwork(TrainableNetwork):
    """Runtime network over a :class:`MultiLayerConfiguration`."""

    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__(conf)
        self.layers = conf.layers
        self._has_loss_output = hasattr(self.layers[-1],
                                        "compute_score_array")

    # ------------------------------------------------------------------
    # init (parity: MultiLayerNetwork.init :368)
    # ------------------------------------------------------------------

    def init(self, key: Optional[jax.Array] = None) -> "MultiLayerNetwork":
        with _xla.init_region(self):
            if key is None:
                key = _rng.key(self.training.seed)
            params, state = {}, {}
            for i, layer in enumerate(self.layers):
                lk = _rng.fold_name(key, _layer_key(i))
                params[_layer_key(i)] = layer.init_params(lk, self.policy)
                state[_layer_key(i)] = layer.init_state(self.policy)
            self.params = params
            self.state = state
            # persistent-state keys per layer (e.g. BN running stats), cached
            # so the hot fit loop never re-calls init_state just to read key
            # names
            self._persistent_keys = [
                tuple(layer.init_state(self.policy).keys())
                for layer in self.layers]
            self._updater = _updaters.make_updater(
                self.training, self._lr_multipliers())
            self.updater_state = self._updater.init(params)
        return self

    def _lr_multipliers(self) -> Pytree:
        """Static per-param LR multiplier pytree (per-layer learning_rate and
        bias_learning_rate overrides, reference conf.getLearningRateByParam)."""
        base = float(self.training.learning_rate)
        mults = {}
        for i, layer in enumerate(self.layers):
            layer_lr = layer.learning_rate if layer.learning_rate is not None else base
            bias_lr = (layer.bias_learning_rate
                       if layer.bias_learning_rate is not None else layer_lr)
            if base == 0.0:
                # frozen net: any per-layer override would be silently scaled
                # to 0 through the multiplier — reject it loudly
                if layer_lr != 0.0 or bias_lr != 0.0:
                    raise ValueError(
                        f"layer {i} sets learning_rate={layer_lr}/"
                        f"bias_learning_rate={bias_lr} but the global "
                        "learning_rate is 0.0; per-layer overrides are "
                        "expressed as multiples of the global rate")
                mults[_layer_key(i)] = {
                    name: 1.0 for name in layer.param_shapes(self.policy)}
                continue
            mults[_layer_key(i)] = {
                name: (bias_lr / base if name == "b" else layer_lr / base)
                for name in layer.param_shapes(self.policy)
            }
        return mults

    def _param_layers(self):
        return ((_layer_key(i), layer) for i, layer in enumerate(self.layers))

    # ------------------------------------------------------------------
    # functional forward core
    # ------------------------------------------------------------------

    def _forward(self, params, states, x, *, train: bool, rng=None,
                 mask=None, upto: Optional[int] = None,
                 collect: bool = False):
        """Thread input through preprocessors + layers.

        Returns (activations | final activation, new_states).
        `states` is a list of per-layer dicts; recurrent carry (h/c) rides in
        the same dicts when present (TBPTT / rnnTimeStep).
        """
        upto = len(self.layers) if upto is None else upto
        if self.training.gradient_checkpointing and train and not collect:
            return self._forward_segmented(params, states, x, rng=rng,
                                           mask=mask, upto=upto)
        minibatch = x.shape[0]
        cur, cur_mask = x, mask
        acts = [x] if collect else None
        new_states = []
        for i in range(len(self.layers)):
            if i >= upto:
                new_states.append(states[i])
                continue
            lrng = None if rng is None else _rng.fold_name(rng, _layer_key(i))
            cur, cur_mask, st = self._apply_layer(
                i, params[_layer_key(i)], cur, cur_mask, states[i], lrng,
                train=train, minibatch=minibatch)
            new_states.append(st)
            if collect:
                acts.append(cur)
        return (acts if collect else cur), new_states

    def _apply_layer(self, i, p_i, cur, cur_mask, state_i, lrng, *,
                     train, minibatch):
        """Preprocessor + apply at layer position ``i`` — the single
        definition of per-layer forward semantics, shared by the plain and
        remat-segmented paths (so they cannot drift)."""
        proc = self.conf.input_preprocessors.get(i)
        if proc is not None:
            cur = call_preprocessor(proc, cur, minibatch_size=minibatch,
                                    rng=lrng)
            cur_mask = proc.transform_mask(cur_mask, minibatch_size=minibatch)
        cur, st = self.layers[i].apply(p_i, cur, state=state_i, train=train,
                                       rng=lrng, mask=cur_mask,
                                       policy=self.policy)
        return cur, cur_mask, (st if st is not None else {})

    def _forward_segmented(self, params, states, x, *, rng=None, mask=None,
                           upto: Optional[int] = None):
        """Training forward with SEGMENT-level rematerialization: layers are
        grouped into ~sqrt(N) runs and each run re-executes under
        ``jax.checkpoint`` in the backward — only segment-boundary
        activations stay live (per-layer checkpointing would keep every
        layer output as a residual and save almost nothing)."""
        n = len(self.layers) if upto is None else upto
        n_seg = max(1, int(np.ceil(np.sqrt(max(n, 1)))))
        minibatch = x.shape[0]
        cur, cur_mask = x, mask
        new_states: List[Dict] = []
        for idx in np.array_split(np.arange(n), n_seg):
            seg = [int(i) for i in idx]
            seg_params = [params[_layer_key(i)] for i in seg]
            seg_states = [states[i] for i in seg]
            seg_rngs = [None if rng is None
                        else _rng.fold_name(rng, _layer_key(i)) for i in seg]

            def seg_fn(p_seg, cur, cur_mask, st_seg, rngs, _seg=tuple(seg)):
                st_out = []
                for j, i in enumerate(_seg):
                    cur, cur_mask, st = self._apply_layer(
                        i, p_seg[j], cur, cur_mask, st_seg[j], rngs[j],
                        train=True, minibatch=minibatch)
                    st_out.append(st)
                return cur, cur_mask, st_out

            cur, cur_mask, st_out = jax.checkpoint(seg_fn)(
                seg_params, cur, cur_mask, seg_states, seg_rngs)
            new_states.extend(st_out)
        new_states.extend(states[n:])   # layers beyond upto: untouched
        return cur, new_states

    def _states_list(self, rnn_state=None):
        out = []
        for i in range(len(self.layers)):
            st = dict(self.state.get(_layer_key(i), {}))
            if rnn_state is not None and rnn_state[i]:
                st.update(rnn_state[i])
            out.append(st)
        return out

    _states = _states_list   # the name the shared engine asks under

    def _persist_states(self, new_states):
        """Keep only persistent (init_state-declared) entries, e.g. BN stats."""
        for i, keys in enumerate(self._persistent_keys):
            if keys:
                self.state[_layer_key(i)] = {
                    k: new_states[i][k] for k in keys if k in new_states[i]}

    # ------------------------------------------------------------------
    # inference (parity: output :1581 / feedForward :627 / rnnTimeStep :2274)
    # ------------------------------------------------------------------

    def output(self, x, train: bool = False):
        """Final-layer activations (compiled; cached per train/eval mode).
        train=True runs train-mode forward semantics (dropout active, BN
        batch statistics) without updating parameters."""
        x = jnp.asarray(x)
        # trace_env_key: flash-attention routing flags are read at trace
        # time, so the compiled program is only reused while they match
        cache_key = f"output_train={train}@{_xla.trace_env_key()}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            @jax.jit
            def fn(params, states, x, rng):
                out, _ = self._forward(params, states, x, train=train,
                                       rng=rng if train else None)
                return out
            fn = _xla.retrace_guard(fn, "MultiLayerNetwork.output")
            self._jit_cache[cache_key] = fn
        rng = _rng.fold_name(_rng.key(self.training.seed),
                             f"output_{self.iteration_count}") if train else None
        return fn(self.params, self._states_list(), x, rng)

    def feed_forward(self, x, train: bool = False) -> List[jax.Array]:
        """All layer activations, input first (parity: feedForward :627)."""
        x = jnp.asarray(x)
        acts, _ = self._forward(self.params, self._states_list(), x,
                                train=train, collect=True)
        return acts

    def rnn_clear_previous_state(self) -> None:
        self._rnn_state = None
        self._rnn_steps_fed = 0

    def rnn_time_step(self, x):
        """Streaming inference: feed one (or a few) timesteps, carrying h/c
        (parity: rnnTimeStep :2274). x: [b, f] or [b, t, f]."""
        x = jnp.asarray(x)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        if self._rnn_state is None:
            # seed the streaming carries (LSTM h/c zeros; attention K/V
            # caches when max_cache_t is set) — apply() distinguishes a
            # streaming call from plain output() by the presence of the
            # carried cache
            self._rnn_state = self._zero_rnn_carry(x.shape[0])
            self._rnn_steps_fed = 0
        # strict-mode streaming caches refuse the overflowing chunk
        # host-side, before it can touch the cache
        _precheck_streamed_steps(self, x.shape[1])
        cache_key = f"rnn_time_step@{_xla.trace_env_key()}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            @jax.jit
            def fn(params, states, x):
                out, new_states = self._forward(params, states, x,
                                                train=False)
                return out, self._extract_rnn_carry(new_states)
            fn = _xla.retrace_guard(fn, "MultiLayerNetwork.rnn_time_step")
            self._jit_cache[cache_key] = fn
        out, self._rnn_state = fn(self.params,
                                  self._states_list(self._rnn_state), x)
        # count only steps the cache actually absorbed (a rejected chunk
        # raised above and never touched it)
        _note_streamed_steps(self, x.shape[1])
        return out[:, 0, :] if (squeeze and out.ndim == 3) else out

    # ------------------------------------------------------------------
    # score + gradients (parity: computeGradientAndScore :1867)
    # ------------------------------------------------------------------

    def _loss_fn(self, params, states, x, y, mask, rng, *,
                 collect_stats=False):
        # collect_stats: falsy = plain loss; True or a health.StatsConfig
        # (whose act_sample bounds the activation reductions) additionally
        # returns per-layer activation summaries through the aux output
        if not self._has_loss_output:
            raise ValueError(
                "final layer has no loss (need OutputLayer/RnnOutputLayer/"
                "LossLayer to train with fit())")
        n_hidden = len(self.layers) - 1
        fwd = self._forward(
            params, states, x, train=True, rng=rng, mask=mask,
            upto=n_hidden, collect=collect_stats)
        if collect_stats:
            # collect=True keeps per-layer activations (bypassing remat —
            # stats collection trades that memory saving for visibility);
            # summarize each to 3 gradient-stopped scalars right here
            acts, new_states = fwd
            hidden = acts[-1]
            sample = getattr(collect_stats, "act_sample", 0)
            act_stats = {
                _layer_key(i): _health.act_summary(acts[i + 1], sample)
                for i in range(n_hidden)}
        else:
            hidden, new_states = fwd
        out_idx = len(self.layers) - 1
        out_layer = self.layers[out_idx]
        proc = self.conf.input_preprocessors.get(out_idx)
        out_mask = mask
        if proc is not None:
            lrng = None if rng is None else _rng.fold_name(rng,
                                                           _layer_key(out_idx))
            hidden = call_preprocessor(proc, hidden,
                                       minibatch_size=x.shape[0], rng=lrng)
            out_mask = proc.transform_mask(out_mask, minibatch_size=x.shape[0])
        score_arr = out_layer.compute_score_array(
            params[_layer_key(out_idx)], hidden, y, mask=out_mask,
            policy=self.policy)
        denom = _losses.masked_denominator(
            out_mask, y, score_arr.shape[0],
            sparse=_losses.is_sparse(out_layer.loss))
        loss = jnp.sum(score_arr) / denom
        loss = loss + self._reg_penalty(params)
        # layers may surface auxiliary objectives through their state
        # (e.g. MoELayer's load-balancing loss, pre-scaled by aux_weight)
        for st in new_states:
            if "aux_loss" in st:
                loss = loss + st["aux_loss"]
        # keep full precision under a float64 policy (gradient checking);
        # float32 otherwise (bf16 losses are too coarse for LR-sized steps)
        loss_dtype = (jnp.float64 if self.policy.param_dtype == jnp.float64
                      else jnp.float32)
        if collect_stats:
            return loss.astype(loss_dtype), (new_states, act_stats)
        return loss.astype(loss_dtype), new_states

    def score_for(self, x, y, mask=None) -> float:
        """Loss on a batch without updating (parity: score via
        computeGradientAndScore, eval mode)."""
        x, y = jnp.asarray(x), jnp.asarray(y)
        loss, _ = self._loss_fn(self.params, self._states_list(), x, y,
                                mask, None)
        return float(loss)

    def compute_gradient_and_score(self, x, y, mask=None):
        """(gradients, score) for one batch — no update applied."""
        x, y = jnp.asarray(x), jnp.asarray(y)
        (loss, _), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(
                self.params, self._states_list(), x, y, mask, None)
        return grads, float(loss)

    # ------------------------------------------------------------------
    # fit: a call's arguments become the one-array batch the loss takes;
    # the engine (TrainableNetwork) does the rest
    # ------------------------------------------------------------------

    @staticmethod
    def _as_batch(x, y, mask):
        return (jnp.asarray(x), jnp.asarray(y),
                None if mask is None else jnp.asarray(mask))

    @staticmethod
    def _batch_size(x) -> int:
        return int(x.shape[0])

    def fit(self, data, labels=None, *, epochs: int = 1, mask=None,
            coalesce: Optional[int] = None, session=None) -> None:
        """Train. `data` may be:
          - (features, labels) arrays (`labels=None` form passes labels here),
          - a DataSet (has .features/.labels),
          - an iterator yielding DataSets or (features, labels) tuples.

        ``coalesce``, ``session`` and how the loop overlaps staging,
        dispatch and listeners: ``TrainableNetwork._fit``."""
        self._fit(data, labels, mask, epochs, coalesce, session)

    def fit_batch(self, x, y, mask=None) -> float:
        """One minibatch update (tbptt-aware). Returns the score."""
        return self._fit_batch(*self._as_batch(x, y, mask))

    def fit_scan(self, xs, ys, masks=None):
        """Train on K pre-staged batches in one device dispatch.

        xs: [k, b, ...], ys: [k, b, ...], masks: optional [k, ...].
        Returns the per-step losses (device array, shape [k]).
        """
        return self._fit_scan(*self._as_batch(xs, ys, masks))

    def fit_repeated(self, x, y, k: int, mask=None):
        """Run K optimizer updates on one pre-staged batch in a single device
        dispatch (lax.scan over step indices). The on-chip analog of calling
        ``fit_batch(x, y)`` K times: same per-update rng folding, iteration
        counters, and listener firing — but one dispatch and one batch of HBM.
        Used for steady-state throughput measurement; returns [k] losses."""
        return self._fit_repeated(*self._as_batch(x, y, mask), k)

    def _tbptt_T(self, x):
        """The sequence length truncated BPTT chunks; None when it is off
        or the input is not a time series."""
        if self.conf.backprop_type != "truncated_bptt" or x.ndim != 3:
            return None
        return int(x.shape[1])

    @staticmethod
    def _tbptt_slice(x, y, mask, T, start, end):
        return (x[:, start:end],
                y[:, start:end] if y.ndim == 3 else y,
                mask[:, start:end] if (mask is not None and mask.ndim >= 2)
                else mask)

    def _zero_rnn_carry(self, batch):
        carry = []
        for layer in self.layers:
            # max_cache_t None = a streaming-capable layer (attention)
            # whose cache is disabled — it carries nothing
            if (hasattr(layer, "_zero_state")
                    and getattr(layer, "max_cache_t", True) is not None):
                h, c = layer._zero_state(batch, self.policy)
                carry.append({"h": h, "c": c})
            else:
                carry.append({})
        return carry

    # ------------------------------------------------------------------
    # layerwise pretraining (parity: MultiLayerNetwork.pretrain :1052 —
    # greedy per-layer AutoEncoder reconstruction / RBM CD-k before backprop)
    # ------------------------------------------------------------------

    def pretrain(self, data, labels=None, *, epochs: int = 1,
                 learning_rate: Optional[float] = None) -> None:
        """Greedy layerwise pretraining of AutoEncoder/RBM layers. Each
        pretrainable layer trains on the previous layers' activations
        (earlier layers frozen), then the stack moves one layer deeper."""
        if self.params is None:
            self.init()
        lr = float(learning_rate if learning_rate is not None
                   else self.training.learning_rate)
        pre_idx = [i for i, l in enumerate(self.layers)
                   if hasattr(l, "pretrain_loss")
                   or hasattr(l, "contrastive_divergence_grads")]
        if not pre_idx:
            return
        from .conf.pretrain import make_pretrain_step
        batches = list(self._as_batches(data, labels, None))
        for i in pre_idx:
            step = make_pretrain_step(self.layers[i], lr, self.policy)
            # earlier layers are frozen while layer i trains, so its input
            # activations are constant across epochs — but materializing all
            # of them is O(dataset) device memory, so only precompute when
            # the reuse (epochs>1) and the footprint (few batches) justify it
            cache_all = epochs > 1 and len(batches) <= 64
            hiddens = ([self._activation_upto(jnp.asarray(x), i)
                        for x, _, _ in batches] if cache_all else None)
            for e in range(epochs):
                for bi, (x, _, _) in enumerate(batches):
                    hidden = (hiddens[bi] if cache_all
                              else self._activation_upto(jnp.asarray(x), i))
                    rng = _rng.fold_name(
                        _rng.key(self.training.seed), f"pre_{i}_{e}_{bi}")
                    self.params[_layer_key(i)] = step(
                        self.params[_layer_key(i)], hidden, rng)

    def _activation_upto(self, x, layer_idx: int):
        """Input activations for layer `layer_idx` (frozen earlier layers)."""
        # trace_env_key: frozen-layer forwards trace the same attention
        # routing flags as output()/fit — a flag flip must retrace here too
        fn_key = f"acts_upto_{layer_idx}@{_xla.trace_env_key()}"
        fn = self._jit_cache.get(fn_key)
        if fn is None:
            @jax.jit
            def fn(params, states, x):
                cur, cur_mask = x, None
                minibatch = x.shape[0]
                for j in range(layer_idx):
                    proc = self.conf.input_preprocessors.get(j)
                    if proc is not None:
                        cur = proc(cur, minibatch_size=minibatch)
                    cur, _ = self.layers[j].apply(
                        params[_layer_key(j)], cur, state=states[j],
                        train=False, policy=self.policy)
                proc = self.conf.input_preprocessors.get(layer_idx)
                if proc is not None:
                    cur = proc(cur, minibatch_size=minibatch)
                return cur
            self._jit_cache[fn_key] = fn
        return fn(self.params, self._states_list(), x)


    # ------------------------------------------------------------------
    # evaluation bridge (full Evaluation class in eval/)
    # ------------------------------------------------------------------

    def evaluate(self, data, labels=None):
        """Classification evaluation over an iterator or (x, y) arrays.

        When the iterator yields DataSets carrying ``example_metadata``
        (``RecordReaderDataSetIterator(collect_metadata=True)``), the
        provenance flows into the returned Evaluation — ask it
        ``get_prediction_errors()`` for WHICH source records were
        misclassified (parity: ``Evaluation.java:195`` eval-with-metadata
        driven from the iterator)."""
        from ..eval import Evaluation
        from ..util.batching import iter_batches
        ev = Evaluation()
        # fit() no longer resets the source after its final epoch; revive
        # an exhausted resettable iterator here instead of silently
        # evaluating zero batches
        if (hasattr(data, "has_next") and not data.has_next()
                and hasattr(data, "reset")):
            data.reset()
        for x, y, m, meta in iter_batches(data, labels, with_meta=True):
            out = self.output(jnp.asarray(x))
            ev.eval(np.asarray(y), np.asarray(out),
                    mask=None if m is None else np.asarray(m),
                    metadata=meta)
        if hasattr(data, "reset"):
            data.reset()
        return ev

    # ------------------------------------------------------------------
    # serde bridge (full checkpoint container in util/serialization.py)
    # ------------------------------------------------------------------

    def set_params(self, params) -> None:
        self.params = params
