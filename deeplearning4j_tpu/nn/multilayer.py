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
  - There is ONE jitted train step (donated params + optimizer state) that
    fuses: forward through all layers, loss + l1/l2, ``jax.grad`` backward,
    gradient normalization, and the updater apply. The reference's
    Solver → ConvexOptimizer → Updater call chain (``Solver.java:41``,
    ``StochasticGradientDescent.java:50-72``) collapses into this one
    XLA program — no per-layer dispatch, no JNI hops.
  - Backprop is autodiff through the forward functions; the reference's
    hand-written ``calcBackpropGradients`` reverse loop
    (``MultiLayerNetwork.java:1123-1190``) has no analog by design.
  - Non-param layer state (BatchNorm running stats) and recurrent carry
    (LSTM h/c) are threaded functionally and returned from the step.
  - The iteration counter is a traced scalar so LR schedules compile into
    the step instead of recompiling per iteration.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as _dtypes
from .. import losses as _losses
from .. import rng as _rng
from ..optimize import updaters as _updaters
from ..util import health as _health
from ..util import xla as _xla
from ..util.netutil import note_streamed_steps as _note_streamed_steps
from ..util.netutil import precheck_streamed_steps as _precheck_streamed_steps
from .conf.multi_layer import MultiLayerConfiguration
from .conf.preprocessors import call_preprocessor

Pytree = Any


def _layer_key(i: int) -> str:
    return f"layer_{i}"


class MultiLayerNetwork:
    """Runtime network over a :class:`MultiLayerConfiguration`."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.training = conf.training
        self.policy = _dtypes.policy_from_name(conf.training.dtype)
        self.params: Optional[Dict[str, Dict[str, jax.Array]]] = None
        self.state: Dict[str, Dict[str, jax.Array]] = {}
        self.updater_state: Optional[Pytree] = None
        self.listeners: List[Any] = []
        self.iteration_count = 0   # minibatches seen (listener-visible)
        self._update_count = 0     # parameter updates applied (tbptt chunks too)
        self.epoch_count = 0
        self._score: Optional[float] = None
        self._rnn_state: Optional[List[Dict[str, jax.Array]]] = None
        self._rnn_steps_fed = 0    # streaming steps since last cache reset
        self._updater = None
        self._jit_cache: Dict[str, Any] = {}
        # on-device training-health stats (util.health): None = off (the
        # default; the no-stats trace is untouched), a StatsConfig routes
        # fit_batch/fit_scan through the stats-collecting step variant
        self.health_stats: Optional[_health.StatsConfig] = None
        self._last_health_stats: Optional[_health.DeviceStats] = None

        out = self.layers[-1]
        self._has_loss_output = hasattr(out, "compute_score_array")

    # ------------------------------------------------------------------
    # init (parity: MultiLayerNetwork.init :368)
    # ------------------------------------------------------------------

    def init(self, key: Optional[jax.Array] = None) -> "MultiLayerNetwork":
        if key is None:
            key = _rng.key(self.training.seed)
        params, state = {}, {}
        for i, layer in enumerate(self.layers):
            lk = _rng.fold_name(key, _layer_key(i))
            params[_layer_key(i)] = layer.init_params(lk, self.policy)
            state[_layer_key(i)] = layer.init_state(self.policy)
        self.params = params
        self.state = state
        # persistent-state keys per layer (e.g. BN running stats), cached so
        # the hot fit loop never re-calls init_state just to read key names
        self._persistent_keys = [
            tuple(layer.init_state(self.policy).keys()) for layer in self.layers]
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

    def num_params(self) -> int:
        if self.params is None:
            raise ValueError("call init() first")
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

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

    def _persist_states(self, new_states):
        """Keep only persistent (init_state-declared) entries, e.g. BN stats."""
        for i, keys in enumerate(self._persistent_keys):
            if keys:
                self.state[_layer_key(i)] = {
                    k: new_states[i][k] for k in keys if k in new_states[i]}

    @staticmethod
    def _extract_rnn_carry(new_states):
        return [{k: v for k, v in st.items() if k in ("h", "c")}
                for st in new_states]

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

    def _reg_penalty(self, params):
        """l1 + 0.5*l2 penalties over each layer's regularized params
        (parity: BaseLayer.calcL1/calcL2; gradient of 0.5*l2*||W||^2 is l2*W,
        matching the reference's update)."""
        if not self.training.regularization:
            return 0.0
        acc_dtype = (jnp.float64 if self.policy.param_dtype == jnp.float64
                     else jnp.float32)
        total = 0.0
        for i, layer in enumerate(self.layers):
            l1 = float(layer.l1 or 0.0)
            l2 = float(layer.l2 or 0.0)
            if l1 == 0.0 and l2 == 0.0:
                continue
            lp = params[_layer_key(i)]
            for name in layer.regularized_params():
                if name not in lp:
                    continue
                w = lp[name].astype(acc_dtype)
                if l1:
                    total = total + l1 * jnp.sum(jnp.abs(w))
                if l2:
                    total = total + 0.5 * l2 * jnp.sum(jnp.square(w))
        return total

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

    def score(self) -> Optional[float]:
        """Score from the most recent fit iteration (parity: score() :1900).
        Lazily syncs: the fit loop keeps the loss on device so step dispatch
        pipelines; the device→host transfer happens here, on demand."""
        if self._score is None:
            return None
        self._score = float(self._score)
        return self._score

    def compute_gradient_and_score(self, x, y, mask=None):
        """(gradients, score) for one batch — no update applied."""
        x, y = jnp.asarray(x), jnp.asarray(y)
        (loss, _), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(
                self.params, self._states_list(), x, y, mask, None)
        return grads, float(loss)

    # ------------------------------------------------------------------
    # the jitted train step
    # ------------------------------------------------------------------

    def _make_train_step(self, stats_cfg: Optional[_health.StatsConfig] = None):
        t = self.training
        norm_kind = t.gradient_normalization
        norm_thr = float(t.gradient_normalization_threshold)
        updater = self._updater
        collect = stats_cfg is not None

        def step(params, opt_state, states, x, y, mask, rng, iteration):
            loss, new_states, grads_raw, act_stats = \
                _health.value_grad_with_stats(
                    self._loss_fn, stats_cfg, params, states, x, y, mask, rng)
            grads = _updaters.normalize_gradients(grads_raw, norm_kind,
                                                  norm_thr)
            deltas, opt_state = updater.update(grads, opt_state, iteration)
            params = _updaters.apply_updates(params, deltas)
            if not collect:
                return params, opt_state, new_states, loss
            # per-layer health stats in the SAME dispatch: raw (pre-norm)
            # grads, the applied deltas, and the post-update params
            stats = _health.model_stats(params, grads_raw, deltas,
                                        act_stats, stats_cfg, loss=loss)
            return params, opt_state, new_states, loss, stats

        # the XLA module carries the retrace-guard name, so a device
        # trace tells the train step from any other jit_step
        step.__name__ = ("MultiLayerNetwork_train_step_stats" if collect
                         else "MultiLayerNetwork_train_step")
        return jax.jit(step, donate_argnums=(0, 1),
                       compiler_options=_xla.train_step_options())

    def _train_step(self):
        # explicit override first (ParallelWrapper installs its sharded
        # SPMD step here; an override is pinned, not trace-env-keyed and
        # not stats-keyed — sharded steps do not collect health stats)
        fn = self._jit_cache.get("train_step_override")
        if fn is not None:
            return fn
        cfg = self.health_stats
        suffix = "" if cfg is None else f"|stats={cfg.trace_key()}"
        cache_key = f"train_step@{_xla.trace_env_key()}{suffix}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            # distinct guard name for the stats variant: the no-stats
            # trace's retrace pin (1 compile per signature) must not
            # move when stats are toggled on and back off
            name = ("MultiLayerNetwork.train_step" if cfg is None
                    else "MultiLayerNetwork.train_step_stats")
            fn = _xla.retrace_guard(self._make_train_step(cfg), name)
            self._jit_cache[cache_key] = fn
        return fn

    def _make_train_scan(self, stats_cfg: Optional[_health.StatsConfig] = None):
        """K train steps fused into ONE XLA program via lax.scan — the
        idiomatic TPU inner loop: no per-step host dispatch, the whole
        sequence of updates runs on-chip. Used by fit_scan(). With
        ``stats_cfg`` the scan also emits the health-stats pytree of the
        LAST step (stats stay per-dispatch-window, like the score)."""
        t = self.training
        norm_kind = t.gradient_normalization
        norm_thr = float(t.gradient_normalization_threshold)
        updater = self._updater
        base = _rng.key(t.seed)
        collect = stats_cfg is not None

        def one(carry, batch):
            params, opt_state, states, it = carry
            x, y, mask = batch
            # per-step rng derived from the TRACED counter — computing keys
            # eagerly from the host-side update count bakes fresh constants
            # into the program and forces a recompile every call
            rng = jax.random.fold_in(base, it)
            loss, new_states, grads_raw, act_stats = \
                _health.value_grad_with_stats(
                    self._loss_fn, stats_cfg, params, states, x, y, mask, rng)
            grads = _updaters.normalize_gradients(grads_raw, norm_kind,
                                                  norm_thr)
            deltas, opt_state = updater.update(grads, opt_state, it)
            params = _updaters.apply_updates(params, deltas)
            # carry structure must stay fixed: keep exactly the persistent
            # state keys (BN stats); transient rnn carry (h/c) resets per batch
            kept = [
                {k: new_states[i].get(k, v) for k, v in st_old.items()}
                for i, st_old in enumerate(states)]
            if collect:
                stats = _health.model_stats(params, grads_raw, deltas,
                                            act_stats, stats_cfg, loss=loss)
                return (params, opt_state, kept, it + 1), (loss, stats)
            return (params, opt_state, kept, it + 1), loss

        def scan_steps(params, opt_state, states, xs, ys, masks, it0):
            (params, opt_state, states, _), ys_out = jax.lax.scan(
                one, (params, opt_state, states, it0), (xs, ys, masks),
                unroll=_xla.scan_unroll())
            if collect:
                losses, stats_seq = ys_out
                last_stats = jax.tree_util.tree_map(lambda a: a[-1],
                                                    stats_seq)
                return params, opt_state, states, losses, last_stats
            return params, opt_state, states, ys_out

        return jax.jit(scan_steps, donate_argnums=(0, 1),
                       compiler_options=_xla.train_step_options())

    def fit_scan(self, xs, ys, masks=None):
        """Train on K pre-staged batches in one device dispatch.

        xs: [k, b, ...], ys: [k, b, ...], masks: optional [k, ...].
        Returns the per-step losses (device array, shape [k]).
        """
        xs, ys = jnp.asarray(xs), jnp.asarray(ys)
        self._reject_tbptt(xs[0], "fit_scan")
        k = xs.shape[0]
        if masks is not None:
            masks = jnp.asarray(masks)
        cfg = self.health_stats
        suffix = "" if cfg is None else f"|stats={cfg.trace_key()}"
        cache_key = f"train_scan@{_xla.trace_env_key()}{suffix}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            name = ("MultiLayerNetwork.train_scan" if cfg is None
                    else "MultiLayerNetwork.train_scan_stats")
            fn = _xla.retrace_guard(self._make_train_scan(cfg), name)
            self._jit_cache[cache_key] = fn
        it0 = jnp.asarray(self._update_count, jnp.int32)
        states = self._states_list()
        out = fn(
            self.params, self.updater_state, states, xs, ys, masks, it0)
        if cfg is not None:
            params, opt_state, new_states, losses, stats = out
            self._last_health_stats = _health.DeviceStats(
                stats, iteration=self.iteration_count + k,
                model="MultiLayerNetwork")
        else:
            params, opt_state, new_states, losses = out
        self.params = params
        self.updater_state = opt_state
        self._update_count += k
        self._persist_states(new_states)
        self._score = losses[-1]
        # replay per-step losses so listener/stats semantics (score history,
        # throughput via record_batch) match fit()/fit_batch for k updates
        if self.listeners:
            batch_size = int(xs.shape[1])
            per_step = np.asarray(losses)
            for i in range(k):
                self._fire_iteration(batch_size, per_step[i])
        else:
            self.iteration_count += k
        return losses

    def _make_train_repeat(self, stats_cfg: Optional[_health.StatsConfig] = None):
        """K train steps on ONE closed-over batch via lax.scan over step
        indices — constant HBM regardless of K. Used by fit_repeated().
        With ``stats_cfg`` the scan also emits the health-stats pytree of
        the LAST step (same window semantics as fit_scan)."""
        t = self.training
        norm_kind = t.gradient_normalization
        norm_thr = float(t.gradient_normalization_threshold)
        updater = self._updater
        base = _rng.key(t.seed)
        collect = stats_cfg is not None

        def one(x, y, mask, carry, it):
            params, opt_state, states = carry
            rng = jax.random.fold_in(base, it)
            loss, new_states, grads_raw, act_stats = \
                _health.value_grad_with_stats(
                    self._loss_fn, stats_cfg, params, states, x, y, mask, rng)
            grads = _updaters.normalize_gradients(grads_raw, norm_kind,
                                                  norm_thr)
            deltas, opt_state = updater.update(grads, opt_state, it)
            params = _updaters.apply_updates(params, deltas)
            kept = [
                {k: new_states[i].get(k, v) for k, v in st_old.items()}
                for i, st_old in enumerate(states)]
            if collect:
                stats = _health.model_stats(params, grads_raw, deltas,
                                            act_stats, stats_cfg, loss=loss)
                return (params, opt_state, kept), (loss, stats)
            return (params, opt_state, kept), loss

        def repeat_steps(params, opt_state, states, x, y, mask, it0, k):
            # unroll (default 2): XLA removes inter-iteration carry copies
            # between the paired bodies (measured ~1.2 ms/step on ResNet-50
            # @ v5e); DL4JTPU_SCAN_UNROLL overrides for tuning
            (params, opt_state, states), ys_out = jax.lax.scan(
                functools.partial(one, x, y, mask), (params, opt_state, states),
                it0 + jnp.arange(k), unroll=_xla.scan_unroll())
            if collect:
                losses, stats_seq = ys_out
                last_stats = jax.tree_util.tree_map(lambda a: a[-1],
                                                    stats_seq)
                return params, opt_state, states, losses, last_stats
            return params, opt_state, states, ys_out

        return jax.jit(repeat_steps, donate_argnums=(0, 1, 2),
                       static_argnums=(7,),
                       compiler_options=_xla.train_step_options())

    def fit_repeated(self, x, y, k: int, mask=None):
        """Run K optimizer updates on one pre-staged batch in a single device
        dispatch (lax.scan over step indices). The on-chip analog of calling
        ``fit_batch(x, y)`` K times: same per-update rng folding, iteration
        counters, and listener firing — but one dispatch and one batch of HBM.
        Used for steady-state throughput measurement; returns [k] losses."""
        x, y = jnp.asarray(x), jnp.asarray(y)
        self._reject_tbptt(x, "fit_repeated")
        if mask is not None:
            mask = jnp.asarray(mask)
        cfg = self.health_stats
        suffix = "" if cfg is None else f"|stats={cfg.trace_key()}"
        cache_key = f"train_repeat@{_xla.trace_env_key()}{suffix}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            name = ("MultiLayerNetwork.train_repeat" if cfg is None
                    else "MultiLayerNetwork.train_repeat_stats")
            fn = _xla.retrace_guard(self._make_train_repeat(cfg), name)
            self._jit_cache[cache_key] = fn
        it0 = jnp.asarray(self._update_count, jnp.int32)
        out = fn(
            self.params, self.updater_state, self._states_list(), x, y,
            mask, it0, int(k))
        if cfg is not None:
            params, opt_state, new_states, losses, stats = out
            self._last_health_stats = _health.DeviceStats(
                stats, iteration=self.iteration_count + int(k),
                model="MultiLayerNetwork")
        else:
            params, opt_state, new_states, losses = out
        self.params = params
        self.updater_state = opt_state
        self._update_count += int(k)
        self._persist_states(new_states)
        self._score = losses[-1]
        if self.listeners:
            batch_size = int(x.shape[0])
            per_step = np.asarray(losses)
            for i in range(int(k)):
                self._fire_iteration(batch_size, per_step[i])
        else:
            self.iteration_count += int(k)
        return losses

    # ------------------------------------------------------------------
    # fit (parity: fit(DataSetIterator) :1037, doTruncatedBPTT :1079)
    # ------------------------------------------------------------------

    def set_listeners(self, *listeners) -> None:
        # Accept both varargs and a single collection (ref Model.setListeners
        # has both overloads).
        if len(listeners) == 1 and isinstance(listeners[0], (list, tuple)):
            listeners = tuple(listeners[0])
        self.listeners = list(listeners)

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)

    def enable_health_stats(self, config=True) -> None:
        """Compute per-layer training-health stats (util.health) INSIDE
        the train dispatch from the next fit call on: the stats-keyed jit
        cache traces a separate program, so the cached no-stats trace is
        untouched and toggling back off reuses it without a recompile.
        Consumers read :func:`util.health.latest_stats` — one host sync
        per read, the snapshot carries the step loss."""
        self.health_stats = _health.StatsConfig.coerce(config)

    def disable_health_stats(self) -> None:
        self.health_stats = None

    def fit(self, data, labels=None, *, epochs: int = 1, mask=None,
            coalesce: Optional[int] = None, session=None) -> None:
        """Train. `data` may be:
          - (features, labels) arrays (`labels=None` form passes labels here),
          - a DataSet (has .features/.labels),
          - an iterator yielding DataSets or (features, labels) tuples.

        The loop is dispatch-asynchronous: host batches are device-staged
        by a background thread (``util.ingest.stage``; ``DL4JTPU_INGEST=0``
        disables), losses stay on device behind a bounded in-flight window
        (``DL4JTPU_MAX_INFLIGHT``), and listeners receive a ``LazyScore``
        that syncs only when read. ``coalesce=K`` (or ``DL4JTPU_COALESCE_K``)
        additionally fuses runs of K same-shape batches into one fit_scan
        dispatch — opt-in, because the fused path derives per-step rng
        differently. Epoch resets happen lazily at the START of each
        subsequent epoch, so the final epoch never restarts the producer
        just to throw the work away. ``session`` attaches a
        ``util.durable.DurableSession`` (cursor tracking, async
        checkpoints, preemption drain, watchdog).
        """
        from ..util.ingest import run_fit_loop
        if self.params is None:
            self.init()
        run_fit_loop(self, data, labels, mask, epochs, coalesce,
                     model_label="MultiLayerNetwork", session=session)

    @staticmethod
    def _as_batches(data, labels=None, mask=None):
        from ..util.batching import iter_batches
        return iter_batches(data, labels, mask)

    def fit_batch(self, x, y, mask=None) -> float:
        """One minibatch update (tbptt-aware). Returns the score."""
        x, y = jnp.asarray(x), jnp.asarray(y)
        if mask is not None:
            mask = jnp.asarray(mask)
        if (self.conf.backprop_type == "truncated_bptt" and x.ndim == 3
                and x.shape[1] > self.conf.tbptt_fwd_length):
            return self._fit_tbptt(x, y, mask)
        loss = self._step_and_update(x, y, mask, rnn_state=None)
        self._fire_iteration(x.shape[0], loss)
        return loss

    def _reject_tbptt(self, x, api: str) -> None:
        """The fused-scan paths run ONE full-sequence BPTT update per batch;
        silently doing that under a truncated_bptt config would change both
        memory behavior and optimization semantics — refuse loudly."""
        if (self.conf.backprop_type == "truncated_bptt" and x.ndim == 3
                and x.shape[1] > self.conf.tbptt_fwd_length):
            raise ValueError(
                f"{api} does not chunk truncated BPTT (T={x.shape[1]} > "
                f"tbptt_fwd_length={self.conf.tbptt_fwd_length}); use "
                "fit()/fit_batch(), or pre-chunk the sequences")

    def _fit_tbptt(self, x, y, mask) -> float:
        """Truncated BPTT: slice [b,t,..] into fwd-length chunks, carrying
        recurrent state across chunks with gradients stopped at the boundary
        (parity: doTruncatedBPTT :1079)."""
        length = self.conf.tbptt_fwd_length
        T = x.shape[1]
        rnn_state = self._zero_rnn_carry(x.shape[0])
        loss = 0.0
        for start in range(0, T, length):
            end = min(start + length, T)
            xs = x[:, start:end]
            ys = y[:, start:end] if y.ndim == 3 else y
            ms = mask[:, start:end] if (mask is not None and mask.ndim >= 2) else mask
            loss = self._step_and_update(xs, ys, ms, rnn_state=rnn_state)
            rnn_state = self._last_rnn_carry
            # one iteration (and listener firing) per TBPTT segment, same as
            # the graph runtime and the reference's doTruncatedBPTT
            self._fire_iteration(x.shape[0], loss)
        return loss

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

    def _step_and_update(self, x, y, mask, rnn_state) -> float:
        # keyed on the update counter so each tbptt chunk gets a fresh dropout
        # stream and the updater sees a monotonically advancing step
        rng = _rng.fold_name(_rng.key(self.training.seed),
                             f"update_{self._update_count}")
        states = self._states_list(rnn_state)
        it = jnp.asarray(self._update_count, jnp.int32)
        out = self._train_step()(
            self.params, self.updater_state, states, x, y, mask, rng, it)
        # sharded overrides always return 4 outputs; only the stats
        # variant of the owned step returns the fifth (the stats pytree)
        if len(out) == 5:
            params, opt_state, new_states, loss, stats = out
            self._last_health_stats = _health.DeviceStats(
                stats, iteration=self.iteration_count + 1,
                model="MultiLayerNetwork")
        else:
            params, opt_state, new_states, loss = out
        self.params = params
        self.updater_state = opt_state
        self._update_count += 1
        # stop-gradient boundary for tbptt: carry values, not graph
        self._last_rnn_carry = jax.tree_util.tree_map(
            jax.lax.stop_gradient, self._extract_rnn_carry(new_states))
        self._persist_states(new_states)
        # keep the loss on device — no host sync in the hot loop; score()
        # and listeners that read it pay the transfer lazily
        self._score = loss
        return loss

    def _fire_iteration(self, batch_size, loss):
        self.iteration_count += 1
        if not self.listeners:
            return
        # listeners get a LazyScore: the device loss syncs to host only
        # when (and if) a listener actually reads it — frequency-gated
        # listeners pay one sync per window, silent ones pay zero
        from ..util.ingest import as_listener_score
        score = as_listener_score(loss)
        for l in self.listeners:
            if hasattr(l, "record_batch"):
                l.record_batch(batch_size)
            l.iteration_done(self, self.iteration_count, score)

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

    def clone_params(self):
        """Deep copy — the train step donates the live param buffers, so an
        aliasing 'clone' would be invalidated by the next fit_batch."""
        return jax.tree_util.tree_map(lambda p: jnp.array(p), self.params)

    def set_params(self, params) -> None:
        self.params = params
