"""TrainableNetwork: the one training engine under both runtimes.

``MultiLayerNetwork`` and ``ComputationGraph`` inherit this class. It owns
how a train step is built, cached, named, dispatched and accounted; a
runtime keeps what is its own: walking its topology and computing its loss.

There is ONE jitted train step (donated params + optimizer state) that
fuses forward, loss + l1/l2, ``jax.grad`` backward, gradient normalization
and the updater apply. The reference's Solver → ConvexOptimizer → Updater
call chain (``Solver.java:41``, ``StochasticGradientDescent.java:50-72``)
collapses into this one XLA program: no per-layer dispatch, no JNI hops.
The iteration counter is a traced scalar, so LR schedules compile into the
step instead of recompiling per iteration.

The batch ``(x, y, mask)`` is an opaque pytree here: one array each for the
sequential runtime, lists of arrays for the graph. Whatever depends on that
shape is asked of the runtime, which defines

  - ``_loss_fn(params, states, x, y, mask, rng, *, collect_stats=False)``;
  - ``_states(rnn_state=None)``, the per-layer state container the loss
    takes (a list or a dict of dicts), and ``_persist_states(new_states)``;
  - ``_zero_rnn_carry(batch)``, the zero h/c carry in that container's form;
  - ``_param_layers()``, the ``(parameter key, layer)`` pairs;
  - ``fit``, ``fit_batch``, ``fit_scan``, ``fit_repeated``: the public
    signatures (their keywords differ), each one line onto ``_fit``,
    ``_fit_batch``, ``_fit_scan``, ``_fit_repeated`` here;
  - ``_batch_size(x)``, the examples in one batch's inputs;
  - ``_tbptt_T(x)``, the sequence length truncated BPTT chunks (None when
    it is off or nothing is temporal), and ``_tbptt_slice(x, y, mask, T,
    start, end)``, one segment of a batch.

Every name a trace or a metric sees (XLA module, retrace-guard series,
``DeviceStats.model``, the fit loop's label) derives from the runtime's
class name, here and nowhere else.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as _dtypes
from .. import rng as _rng
from ..optimize import updaters as _updaters
from ..util import health as _health
from ..util import xla as _xla

Pytree = Any


def _map_states(fn, states, *rest):
    """``fn`` over each layer's state dict of the runtime's container (a list
    or a dict of dicts), zipped with the same entry of each of ``rest``."""
    return jax.tree_util.tree_map(fn, states, *rest,
                                  is_leaf=lambda node: node is not states)


class TrainableNetwork:
    """What a runtime network over a configuration shares with the other."""

    def __init__(self, conf):
        self.conf = conf
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
        self._updater = None
        self._rnn_state: Optional[Pytree] = None
        self._rnn_steps_fed = 0    # streaming steps since last cache reset
        self._jit_cache: Dict[str, Any] = {}
        # on-device training-health stats (util.health): None = off (the
        # default; the no-stats trace is untouched), a StatsConfig routes
        # fit_batch/fit_scan through the stats-collecting step variant
        self.health_stats: Optional[_health.StatsConfig] = None
        self._last_health_stats: Optional[_health.DeviceStats] = None

    def num_params(self) -> int:
        if self.params is None:
            raise ValueError("call init() first")
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def clone_params(self):
        """Deep copy — the train step donates the live param buffers, so an
        aliasing 'clone' would be invalidated by the next fit_batch."""
        return jax.tree_util.tree_map(lambda p: jnp.array(p), self.params)

    @staticmethod
    def _extract_rnn_carry(new_states):
        return _map_states(
            lambda st: {k: v for k, v in st.items() if k in ("h", "c")},
            new_states)

    # ------------------------------------------------------------------
    # score (parity: score() MultiLayerNetwork.java:1900, calcL1/calcL2)
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
        for key, layer in self._param_layers():
            l1 = float(layer.l1 or 0.0)
            l2 = float(layer.l2 or 0.0)
            if l1 == 0.0 and l2 == 0.0:
                continue
            lp = params[key]
            for name in layer.regularized_params():
                if name not in lp:
                    continue
                w = lp[name].astype(acc_dtype)
                if l1:
                    total = total + l1 * jnp.sum(jnp.abs(w))
                if l2:
                    total = total + 0.5 * l2 * jnp.sum(jnp.square(w))
        return total

    def score(self) -> Optional[float]:
        """Score from the most recent fit iteration. Lazily syncs: the fit
        loop keeps the loss on device so step dispatch pipelines; the
        device→host transfer happens here, on demand."""
        if self._score is None:
            return None
        self._score = float(self._score)
        return self._score

    # ------------------------------------------------------------------
    # the jitted train programs: one step, K steps scanned over K batches,
    # K steps repeated on one batch
    # ------------------------------------------------------------------

    def _make_update(self, stats_cfg: Optional[_health.StatsConfig]):
        """The body all three programs share: loss and gradients, gradient
        normalization, the updater. Returns ``(params, opt_state, new_states,
        loss, stats)``; ``stats`` is None unless ``stats_cfg`` collects the
        per-layer health stats in the SAME dispatch, from the raw (pre-norm)
        grads, the applied deltas and the post-update params."""
        t = self.training
        norm_kind = t.gradient_normalization
        norm_thr = float(t.gradient_normalization_threshold)
        updater = self._updater

        def update(params, opt_state, states, x, y, mask, rng, it):
            loss, new_states, grads_raw, act_stats = \
                _health.value_grad_with_stats(
                    self._loss_fn, stats_cfg, params, states, x, y, mask, rng)
            grads = _updaters.normalize_gradients(grads_raw, norm_kind,
                                                  norm_thr)
            deltas, opt_state = updater.update(grads, opt_state, it)
            params = _updaters.apply_updates(params, deltas)
            stats = None
            if stats_cfg is not None:
                stats = _health.model_stats(params, grads_raw, deltas,
                                            act_stats, stats_cfg, loss=loss)
            return params, opt_state, new_states, loss, stats

        return update

    def _make_train_step(self, stats_cfg: Optional[_health.StatsConfig] = None):
        update = self._make_update(stats_cfg)

        def step(params, opt_state, states, x, y, mask, rng, iteration):
            out = update(params, opt_state, states, x, y, mask, rng, iteration)
            return out if stats_cfg is not None else out[:4]

        # the XLA module carries the retrace-guard name, so a device
        # trace tells the train step from any other jit_step
        step.__name__ = type(self).__name__ + (
            "_train_step" if stats_cfg is None else "_train_step_stats")
        return jax.jit(step, donate_argnums=(0, 1),
                       compiler_options=_xla.train_step_options())

    def _scanned(self, stats_cfg):
        """``(one, finish)`` for the two fused programs: ``one(carry, x, y,
        mask, it)`` is a scan body over ``carry = (params, opt_state,
        states)``, ``finish`` shapes the scan's result into the program's
        outputs. With ``stats_cfg`` the scan also emits the health-stats
        pytree of the LAST step (stats stay per-dispatch-window, like the
        score)."""
        update = self._make_update(stats_cfg)
        base = _rng.key(self.training.seed)

        def one(carry, x, y, mask, it):
            params, opt_state, states = carry
            # per-step rng derived from the TRACED counter — computing keys
            # eagerly from the host-side update count bakes fresh constants
            # into the program and forces a recompile every call
            rng = jax.random.fold_in(base, it)
            params, opt_state, new_states, loss, stats = update(
                params, opt_state, states, x, y, mask, rng, it)
            # carry structure must stay fixed: keep exactly the persistent
            # state keys (BN stats); transient rnn carry (h/c) resets per batch
            kept = _map_states(
                lambda old, new: {k: new.get(k, v) for k, v in old.items()},
                states, new_states)
            return (params, opt_state, kept), (
                loss if stats is None else (loss, stats))

        def finish(carry, ys_out):
            if stats_cfg is None:
                return (*carry, ys_out)
            losses, stats_seq = ys_out
            return (*carry, losses,
                    jax.tree_util.tree_map(lambda a: a[-1], stats_seq))

        return one, finish

    def _make_train_scan(self, stats_cfg: Optional[_health.StatsConfig] = None):
        """K train steps fused into ONE XLA program via lax.scan — the
        idiomatic TPU inner loop: no per-step host dispatch, the whole
        sequence of updates runs on-chip. Used by fit_scan()."""
        step, finish = self._scanned(stats_cfg)

        def one(carry, batch):
            params, opt_state, states, it = carry
            x, y, mask = batch
            carry, out = step((params, opt_state, states), x, y, mask, it)
            return (*carry, it + 1), out

        def scan_steps(params, opt_state, states, xs, ys, masks, it0):
            (*carry, _), ys_out = jax.lax.scan(
                one, (params, opt_state, states, it0), (xs, ys, masks),
                unroll=_xla.scan_unroll())
            return finish(carry, ys_out)

        return jax.jit(scan_steps, donate_argnums=(0, 1),
                       compiler_options=_xla.train_step_options())

    def _make_train_repeat(self, stats_cfg: Optional[_health.StatsConfig] = None):
        """K train steps on ONE closed-over batch via lax.scan over step
        indices — constant HBM regardless of K. Used by fit_repeated()."""
        step, finish = self._scanned(stats_cfg)

        def repeat_steps(params, opt_state, states, x, y, mask, it0, k):
            # unroll (default 2): XLA removes inter-iteration carry copies
            # between the paired bodies (measured ~1.2 ms/step on ResNet-50
            # @ v5e); DL4JTPU_SCAN_UNROLL overrides for tuning
            carry, ys_out = jax.lax.scan(
                lambda carry, it: step(carry, x, y, mask, it),
                (params, opt_state, states), it0 + jnp.arange(k),
                unroll=_xla.scan_unroll())
            return finish(carry, ys_out)

        return jax.jit(repeat_steps, donate_argnums=(0, 1, 2),
                       static_argnums=(7,),
                       compiler_options=_xla.train_step_options())

    def _train_fn(self, kind: str):
        """The cached, retrace-guarded program ``_make_train_<kind>`` builds
        (``step``, ``scan`` or ``repeat``), for the current trace environment
        and health-stats setting."""
        cfg = self.health_stats
        suffix = "" if cfg is None else f"|stats={cfg.trace_key()}"
        # trace_env_key: flash-attention routing flags are read at trace
        # time, so the compiled program is only reused while they match
        cache_key = f"train_{kind}@{_xla.trace_env_key()}{suffix}"
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            # distinct guard name for the stats variant: the no-stats
            # trace's retrace pin (1 compile per signature) must not
            # move when stats are toggled on and back off
            name = f"{type(self).__name__}.train_{kind}" + (
                "" if cfg is None else "_stats")
            make = getattr(self, f"_make_train_{kind}")
            fn = _xla.retrace_guard(make(cfg), name)
            self._jit_cache[cache_key] = fn
        return fn

    def _train_step(self):
        # explicit override first (ParallelWrapper installs its sharded
        # SPMD step here; an override is pinned, not trace-env-keyed and
        # not stats-keyed — sharded steps do not collect health stats)
        fn = self._jit_cache.get("train_step_override")
        if fn is not None:
            return fn
        return self._train_fn("step")

    # ------------------------------------------------------------------
    # dispatch and accounting
    # ------------------------------------------------------------------

    def _commit_update(self, out, k: int):
        """Take over a train dispatch's outputs after ``k`` updates; returns
        ``(new_states, loss)``."""
        # sharded overrides always return 4 outputs; only the stats
        # variant of an owned program returns the fifth (the stats pytree)
        if len(out) == 5:
            params, opt_state, new_states, loss, stats = out
            self._last_health_stats = _health.DeviceStats(
                stats, iteration=self.iteration_count + k,
                model=type(self).__name__)
        else:
            params, opt_state, new_states, loss = out
        self.params = params
        self.updater_state = opt_state
        self._update_count += k
        self._persist_states(new_states)
        return new_states, loss

    def _step_and_update(self, x, y, mask, rnn_state):
        # keyed on the update counter so each tbptt chunk gets a fresh dropout
        # stream and the updater sees a monotonically advancing step
        rng = _rng.fold_name(_rng.key(self.training.seed),
                             f"update_{self._update_count}")
        it = jnp.asarray(self._update_count, jnp.int32)
        out = self._train_step()(
            self.params, self.updater_state, self._states(rnn_state),
            x, y, mask, rng, it)
        new_states, loss = self._commit_update(out, 1)
        # stop-gradient boundary for tbptt: carry values, not graph
        self._last_rnn_carry = jax.tree_util.tree_map(
            jax.lax.stop_gradient, self._extract_rnn_carry(new_states))
        # keep the loss on device — no host sync in the hot loop; score()
        # and listeners that read it pay the transfer lazily
        self._score = loss
        return loss

    def _fit_fused(self, kind: str, batch, k: int, batch_size: int, *static):
        """K updates in one dispatch of the ``kind`` program over ``batch``;
        returns the per-step losses (device array, shape [k])."""
        it0 = jnp.asarray(self._update_count, jnp.int32)
        out = self._train_fn(kind)(
            self.params, self.updater_state, self._states(), *batch, it0,
            *static)
        _, losses = self._commit_update(out, k)
        self._score = losses[-1]
        # replay per-step losses so listener/stats semantics (score history,
        # throughput via record_batch) match fit()/fit_batch for k updates
        if self.listeners:
            per_step = np.asarray(losses)
            for i in range(k):
                self._fire_iteration(batch_size, per_step[i])
        else:
            self.iteration_count += k
        return losses

    def _fit_scan(self, xs, ys, masks):
        first = jax.tree_util.tree_map(lambda a: a[0], xs)
        self._reject_tbptt(first, "fit_scan")
        return self._fit_fused("scan", (xs, ys, masks), self._batch_size(xs),
                               self._batch_size(first))

    def _fit_repeated(self, x, y, mask, k: int):
        self._reject_tbptt(x, "fit_repeated")
        return self._fit_fused("repeat", (x, y, mask), int(k),
                               self._batch_size(x), int(k))

    def _fire_iteration(self, batch_size, loss):
        self.iteration_count += 1
        if not self.listeners:
            return
        # listeners get a LazyScore: the device loss syncs to host only
        # when (and if) a listener actually reads it — frequency-gated
        # listeners pay one sync per window, silent ones pay zero (host
        # scalars from the fused-scan replay pass through)
        from ..util.ingest import as_listener_score
        score = as_listener_score(loss)
        for l in self.listeners:
            if hasattr(l, "record_batch"):
                l.record_batch(batch_size)
            l.iteration_done(self, self.iteration_count, score)

    # ------------------------------------------------------------------
    # fit (parity: fit(DataSetIterator) MultiLayerNetwork.java:1037 and
    # ComputationGraph.java:614-760, doTruncatedBPTT :1079)
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

    def _fit(self, data, labels, mask, epochs: int, coalesce: Optional[int],
             session) -> None:
        """The loop under each runtime's ``fit`` (whose signatures differ:
        the graph's takes no ``mask``, its masks ride in DataSet batches).

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
                     model_label=type(self).__name__, session=session)

    @staticmethod
    def _as_batches(data, labels=None, mask=None):
        from ..util.batching import iter_batches
        return iter_batches(data, labels, mask)

    def _reject_tbptt(self, x, api: str) -> None:
        """The fused-scan paths run ONE full-sequence BPTT update per batch;
        silently doing that under a truncated_bptt config would change both
        memory behavior and optimization semantics — refuse loudly."""
        T = self._tbptt_T(x)
        if T is not None and T > self.conf.tbptt_fwd_length:
            raise ValueError(
                f"{api} does not chunk truncated BPTT (T={T} > "
                f"tbptt_fwd_length={self.conf.tbptt_fwd_length}); use "
                "fit()/fit_batch(), or pre-chunk the sequences")

    def _fit_batch(self, x, y, mask):
        """One minibatch update (tbptt-aware). Returns the score."""
        T = self._tbptt_T(x)
        if T is not None and T > self.conf.tbptt_fwd_length:
            return self._fit_tbptt(x, y, mask, T)
        loss = self._step_and_update(x, y, mask, None)
        self._fire_iteration(self._batch_size(x), loss)
        return loss

    def _fit_tbptt(self, x, y, mask, T: int):
        """Truncated BPTT: slice [b, t, ..] into fwd-length chunks, carrying
        every recurrent layer's h/c across chunks with gradients stopped at
        the boundary (parity: doTruncatedBPTT :1079)."""
        length = self.conf.tbptt_fwd_length
        batch = self._batch_size(x)
        rnn_state = self._zero_rnn_carry(batch)
        loss = 0.0
        for start in range(0, T, length):
            segment = self._tbptt_slice(x, y, mask, T, start,
                                        min(start + length, T))
            loss = self._step_and_update(*segment, rnn_state)
            rnn_state = self._last_rnn_carry
            # one iteration (and listener firing) per TBPTT segment, matching
            # the reference's doTruncatedBPTT accounting: listeners see every
            # iteration number, not one per full-sequence batch
            self._fire_iteration(batch, loss)
        return loss
