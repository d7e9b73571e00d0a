"""ParallelWrapper: data-parallel training over a device mesh.

Parity: reference ``ParallelWrapper.java:37-204`` (single-node multi-device,
parameter averaging every ``averagingFrequency`` iterations, updater-state
averaging at ``:163-186``) and ``ParameterAveragingTrainingMaster.java:763-832``
(the Spark multi-node variant of the same algorithm).

See package docstring for the two modes (sync SPMD vs local-SGD). Both
modes are SINGLE-PROCESS programs over one mesh: every replica lives in
this process, so a replica cannot "die" independently. The cross-PROCESS
analog of the local-SGD mode — where a host can be preempted mid-window
and rejoin — is :mod:`deeplearning4j_tpu.parallel.elastic`, which also
composes with this class: an ``ElasticTrainer`` built with a mesh runs
its per-host local steps through a sync-mode ``ParallelWrapper``
(``stepper_factory``), nesting in-host data parallelism under the
fleet-level bounded-staleness rounds.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import rng as _rng
from ..ops.attention import sequence_sharding
from ..optimize import updaters as _updaters
from .mesh import data_parallel_mesh
from .stats import maybe_time_phase

Pytree = Any


def _tree_map(f, *trees):
    # treat None as a leaf so optional masks ride through untouched
    return jax.tree_util.tree_map(f, *trees, is_leaf=lambda x: x is None)


from ..util.netutil import is_graph as _is_graph


def _net_states(net):
    """states in whatever structure the net's _loss_fn expects."""
    return net._states_map() if _is_graph(net) else net._states_list()


def _batchify(net, x, y, mask):
    """Convert a batch to the form the net's _loss_fn expects: arrays for
    MultiLayerNetwork, lists of arrays for ComputationGraph (multi-in/out)."""
    def conv(v):
        if v is None:
            return None
        if isinstance(v, (list, tuple)):
            return [None if a is None else jnp.asarray(a) for a in v]
        return jnp.asarray(v)
    x, y, mask = conv(x), conv(y), conv(mask)
    if _is_graph(net):
        x = x if isinstance(x, list) else [x]
        y = y if isinstance(y, list) else [y]
        if mask is not None and not isinstance(mask, list):
            mask = [mask]
    return x, y, mask


def _batch_dim(x) -> int:
    leaf = x[0] if isinstance(x, (list, tuple)) else x
    return int(leaf.shape[0])


class ParallelWrapper:
    """Wrap an (initialized) network for data-parallel training.

    Usage (mirrors the reference's builder)::

        net = MultiLayerNetwork(conf).init()
        pw = ParallelWrapper(net, mesh=None, averaging_frequency=1)
        pw.fit(iterator, epochs=2)        # trains net in place

    ``averaging_frequency=1`` → per-step gradient all-reduce (sync SPMD).
    ``averaging_frequency=k>1`` → independent per-replica steps; params +
    updater state + layer states averaged every k iterations.
    """

    def __init__(self, net, mesh: Optional[Mesh] = None,
                 averaging_frequency: int = 1, stats=None,
                 skip_nonfinite_budget: Optional[int] = None):
        if net.params is None:
            net.init()
        self.net = net
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        if "data" not in self.mesh.axis_names:
            raise ValueError(f"mesh must have a 'data' axis, got {self.mesh.axis_names}")
        self.averaging_frequency = int(averaging_frequency)
        self.n_devices = self.mesh.shape["data"]
        self._local: Optional[_LocalSgdState] = None
        # resilience: with a budget set, steps whose gradients (or loss)
        # are non-finite are skipped ON DEVICE (old params/opt-state kept)
        # and counted on the host, raising once the budget is exhausted.
        # The per-step finiteness read forces a host sync, so this is an
        # opt-in robustness feature, off (None) by default.
        self.nonfinite_guard = None
        if skip_nonfinite_budget is not None:
            from ..util.resilience import NonFiniteGuard
            self.nonfinite_guard = NonFiniteGuard(
                int(skip_nonfinite_budget), net)
        # phase timing (parity: SparkTrainingStats / StatsCalculationHelper);
        # stats=True builds a default collector, or pass a TrainingStats
        if stats is True:
            from .stats import TrainingStats
            stats = TrainingStats()
        self.stats = stats or None
        if self.averaging_frequency == 1:
            # install the sharded step as the net's pinned train-step
            # override: net.fit then runs SPMD transparently (the
            # override slot bypasses the trace-env cache keying)
            net._jit_cache["train_step_override"] = self._make_sync_step()
            # lay the net's trees over the mesh now, as the step's
            # out_shardings will leave them: otherwise the second step
            # sees differently-placed inputs and compiles a second time
            net.params, net.updater_state, net.state = jax.device_put(
                (net.params, net.updater_state, net.state),
                NamedSharding(self.mesh, P()))
        elif self.averaging_frequency < 1:
            raise ValueError("averaging_frequency must be >= 1")

    # ------------------------------------------------------------------
    # sync mode: one SPMD step, batch sharded, params replicated
    # ------------------------------------------------------------------

    def _make_sync_step(self):
        net = self.net
        t = net.training
        norm_kind = t.gradient_normalization
        norm_thr = float(t.gradient_normalization_threshold)
        updater = net._updater
        repl = NamedSharding(self.mesh, P())
        bsh = NamedSharding(self.mesh, P("data"))

        guard = self.nonfinite_guard

        def step(params, opt_state, states, x, y, mask, rng, iteration):
            # trace-time: tells attention the batch is sharded over
            # "data", so a Pallas kernel (which the compiler will not
            # partition by itself) is wrapped in a shard_map over it
            with sequence_sharding(self.mesh, None, "data"):
                (loss, new_states), grads = jax.value_and_grad(
                    net._loss_fn, has_aux=True)(params, states, x, y, mask,
                                                rng)
            if guard is not None:
                ok = jnp.logical_and(_updaters.all_finite(grads),
                                     _updaters.all_finite(loss))
            grads = _updaters.normalize_gradients(grads, norm_kind, norm_thr)
            deltas, opt_state2 = updater.update(grads, opt_state, iteration)
            params2 = _updaters.apply_updates(params, deltas)
            if guard is None:
                return params2, opt_state2, new_states, loss
            # divergent step: keep the old params/opt-state/states (a pure
            # no-op update); the host counts the skip against the budget
            params2 = _updaters.select_tree(ok, params2, params)
            opt_state2 = _updaters.select_tree(ok, opt_state2, opt_state)
            new_states = _updaters.select_tree(ok, new_states, states)
            return params2, opt_state2, new_states, loss, ok

        n_out = 5 if guard is not None else 4
        jitted = jax.jit(
            step,
            donate_argnums=(0, 1),
            in_shardings=(repl, repl, repl, bsh, bsh, bsh, repl, repl),
            out_shardings=tuple([repl] * n_out))

        n = self.n_devices

        def checked(params, opt_state, states, x, y, mask, rng, iteration):
            bs = _batch_dim(x)
            if bs % n:
                raise ValueError(
                    f"batch size {bs} not divisible by the {n}-device "
                    "'data' mesh axis (sync SPMD mode shards the batch "
                    "evenly across devices)")
            out = jitted(params, opt_state, states, x, y, mask, rng,
                         iteration)
            if guard is None:
                return out
            params, opt_state, new_states, loss, ok = out
            try:
                # the returned (selected) params are the valid tree — the
                # inputs were donated; attribution replays against them
                guard.step(ok, batch=(x, y, mask), params=params)
            except Exception:
                # the caller assigns net state only after we return, but
                # the inputs were donated — hand the (unchanged, freshly
                # selected) trees back so the net stays checkpointable
                net.params = params
                net.updater_state = opt_state
                raise
            return params, opt_state, new_states, loss

        return checked

    # ------------------------------------------------------------------
    # local-SGD mode: stacked replicas via shard_map + periodic averaging
    # ------------------------------------------------------------------

    def _ensure_local(self) -> "_LocalSgdState":
        if self._local is None:
            self._local = _LocalSgdState(self)
        return self._local

    # ------------------------------------------------------------------
    # fit API (delegates to net.fit in sync mode)
    # ------------------------------------------------------------------

    def fit(self, data, labels=None, *, epochs: int = 1, mask=None) -> None:
        if self.averaging_frequency == 1 and self.stats is None:
            if _is_graph(self.net):
                if mask is not None:
                    raise ValueError(
                        "ComputationGraph: pass masks via DataSet batches, "
                        "not the mask kwarg")
                self.net.fit(data, labels, epochs=epochs)
            else:
                self.net.fit(data, labels, epochs=epochs, mask=mask)
            return
        if self.averaging_frequency == 1 and _is_graph(self.net) \
                and mask is not None:
            raise ValueError(
                "ComputationGraph: pass masks via DataSet batches, "
                "not the mask kwarg")
        local = (self._ensure_local()
                 if self.averaging_frequency > 1 else None)
        net = self.net
        from ..util import ingest as _ingest
        single = (labels is not None or hasattr(data, "shape")
                  or hasattr(data, "features"))
        for epoch in range(epochs):
            # lazy epoch-start reset (final epoch never restarts the
            # producer); revive an iterator a previous fit() exhausted
            if hasattr(data, "reset") and (
                    epoch > 0 or (hasattr(data, "has_next")
                                  and not data.has_next())):
                data.reset()
            for l in net.listeners:
                l.on_epoch_start(net, net.epoch_count)
            source = net._as_batches(data, labels, mask)
            staged = None
            if (not single and _ingest.staging_enabled()
                    and not _ingest.already_staged(data)):
                # prefetch-only staging (device_put=False): the sharded
                # replica step places batches with its own shardings, so
                # ingest here overlaps host batch PREP, not placement
                staged = _ingest.stage(source, stage_name="parallel",
                                       device_put=False)
                source = staged
            batch_iter = iter(source)
            n_batches = 0
            try:
                while True:
                    with maybe_time_phase(self.stats, "batch_prep"):
                        batch = next(batch_iter, None)
                    if batch is None:
                        break
                    n_batches += 1
                    x, y, m = batch
                    if local is not None:
                        self._timed_local_step(local, x, y, m)
                    else:
                        self._timed_sync_step(x, y, m)
            finally:
                if staged is not None:
                    staged.close()
            if n_batches == 0 and epoch > 0:
                raise ValueError(
                    f"epoch {epoch} yielded no batches — the data iterator is "
                    "exhausted and not resettable; pass arrays/DataSets or a "
                    "resettable iterator for multi-epoch fit")
            for l in net.listeners:
                l.on_epoch_end(net, net.epoch_count)
            net.epoch_count += 1
        if local is not None:
            self._timed_sync_to_net(local)

    def _timed_sync_step(self, x, y, mask):
        holder = []
        with maybe_time_phase(self.stats, "step", holder):
            loss = self.net.fit_batch(x, y, mask)
            holder.append(loss)
        return loss

    def _timed_local_step(self, local, x, y, mask):
        holder = []
        with maybe_time_phase(self.stats, "step", holder):
            loss = local.fit_batch(x, y, mask)
            holder.append(loss)
        if local._steps_since_avg == 0:
            self._timed_sync_to_net(local)
        return loss

    def _timed_sync_to_net(self, local):
        holder = []
        with maybe_time_phase(self.stats, "sync_to_net", holder):
            local.sync_to_net()
            holder.append(self.net.params)

    def fit_batch(self, x, y, mask=None) -> float:
        """One update. In local-SGD mode replicas step independently and the
        average happens only every ``averaging_frequency`` calls (matching the
        reference's semantics); the wrapped net's params are refreshed at each
        averaging point — call :meth:`finish` (or ``average_now``) after the
        last batch to flush a partial window."""
        if self.averaging_frequency == 1:
            return self._timed_sync_step(x, y, mask)
        return self._timed_local_step(self._ensure_local(), x, y, mask)

    def finish(self) -> None:
        """Flush local-SGD replicas into the wrapped net (average + sync)."""
        if self._local is not None:
            self._local.sync_to_net()

    def average_now(self) -> None:
        """Force a parameter average (local-SGD mode)."""
        if self._local is not None:
            self._local.average()
            self._local.sync_to_net()


class _LocalSgdState:
    """Per-replica parameter copies + the shard_map step (local-SGD mode)."""

    def __init__(self, pw: ParallelWrapper):
        self.pw = pw
        self.net = pw.net
        self.mesh = pw.mesh
        self.n = pw.n_devices
        self.k = pw.averaging_frequency
        self._steps_since_avg = 0
        net = self.net
        stack = lambda a: jnp.broadcast_to(a[None], (self.n,) + a.shape)
        dev_sh = NamedSharding(self.mesh, P("data"))
        self.params = jax.device_put(_tree_map(stack, net.params), dev_sh)
        self.opt_state = jax.device_put(_tree_map(stack, net.updater_state), dev_sh)
        self.states = jax.device_put(_tree_map(stack, _net_states(net)), dev_sh)
        self._step = self._make_step()
        self._avg = self._make_avg()

    def _make_step(self):
        from jax import shard_map

        net = self.net
        t = net.training
        norm_kind = t.gradient_normalization
        norm_thr = float(t.gradient_normalization_threshold)
        updater = net._updater
        mesh = self.mesh

        guard = self.pw.nonfinite_guard

        def per_replica(params, opt_state, states, x, y, mask, rng, iteration):
            # leading replica axis has block size 1 on each device — drop it
            params0 = _tree_map(lambda a: a[0], params)
            opt_state0 = _tree_map(lambda a: a[0], opt_state)
            states0 = _tree_map(lambda a: a[0], states)
            # distinct dropout stream per replica
            rng = (None if rng is None
                   else jax.random.fold_in(rng, jax.lax.axis_index("data")))
            (loss, new_states), grads = jax.value_and_grad(
                net._loss_fn, has_aux=True)(params0, states0, x, y, mask, rng)
            if guard is not None:
                ok = jnp.logical_and(_updaters.all_finite(grads),
                                     _updaters.all_finite(loss))
            grads = _updaters.normalize_gradients(grads, norm_kind, norm_thr)
            deltas, opt_state1 = updater.update(grads, opt_state0, iteration)
            params1 = _updaters.apply_updates(params0, deltas)
            if guard is not None:
                # this replica diverged: its update becomes a no-op (the
                # next averaging point re-syncs it with healthy replicas)
                params1 = _updaters.select_tree(ok, params1, params0)
                opt_state1 = _updaters.select_tree(ok, opt_state1, opt_state0)
                new_states = _updaters.select_tree(ok, new_states, states0)
            put_back = lambda a: a[None] if hasattr(a, "shape") else a
            out = (_tree_map(put_back, params1),
                   _tree_map(put_back, opt_state1),
                   _tree_map(put_back, new_states), loss[None])
            if guard is not None:
                out = out + (ok[None],)
            return out

        Pd, Pr = P("data"), P()
        out_specs = (Pd, Pd, Pd, Pd) + ((Pd,) if guard is not None else ())
        step = shard_map(
            per_replica, mesh=mesh,
            in_specs=(Pd, Pd, Pd, Pd, Pd, Pd, Pr, Pr),
            out_specs=out_specs)
        return jax.jit(step, donate_argnums=(0, 1))

    def _make_avg(self):
        def avg(tree):
            return _tree_map(
                lambda a: (jnp.broadcast_to(jnp.mean(a, axis=0, keepdims=True),
                                            a.shape)
                           if hasattr(a, "shape") else a), tree)
        return jax.jit(avg, donate_argnums=(0,))

    def fit_batch(self, x, y, mask=None) -> float:
        net = self.net
        x, y, mask = _batchify(net, x, y, mask)
        bs = _batch_dim(x)
        if bs % self.n:
            raise ValueError(
                f"batch size {bs} not divisible by the {self.n}-device "
                "data axis")
        rng = _rng.fold_name(_rng.key(net.training.seed),
                             f"update_{net._update_count}")
        it = jnp.asarray(net._update_count, jnp.int32)
        out = self._step(
            self.params, self.opt_state, self.states, x, y, mask, rng, it)
        guard = self.pw.nonfinite_guard
        if guard is not None:
            self.params, self.opt_state, self.states, loss, oks = out
            n_bad = int(oks.size) - int(jnp.sum(oks))
            try:
                guard.step(n_bad == 0,
                           detail=(f"{n_bad}/{oks.size} replicas diverged; "
                                   "re-synced at next averaging"
                                   if n_bad else ""))
            except Exception:
                # budget exhausted mid-window: average the healthy
                # replicas' progress back into the net so the caller can
                # still checkpoint (mirrors the sync path's guarantee)
                self.sync_to_net()
                raise
        else:
            self.params, self.opt_state, self.states, loss = out
        net._update_count += 1
        self._steps_since_avg += 1
        if self._steps_since_avg >= self.k:
            self.average()
        score = jnp.mean(loss)  # stays on device; score() syncs lazily
        net._score = score
        net._fire_iteration(bs, score)
        return score

    def average(self) -> None:
        """Parameter + updater-state + layer-state averaging
        (parity: ``ParallelWrapper.java:145,:163-186``)."""
        holder = []
        with maybe_time_phase(self.pw.stats, "average", holder):
            self.params = self._avg(self.params)
            self.opt_state = self._avg(self.opt_state)
            self.states = self._avg(self.states)
            holder.append(self.params)
        self._steps_since_avg = 0

    def sync_to_net(self) -> None:
        """Propagate replica-0 (= averaged) values back to the wrapped net."""
        if self._steps_since_avg:
            self.average()
        take0 = lambda a: a[0] if hasattr(a, "shape") else a
        net = self.net
        net.params = _tree_map(take0, self.params)
        net.updater_state = _tree_map(take0, self.opt_state)
        states = _tree_map(take0, self.states)
        net._persist_states(states)
