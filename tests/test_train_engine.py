"""The training engine both runtimes inherit (``nn/trainable.py``).

(a) what moved into the base is ONE function under both classes, so a later
    change cannot fork it again unnoticed;
(b) the names a trace and a metric see are the strings they were;
(c) a MultiLayerNetwork and the chain-shaped ComputationGraph of the same
    layers train alike through every path of the engine: the gate for
    rebuilding the sequential runtime as a chain graph (ROADMAP D2);
(d) ``_make_train_step()`` and the ``train_step_override`` slot behave as
    ``benchmarks/tests/test_control.py`` and ``ParallelWrapper`` use them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import rng as _rng
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization, DenseLayer, OutputLayer, RnnOutputLayer)
from deeplearning4j_tpu.nn.conf.recurrent import GravesLSTM
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, _layer_key
from deeplearning4j_tpu.nn.trainable import TrainableNetwork
from deeplearning4j_tpu.util import health
from deeplearning4j_tpu.util import metrics as _metrics

RUNTIMES = (MultiLayerNetwork, ComputationGraph)

# every method of the training side that moved whole into the base
SHARED = (
    "_make_train_step", "_train_step", "_make_train_scan",
    "_make_train_repeat", "_step_and_update", "_fire_iteration", "_fit",
    "_fit_batch", "_fit_tbptt", "_fit_scan", "_fit_repeated",
    "_reject_tbptt", "_as_batches", "set_listeners", "add_listener",
    "enable_health_stats", "disable_health_stats", "score", "num_params",
    "clone_params", "_reg_penalty", "_extract_rnn_carry",
)
# what stays a runtime's own: each class answers these for itself
OWN = (
    "_loss_fn", "_states", "_persist_states", "_zero_rnn_carry",
    "_param_layers", "_batch_size", "_tbptt_T", "_tbptt_slice",
    "_lr_multipliers", "fit", "fit_batch", "fit_scan", "fit_repeated", "init",
    "output", "score_for", "pretrain", "evaluate",
)


@pytest.mark.parametrize("name", SHARED)
def test_shared_method_has_one_definition(name):
    base = getattr(TrainableNetwork, name)
    for cls in RUNTIMES:
        assert getattr(cls, name) is base, f"{cls.__name__}.{name} forked"


@pytest.mark.parametrize("name", OWN)
def test_runtime_defines_its_own(name):
    assert not hasattr(TrainableNetwork, name)
    for cls in RUNTIMES:
        assert name in vars(cls), f"{cls.__name__} lacks {name}"


# ----------------------------------------------------------------------
# the same layers as a list and as a chain-shaped graph
# ----------------------------------------------------------------------

def _builder():
    return (NeuralNetConfiguration.builder().seed(7).updater("adam")
            .learning_rate(0.05).regularization(True).l2(1e-3))


def _dense_layers():
    return [DenseLayer(n_out=8, activation="tanh"), BatchNormalization(),
            OutputLayer(n_out=3, activation="softmax", loss="mcxent")]


def _lstm_layers():
    return [GravesLSTM(n_out=8, activation="tanh"),
            RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")]


def _pair(layers_fn, input_type, tbptt=None):
    """(MultiLayerNetwork, ComputationGraph) over the same layers with the
    same parameters; vertex ``i`` of the chain is named as the list's key."""
    lb = _builder().list()
    for layer in layers_fn():
        lb = lb.layer(layer)
    mconf = lb.set_input_type(input_type).build()
    gb = _builder().graph_builder().add_inputs("in")
    prev = "in"
    for i, layer in enumerate(layers_fn()):
        gb = gb.add_layer(_layer_key(i), layer, prev)
        prev = _layer_key(i)
    gconf = gb.set_outputs(prev).set_input_types(input_type).build()
    for conf in (mconf, gconf):
        if tbptt:
            conf.backprop_type = "truncated_bptt"
            conf.tbptt_fwd_length = tbptt
    mln = MultiLayerNetwork(mconf).init()
    graph = ComputationGraph(gconf).init()
    # a copy: the step donates the buffers it is given
    graph.params = mln.clone_params()
    return mln, graph


def _assert_same(mln, graph, *, atol=1e-6):
    for tree in ("params", "state", "updater_state"):
        a = jax.tree_util.tree_leaves(getattr(mln, tree))
        b = jax.tree_util.tree_leaves(getattr(graph, tree))
        assert len(a) == len(b), tree
        for p, q in zip(a, b):
            np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                       atol=atol, err_msg=tree)
    assert mln.iteration_count == graph.iteration_count
    assert mln._update_count == graph._update_count
    assert mln.score() == pytest.approx(graph.score(), abs=atol)


def _dense_batch(rng, k=None):
    lead = () if k is None else (k,)
    x = rng.normal(size=lead + (6, 5)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, lead + (6,))]
    return x, y


def _train(path, mln, graph, rng):
    """Drive both nets through one path of the engine; the losses of each."""
    if path == "fit_batch":
        x, y = _dense_batch(rng)
        return ([float(mln.fit_batch(x, y)) for _ in range(3)],
                [float(graph.fit_batch([x], [y])) for _ in range(3)])
    if path == "fit_scan":
        xs, ys = _dense_batch(rng, k=3)
        return (np.asarray(mln.fit_scan(xs, ys)),
                np.asarray(graph.fit_scan([xs], [ys])))
    x, y = _dense_batch(rng)
    return (np.asarray(mln.fit_repeated(x, y, 3)),
            np.asarray(graph.fit_repeated([x], [y], 3)))


@pytest.mark.parametrize("stats", [False, True], ids=["plain", "health"])
@pytest.mark.parametrize("path", ["fit_batch", "fit_scan", "fit_repeated"])
def test_chain_graph_trains_like_the_list(path, stats, rng):
    mln, graph = _pair(_dense_layers, InputType.feed_forward(5))
    if stats:
        mln.enable_health_stats()
        graph.enable_health_stats()
    l_mln, l_graph = _train(path, mln, graph, rng)
    np.testing.assert_allclose(l_mln, l_graph, atol=1e-6)
    assert mln._update_count == 3
    _assert_same(mln, graph)
    for net in (mln, graph):
        snap = health.latest_stats(net)
        if not stats:
            assert snap is None
            continue
        assert snap.model == type(net).__name__
        assert snap.iteration == 3
    if stats:
        a, b = (health.latest_stats(n).value() for n in (mln, graph))
        for p, q in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                       atol=1e-5)


class _Recorder:
    def __init__(self):
        self.seen = []

    def record_batch(self, n):
        self.seen.append(("batch", n))

    def iteration_done(self, model, iteration, score):
        self.seen.append((iteration, round(float(score), 5)))


def test_chain_graph_tbptt_like_the_list(rng):
    """A TBPTT batch over an LSTM: T=10 under fwd length 4 is three
    updates, one iteration (and listener call) a segment, in both."""
    mln, graph = _pair(_lstm_layers, InputType.recurrent(5), tbptt=4)
    x = rng.normal(size=(2, 10, 5)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, 10))]
    mask = np.ones((2, 10), np.float32)
    mask[1, 7:] = 0.0
    rec_m, rec_g = _Recorder(), _Recorder()
    mln.set_listeners(rec_m)
    graph.set_listeners([rec_g])
    l_mln = float(mln.fit_batch(x, y, mask=mask))
    l_graph = float(graph.fit_batch([x], [y], masks=[mask]))
    assert l_mln == pytest.approx(l_graph, abs=1e-6)
    assert mln._update_count == mln.iteration_count == 3
    _assert_same(mln, graph)
    assert rec_m.seen == rec_g.seen
    assert [e[0] for e in rec_m.seen] == ["batch", 1, "batch", 2, "batch", 3]
    # the carry the last segment left behind is in each runtime's own form
    assert [sorted(d) for d in mln._last_rnn_carry] == [["c", "h"], []]
    assert {k: sorted(d) for k, d in graph._last_rnn_carry.items()} == {
        _layer_key(0): ["c", "h"], _layer_key(1): []}
    for net, first in ((mln, x), (graph, [x])):
        with pytest.raises(ValueError, match=(
                r"fit_repeated does not chunk truncated BPTT \(T=10 > "
                r"tbptt_fwd_length=4\); use fit\(\)/fit_batch\(\), or "
                r"pre-chunk the sequences")):
            net.fit_repeated(first, y if net is mln else [y], 2)


# ----------------------------------------------------------------------
# names: XLA modules and the retrace-guard series
# ----------------------------------------------------------------------

def _retraces(fn_name):
    c = _metrics.REGISTRY.get("jit_retraces_total")
    return 0.0 if c is None else c.value(fn=fn_name)


def _step_args(net, x, y):
    rng = _rng.fold_name(_rng.key(net.training.seed), "update_0")
    return (net.params, net.updater_state, net._states(), x, y, None, rng,
            jnp.asarray(0, jnp.int32))


@pytest.mark.parametrize("stats", [False, True], ids=["plain", "health"])
@pytest.mark.parametrize("which", [0, 1], ids=[c.__name__ for c in RUNTIMES])
def test_names_a_trace_and_a_metric_see(which, stats, rng):
    net = _pair(_dense_layers, InputType.feed_forward(5))[which]
    cls = RUNTIMES[which].__name__
    x, y = _dense_batch(rng)
    xs, ys = _dense_batch(rng, k=2)
    if which:
        x, y, xs, ys = [x], [y], [xs], [ys]
    cfg = health.StatsConfig() if stats else None
    tail = "_stats" if stats else ""
    text = net._make_train_step(cfg).lower(*_step_args(net, x, y)).as_text()
    assert f"module @jit_{cls}_train_step{tail} " in text
    if stats:
        net.enable_health_stats()
    series = [f"{cls}.train_{kind}{tail}"
              for kind in ("step", "scan", "repeat")]
    before = [_retraces(s) for s in series]
    # the other variant of each program, whose pin must not move
    others = [s.removesuffix("_stats") if stats else s + "_stats"
              for s in series]
    other = [_retraces(s) for s in others]
    for _ in range(2):       # a second same-shape call compiles nothing
        net.fit_batch(x, y)
        net.fit_scan(xs, ys)
        net.fit_repeated(x, y, 2)
    assert [_retraces(s) for s in series] == [b + 1 for b in before]
    assert [_retraces(s) for s in others] == other
    key = "train_step@"
    assert sum(k.startswith(key) for k in net._jit_cache) == 1
    assert any(("|stats=" in k) == stats for k in net._jit_cache
               if k.startswith(key))


# ----------------------------------------------------------------------
# the override slot, as benchmarks/tests/test_control.py and
# ParallelWrapper use it
# ----------------------------------------------------------------------

@pytest.mark.parametrize("which", [0, 1], ids=[c.__name__ for c in RUNTIMES])
def test_train_step_override_slot(which, rng):
    net = _pair(_dense_layers, InputType.feed_forward(5))[which]
    x, y = _dense_batch(rng)
    if which:
        x, y = [x], [y]
    real = net._make_train_step()     # no argument: the plain jitted step
    assert net._train_step().__wrapped__.__name__.endswith("_train_step")
    calls = []

    def step(params, opt, states, inputs, labels, masks, rng, it):
        calls.append(1)               # traced once
        _, _, new_states, loss = real(params, opt, states, inputs, labels,
                                      masks, rng, it)
        return params, opt, new_states, loss

    net._jit_cache["train_step_override"] = jax.jit(step)
    before = net.clone_params()
    # an override is pinned: not stats-keyed, four outputs
    net.enable_health_stats()
    net.fit_batch(x, y)
    net.fit_batch(x, y)
    assert len(calls) == 1
    assert net._update_count == net.iteration_count == 2
    assert health.latest_stats(net) is None
    for p, q in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(net.params)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))
    net.disable_health_stats()
    net._jit_cache.pop("train_step_override")
    loss = float(net.fit_batch(x, y))
    assert np.isfinite(loss) and net._update_count == 3
    changed = [not np.array_equal(np.asarray(p), np.asarray(q))
               for p, q in zip(jax.tree_util.tree_leaves(before),
                               jax.tree_util.tree_leaves(net.params))]
    assert any(changed)
