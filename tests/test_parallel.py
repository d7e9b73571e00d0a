"""Data-parallel training tests on the virtual 8-device CPU mesh.

Parity model: reference ParallelWrapper tests + the Spark correctness oracle
(train locally vs distributed with averagingFrequency=1, single worker →
identical params; SURVEY §4 'Spark correctness oracle').
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization, DenseLayer, OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import (
    ParallelWrapper, create_mesh, data_parallel_mesh)


def _conf(updater="sgd", lr=0.1, seed=42):
    return (NeuralNetConfiguration.builder()
            .seed(seed).updater(updater).learning_rate(lr)
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8)).build())


def _data(rng, n=64):
    x = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8, 3))
    y = np.eye(3, dtype=np.float32)[np.argmax(x @ w, axis=1)]
    return x, y


def _leaves(t):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(t)]


class TestMesh:
    def test_data_parallel_mesh(self):
        mesh = data_parallel_mesh(8)
        assert mesh.shape["data"] == 8

    def test_create_mesh_2d(self):
        mesh = create_mesh({"data": 4, "model": 2})
        assert mesh.shape == {"data": 4, "model": 2}

    def test_too_many_devices_raises(self):
        with pytest.raises(ValueError, match="devices"):
            data_parallel_mesh(1000)


class TestSyncDataParallel:
    def test_loss_decreases(self, rng):
        x, y = _data(rng)
        net = MultiLayerNetwork(_conf("adam", 1e-2)).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8))
        s0 = net.score_for(x, y)
        for _ in range(30):
            pw.fit_batch(x, y)
        assert net.score() < s0 * 0.7

    def test_matches_single_device(self, rng):
        """The distributed correctness oracle: 8-device sync == 1-device."""
        x, y = _data(rng)
        ref = MultiLayerNetwork(_conf("sgd", 0.1)).init()
        for _ in range(5):
            ref.fit_batch(x, y)

        net = MultiLayerNetwork(_conf("sgd", 0.1)).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8))
        for _ in range(5):
            pw.fit_batch(x, y)

        for a, b in zip(_leaves(ref.params), _leaves(net.params)):
            assert np.allclose(a, b, atol=1e-4), "sync dp diverged from single-device"

    def test_batchnorm_global_stats(self, rng):
        """BN under SPMD: batch statistics are computed over the GLOBAL batch
        (XLA inserts the cross-device reduction)."""
        x, y = _data(rng, n=64)
        conf = (NeuralNetConfiguration.builder().seed(1).updater("sgd")
                .learning_rate(0.05)
                .list()
                .layer(DenseLayer(n_out=8, activation="identity"))
                .layer(BatchNormalization())
                .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
                .set_input_type(InputType.feed_forward(8)).build())
        ref = MultiLayerNetwork(conf).init()
        for _ in range(3):
            ref.fit_batch(x, y)
        import copy
        net = MultiLayerNetwork(copy.deepcopy(conf)).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8))
        for _ in range(3):
            pw.fit_batch(x, y)
        for a, b in zip(_leaves(ref.state), _leaves(net.state)):
            assert np.allclose(a, b, atol=1e-4), "BN running stats diverged"

    def test_fit_iterator(self, rng):
        x, y = _data(rng, n=96)
        from deeplearning4j_tpu.datasets import ArrayDataSetIterator
        it = ArrayDataSetIterator(x, y, 32)
        net = MultiLayerNetwork(_conf("adam", 1e-2)).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8))
        pw.fit(it, epochs=3)
        assert net.iteration_count == 9


class TestLocalSgd:
    def test_loss_decreases_and_syncs(self, rng):
        x, y = _data(rng)
        net = MultiLayerNetwork(_conf("sgd", 0.1)).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8),
                             averaging_frequency=4)
        local = pw._ensure_local()
        s0 = net.score_for(x, y)
        for _ in range(12):
            local.fit_batch(x, y)
        local.sync_to_net()
        assert net.score_for(x, y) < s0 * 0.8
        # after sync all replicas hold identical params
        for leaf in jax.tree_util.tree_leaves(local.params):
            arr = np.asarray(leaf)
            assert np.allclose(arr, arr[0:1], atol=1e-6)

    def test_averaging_frequency_1_equals_sync_semantics(self, rng):
        """k=1 local-SGD (average every step) on identical shards == sync.
        With each replica seeing a DIFFERENT shard, k=1 averaging of SGD
        updates equals the sync gradient-mean step for linear updaters."""
        x, y = _data(rng, n=64)
        ref = MultiLayerNetwork(_conf("sgd", 0.1)).init()
        pw_ref = ParallelWrapper(ref, mesh=data_parallel_mesh(8))
        for _ in range(3):
            pw_ref.fit_batch(x, y)

        net = MultiLayerNetwork(_conf("sgd", 0.1)).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8),
                             averaging_frequency=2)
        # run 2-step cycles → average; SGD with per-shard loss means is NOT
        # identical to sync in general, so just assert it converges sanely
        for _ in range(6):
            pw._ensure_local().fit_batch(x, y)
        pw._ensure_local().sync_to_net()
        assert np.isfinite(net.score_for(x, y))

    def test_indivisible_batch_raises(self, rng):
        x, y = _data(rng, n=30)  # 30 % 8 != 0
        net = MultiLayerNetwork(_conf()).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8),
                             averaging_frequency=2)
        with pytest.raises(ValueError, match="divisible"):
            pw._ensure_local().fit_batch(x, y)

    def test_fit_loop_with_listeners(self, rng):
        from deeplearning4j_tpu.optimize import CollectScoresIterationListener
        x, y = _data(rng, n=64)
        net = MultiLayerNetwork(_conf("adam", 1e-2)).init()
        collector = CollectScoresIterationListener()
        net.set_listeners(collector)
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8),
                             averaging_frequency=2)
        pw.fit((x, y), epochs=4)
        assert len(collector.scores) == 4


class TestFitBatchAveragingSemantics:
    def test_fit_batch_averages_exactly_every_k(self, rng):
        """averaging_frequency=4 via the public fit_batch: replicas diverge
        (each sees its own shard) and are averaged exactly on steps 4, 8, ...;
        the wrapped net's params refresh only at those points."""
        x, y = _data(rng, n=64)
        net = MultiLayerNetwork(_conf("sgd", 0.1)).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8),
                             averaging_frequency=4)
        snapshot = _leaves(net.params)  # last published (averaged) params
        for i in range(1, 9):
            pw.fit_batch(x, y)
            local = pw._local
            leaves = [np.asarray(l)
                      for l in jax.tree_util.tree_leaves(local.params)]
            replicas_equal = all(
                np.allclose(a, np.broadcast_to(a[0:1], a.shape), atol=1e-6)
                for a in leaves)
            if i % 4 == 0:
                assert replicas_equal, f"step {i}: replicas not averaged"
                snapshot = _leaves(net.params)
            else:
                assert not replicas_equal, \
                    f"step {i}: replicas averaged too early"
                # net params must still hold the last averaged snapshot
                for a, b in zip(_leaves(net.params), snapshot):
                    assert np.allclose(a, b), \
                        f"step {i}: net params updated mid-window"

    def test_finish_flushes_partial_window(self, rng):
        x, y = _data(rng, n=64)
        net = MultiLayerNetwork(_conf("sgd", 0.1)).init()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8),
                             averaging_frequency=4)
        p_init = _leaves(net.params)
        pw.fit_batch(x, y)
        pw.fit_batch(x, y)  # partial window: net params still p_init
        for a, b in zip(_leaves(net.params), p_init):
            assert np.allclose(a, b)
        pw.finish()
        changed = any(not np.allclose(a, b)
                      for a, b in zip(_leaves(net.params), p_init))
        assert changed, "finish() did not flush the partial window"

    def test_sync_mode_indivisible_batch_raises(self, rng):
        x, y = _data(rng, n=30)  # 30 % 8 != 0
        net = MultiLayerNetwork(_conf()).init()
        ParallelWrapper(net, mesh=data_parallel_mesh(8))
        with pytest.raises(ValueError, match="divisible"):
            net.fit_batch(x, y)


class TestGraphParallel:
    """ParallelWrapper over a ComputationGraph (reference ParallelWrapper
    accepts any Model; see ADVICE r2 #2)."""

    @staticmethod
    def _graph_net(seed=42):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater("sgd").learning_rate(0.1)
                .graph_builder()
                .add_inputs("in")
                .add_layer("d1", DenseLayer(n_out=16, activation="tanh"), "in")
                .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                              loss="mcxent"), "d1")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(8))
                .build())
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        return ComputationGraph(conf).init()

    def test_sync_matches_single_device(self, rng):
        x, y = _data(rng, n=64)
        ref = self._graph_net()
        for _ in range(5):
            ref.fit_batch(x, y)
        net = self._graph_net()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8))
        for _ in range(5):
            pw.fit_batch(x, y)
        for a, b in zip(_leaves(ref.params), _leaves(net.params)):
            assert np.allclose(a, b, atol=1e-5), \
                "graph sync dp diverged from single-device"

    def test_sync_step_runs_the_flash_kernel_under_shard_map(
            self, rng, monkeypatch):
        """The TPU compiler refuses to partition a Mosaic kernel by itself
        ("wrap the call in a shard_map" — met on four real chips, PR 21),
        so the batch-sharded step must run the flash route inside a
        shard_map over "data". Same losses as one device."""
        from deeplearning4j_tpu.models import transformer_lm
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        from deeplearning4j_tpu.ops import attention
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        wrapped = []
        inner = attention._flash_over_batch
        monkeypatch.setattr(
            attention, "_flash_over_batch",
            lambda *a, **kw: wrapped.append(kw["batch_axis"]) or inner(
                *a, **kw))
        mk = lambda: ComputationGraph(transformer_lm(   # noqa: E731
            11, n_layers=1, d_model=16, n_heads=2, d_ff=32, updater="sgd",
            learning_rate=0.01, seed=3, input_ids=True)).init()
        ids = rng.integers(0, 11, (4, 129)).astype(np.int32)
        x, y = ids[:, :-1], ids[:, 1:]
        ref = mk()
        want = [float(ref.fit_batch(x, y)) for _ in range(2)]
        assert not wrapped                 # one device: the plain kernel
        pw = ParallelWrapper(mk(), mesh=data_parallel_mesh(4))
        got = [float(pw.fit_batch(x, y)) for _ in range(2)]
        assert wrapped == ["data"]         # traced once, wrapped
        assert got == pytest.approx(want, rel=1e-5)

    def test_local_sgd_runs_and_averages(self, rng):
        x, y = _data(rng, n=64)
        net = self._graph_net()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8),
                             averaging_frequency=2)
        s0 = net.score_for([x], [y])
        for _ in range(8):
            pw.fit_batch(x, y)
        pw.finish()
        assert net.score_for([x], [y]) < s0

    def test_sync_fit_iterator(self, rng):
        x, y = _data(rng, n=96)
        from deeplearning4j_tpu.datasets import ArrayDataSetIterator
        net = self._graph_net()
        pw = ParallelWrapper(net, mesh=data_parallel_mesh(8))
        pw.fit(ArrayDataSetIterator(x, y, 32), epochs=2)
        assert net.iteration_count == 6


class TestPhaseStats:
    """Phase-timing stats (parity: SparkTrainingStats / StatsUtils
    exportStatsAsHtml, reference dl4j-spark stats/)."""

    def test_sync_master_collects_phases(self, rng, tmp_path):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
        from deeplearning4j_tpu.parallel.training_master import (
            SyncTrainingMaster)
        x, y = _data(rng)
        net = MultiLayerNetwork(_conf()).init()
        master = SyncTrainingMaster(collect_stats=True, blocking_stats=True)
        trainer = master.build(net)
        it = ListDataSetIterator(
            [DataSet(x[i:i + 16], y[i:i + 16]) for i in range(0, 64, 16)])
        trainer.fit(it, epochs=2)
        s = trainer.stats()
        assert s is not None
        assert set(s) >= {"batch_prep", "step"}
        assert s["step"]["count"] == 8
        assert s["step"]["total_ms"] > 0
        for k in ("count", "total_ms", "mean_ms", "min_ms", "max_ms"):
            assert k in s["step"]
        # HTML timeline export (parity: StatsUtils.java:69-92)
        out = tmp_path / "timeline.html"
        trainer.export_stats_html(str(out))
        body = out.read_text()
        assert "svg" in body and "step" in body

    def test_paramavg_master_collects_average_phase(self, rng):
        from deeplearning4j_tpu.parallel.training_master import (
            ParameterAveragingTrainingMaster)
        x, y = _data(rng)
        net = MultiLayerNetwork(_conf()).init()
        master = ParameterAveragingTrainingMaster(
            averaging_frequency=2, collect_stats=True)
        trainer = master.build(net)
        for i in range(4):
            trainer.fit_batch(x[:32], y[:32])
        trainer.finish()
        s = trainer.stats()
        assert s["step"]["count"] == 4
        assert s["average"]["count"] >= 2
        assert "sync_to_net" in s
        js = trainer.training_stats().as_json()
        import json as _json
        parsed = _json.loads(js)
        assert parsed["summary"]["step"]["count"] == 4
        assert len(parsed["events"]) >= 8

    def test_stats_off_by_default(self, rng):
        from deeplearning4j_tpu.parallel.training_master import (
            SyncTrainingMaster)
        net = MultiLayerNetwork(_conf()).init()
        trainer = SyncTrainingMaster().build(net)
        assert trainer.stats() is None
