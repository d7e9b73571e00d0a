"""Worker for the two-process distributed test (the analog of the
reference's Spark ``local[n]`` trick, ``BaseSparkTest.java:90`` — but with a
REAL process boundary: two OS processes joined via jax.distributed, 4
virtual CPU devices each, one 8-device global mesh).

Invoked by tests/test_distributed.py as:
    python _two_process_worker.py <coordinator_port> <rank> <n_steps> [mode]

``mode`` is ``sync`` (default: 8-way data-parallel over the global mesh)
or ``tensor`` (VERDICT item 7: a PURE ``{"model": 8}`` mesh — the tensor
axis itself spans the process/DCN boundary, no data parallelism at all;
params are sharded across both processes and every gradient reduction is
a cross-process collective, fed via ``host_replicated_batch``).

Prints one line: ``RESULT <rank> <json>`` with per-step losses and a
parameter checksum (must match across ranks AND match single-process).
"""

import json
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402


def build_worker_net():
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .seed(42).updater("nesterovs").momentum(0.9).learning_rate(0.1)
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8)).build())
    return MultiLayerNetwork(conf).init()


def global_batches(n_steps):
    rng = np.random.default_rng(123)
    for _ in range(n_steps):
        xg = rng.normal(size=(32, 8)).astype(np.float32)
        yg = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]
        yield xg, yg


def main() -> None:
    port, rank, n_steps = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    mode = sys.argv[4] if len(sys.argv) > 4 else "sync"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from deeplearning4j_tpu.parallel import distributed as dist

    dist.initialize(coordinator_address=f"localhost:{port}",
                    num_processes=2, process_id=rank)
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4

    # cross-process trace propagation: when the spawning test set
    # DL4JTPU_TRACEPARENT, this worker's training joins that trace and
    # reports its span identity in RESULT for the parent to assert on
    from deeplearning4j_tpu.util import tracing as _tracing
    span = None
    ctx = _tracing.env_context()
    if ctx is not None:
        span = _tracing.TRACER.start(
            "worker.fit", parent=ctx,
            attributes={"rank": rank, "mode": mode})

    net = build_worker_net()
    losses = []
    if mode == "sync":
        from deeplearning4j_tpu.parallel.training_master import \
            SyncTrainingMaster
        mesh = dist.global_mesh()
        assert mesh.shape["data"] == 8
        trainer = SyncTrainingMaster().build(net, mesh)
        for xg, yg in global_batches(n_steps):
            # every process generates the same GLOBAL batch, then feeds
            # its process-local half through
            # make_array_from_process_local_data
            lo, hi = rank * 16, (rank + 1) * 16
            x, y = dist.host_local_batch(mesh, xg[lo:hi], yg[lo:hi])
            losses.append(float(trainer.fit_batch(x, y)))
    elif mode == "tensor":
        from deeplearning4j_tpu.parallel.tensor import TensorParallelTrainer
        mesh = dist.global_mesh({"model": 8})
        assert "data" not in mesh.axis_names     # NON-dp: pure tensor axis
        trainer = TensorParallelTrainer(net, mesh)
        for xg, yg in global_batches(n_steps):
            # no batch sharding: the full batch is replicated and the
            # MODEL axis spans the process boundary
            x, y = dist.host_replicated_batch(mesh, xg, yg)
            losses.append(float(trainer.fit_batch(x, y)))
    else:
        raise SystemExit(f"unknown mode {mode!r}")

    # on-device reduction: in tensor mode params are sharded ACROSS the
    # two processes, so a host-side np.asarray would see only local
    # shards; the jnp sum is a global collective yielding a replicated
    # (fully addressable) scalar on every process
    import jax.numpy as jnp
    checksum = float(sum(
        jnp.abs(l).sum()
        for l in jax.tree_util.tree_leaves(net.params)))
    result = {"losses": losses, "checksum": checksum}
    if span is not None:
        span.end()
        result["trace_id"] = span.trace_id
        result["parent_span_id"] = span.parent_id
    print("RESULT", rank, json.dumps(result), flush=True)
    dist.shutdown()


if __name__ == "__main__":
    main()
