"""Multi-host (multi-process) bootstrap and global meshes.

Role parity: the reference's multi-node story is Spark — a driver broadcasts
the model and workers train partitions
(``dl4j-spark/src/main/java/org/deeplearning4j/spark/impl/multilayer/
SparkDl4jMultiLayer.java:211-291``,
``.../impl/paramavg/ParameterAveragingTrainingMaster.java:340-374``), shipping
O(params) over TCP every averaging round.

TPU-native design: every host runs the SAME SPMD program; ``jax.distributed``
stitches the processes into one runtime, ``jax.devices()`` becomes the global
device list, and XLA routes collectives over ICI within a slice and DCN
across slices. There is no driver and no parameter shipping — the "cluster
orchestration layer" collapses into (1) this bootstrap, (2) a global mesh
whose outer axis maps to the process/DCN boundary, and (3) per-process data
feeding (`host_local_batch` for batch-sharded axes, `host_replicated_batch`
for tensor/pipeline-axis meshes).

Membership + round state for ELASTIC fleets (hosts that may die and
rejoin) deliberately does NOT ride on ``jax.distributed``: its
collectives hang on a dead peer, the exact failure this layer must
survive. That state lives on the coordination-store seam instead —
heartbeat leases, the append-only membership log and the round ledger in
:mod:`deeplearning4j_tpu.parallel.elastic` — and ``agree_on_digest``
takes an injectable ``allgather`` precisely so the elastic layer can run
the same commit gate over its store-backed gather.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[Sequence[int]] = None) -> None:
    """Join (or form) the multi-host JAX runtime.

    On Cloud TPU pods, all arguments auto-detect from the metadata server —
    call with no args on every host. Elsewhere pass the coordinator's
    ``host:port``, the world size and this process's rank (the analog of the
    reference's Spark master URL + executor registration).

    Single-process use (no coordinator, ``num_processes`` in (None, 1)) is a
    no-op so the same training script runs unchanged on one host.
    """
    global _initialized
    if _initialized:
        return
    explicit = (coordinator_address is not None
                or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if not explicit and num_processes in (None, 1):
        return  # single-process: nothing to bootstrap
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(**kwargs)
    _initialized = True


def is_initialized() -> bool:
    return _initialized


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def global_mesh(axes: Optional[Dict[str, int]] = None) -> Mesh:
    """Mesh over ALL devices in the (possibly multi-host) runtime.

    Default: 1-D ``data`` mesh over every global device. With ``axes``, the
    product must equal the global device count; devices are arranged so the
    FIRST axis varies slowest across processes — shard the first axis by
    host-boundary-tolerant traffic (data parallelism) and inner axes by
    ICI-hungry traffic (tensor/sequence parallelism), scaling-book style.
    """
    devs = jax.devices()
    if axes is None:
        return Mesh(np.asarray(devs), ("data",))
    names = tuple(axes.keys())
    shape = tuple(axes.values())
    total = int(np.prod(shape))
    if total != len(devs):
        raise ValueError(
            f"mesh {axes} needs {total} devices, runtime has {len(devs)} "
            f"across {jax.process_count()} process(es)")
    n_proc = jax.process_count()
    try:
        from jax.experimental import mesh_utils
        if n_proc > 1 and shape[0] % n_proc == 0:
            # DCN (process) boundary rides the first axis, ICI inside
            arr = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=(shape[0] // n_proc,) + shape[1:],
                dcn_mesh_shape=(n_proc,) + (1,) * (len(shape) - 1),
                devices=devs).reshape(shape)
        else:
            arr = mesh_utils.create_device_mesh(shape, devices=devs)
    except Exception:
        arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, names)


def agree_on_digest(digest: str, *, allgather=None) -> bool:
    """Pre-commit barrier for the multi-process dp path: every host
    presents its training-state digest (``util.durable.params_digest``)
    and the checkpoint commits only if ALL hosts agree — a diverged
    replica (bad host, dropped collective) must not publish its state as
    THE recovery point.

    ``allgather`` is injectable for tests; the default uses
    ``multihost_utils.process_allgather`` (single-process: trivially
    True).
    """
    local = np.frombuffer(bytes.fromhex(digest), dtype=np.uint8)
    if allgather is None:
        if jax.process_count() == 1:
            return True
        from jax.experimental import multihost_utils
        allgather = multihost_utils.process_allgather
    world = np.atleast_2d(np.asarray(allgather(local)))
    return bool((world == world[0]).all())


def host_replicated_batch(mesh: Mesh, *arrays):
    """Assemble REPLICATED global device arrays from identical per-process
    host arrays — the feeding path for meshes whose axes carry model
    state rather than batch shards (tensor/pipeline-axis meshes crossing
    the process boundary, VERDICT item 7). Every process must pass the
    same full array; the result is replicated over the whole mesh so a
    tensor-parallel step can consume it regardless of which axis spans
    DCN. Single-process: plain ``device_put`` with a replicated sharding.
    """
    sharding = NamedSharding(mesh, P())
    out = []
    for a in arrays:
        if a is None:
            out.append(None)
            continue
        a = np.asarray(a)
        if jax.process_count() == 1:
            out.append(jax.device_put(a, sharding))
        else:
            out.append(jax.make_array_from_process_local_data(
                sharding, a, a.shape))
    return out[0] if len(out) == 1 else tuple(out)


def host_local_batch(mesh: Mesh, *arrays, axis: str = "data"):
    """Assemble global device arrays from per-process host-local batches.

    Each process passes ITS shard of the global batch (the analog of a Spark
    worker reading its RDD partition); the result is a global array sharded
    over ``axis`` that the jitted SPMD step consumes directly. Single-process:
    equivalent to ``jax.device_put`` with the batch sharding.
    """
    sharding = NamedSharding(mesh, P(axis))
    out = []
    for a in arrays:
        if a is None:
            out.append(None)
            continue
        a = np.asarray(a)
        if jax.process_count() == 1:
            out.append(jax.device_put(a, sharding))
        else:
            global_shape = (a.shape[0] * jax.process_count(),) + a.shape[1:]
            out.append(jax.make_array_from_process_local_data(
                sharding, a, global_shape))
    return out[0] if len(out) == 1 else tuple(out)
