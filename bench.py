"""Benchmarks for the BASELINE.md configs on the real TPU chip.

Configs measured (BASELINE.md):
  #1 LeNet-5 MNIST        (MultiLayerNetwork.fit_repeated)
  #2 ResNet-50 ImageNet   (ComputationGraph.fit_repeated — the headline MFU
                           number) + a pipeline-fed variant (AsyncDataSetIterator
                           device prefetch feeding fit_scan via the public API)
  #3 char-RNN GravesLSTM  (MultiLayerNetwork.fit_repeated, tokens/s)
  #4 Word2Vec SGNS        (nlp.learning.ns_step_scan, pairs/s)

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}``.

The reference publishes no numbers (BASELINE.md), so ``vs_baseline`` reports
measured MFU / the 40% MFU north-star target (BASELINE.json).

All measured loops run through the framework's PUBLIC APIs (fit_repeated /
fit_scan / ns_step_scan): K updates fused into one XLA dispatch, which is the
idiomatic TPU inner loop.

A config that raises ends the run: the traceback goes to stderr and the exit
code is non-zero, so a failure is never read as a result.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

def _peak_flops_per_sec() -> float:
    """Per-chip peak (bf16) — single source of truth in util/profiling.py.
    A device kind with no published peak there is an error: an MFU is a
    share of a KNOWN chip's peak or it is nothing."""
    from deeplearning4j_tpu.util import profiling
    peak = profiling.peak_flops_per_sec()
    if peak is None:
        import jax
        raise RuntimeError(
            f"no published peak FLOP/s for device kind "
            f"{jax.devices()[0].device_kind!r} — MFU is undefined here "
            "(add the chip to util/profiling.py's table with its source)")
    return peak


MFU_DEVIATION_WARN_PCT = 15.0


def _mfu_crosscheck(fn_name: str, analytic_flops: float) -> dict:
    """Measured-vs-analytic FLOPs cross-check for one benched program:
    compares the compiled executable's HLO cost-analysis FLOPs
    (``compiled_flops{fn}``, recorded by the retrace guard at compile
    time) against the analytic formula's per-dispatch FLOPs. A deviation
    beyond ``MFU_DEVIATION_WARN_PCT`` means the analytic formula (the MFU
    numerator every PERF.md claim uses) has drifted from what the
    compiler actually builds — flagged in the payload AND logged, so
    formula rot is caught mechanically."""
    from deeplearning4j_tpu.util import metrics as _metrics
    out = {"analytic_flops_per_dispatch": analytic_flops}
    g = _metrics.REGISTRY.get("compiled_flops")
    measured = g.value(fn=fn_name) if g is not None else 0.0
    if not measured:
        out["flops_crosscheck"] = "unavailable"
        return out
    dev_pct = 100.0 * (measured - analytic_flops) / analytic_flops
    out.update({
        "compiled_flops_per_dispatch": measured,
        "flops_deviation_pct": round(dev_pct, 2),
        "flops_deviation_exceeds_warn": abs(dev_pct) > MFU_DEVIATION_WARN_PCT,
    })
    if abs(dev_pct) > MFU_DEVIATION_WARN_PCT:
        print(f"WARNING: {fn_name} measured FLOPs deviate "
              f"{dev_pct:+.1f}% from the analytic formula "
              f"(>{MFU_DEVIATION_WARN_PCT:.0f}%) — the MFU numerator has "
              "drifted; re-derive the formula against the compiled "
              "program", flush=True)
    return out


def _conv_flops_nhwc(h, w, c_in, c_out, kh, kw, stride):
    oh, ow = -(-h // stride), -(-w // stride)
    return 2.0 * oh * ow * c_out * kh * kw * c_in, oh, ow


def _resnet50_train_flops_per_example(image=224, n_classes=1000) -> float:
    """Analytic fwd FLOPs for standard bottleneck ResNet-50 — ≈8.2 GFLOP
    fwd at 224² (2 FLOPs per MAC × the published ≈4.1 GMACs); train ≈ 3×
    fwd. Peak in the MFU denominator uses the same 2-FLOPs-per-MAC
    convention, so the ratio is convention-consistent."""
    total = 0.0
    f, h = 0.0, image
    # stem 7x7/2 ch 3->64
    f, oh, _ = _conv_flops_nhwc(h, h, 3, 64, 7, 7, 2)
    total += f
    h = oh
    h = -(-h // 2)  # maxpool /2
    c_in = 64
    for stage, (planes, blocks) in enumerate(
            [(64, 3), (128, 4), (256, 6), (512, 3)]):
        for i in range(blocks):
            stride = 2 if (stage > 0 and i == 0) else 1
            oh = -(-h // stride)
            # 1x1 reduce (at input res), 3x3 (stride), 1x1 expand
            f1, _, _ = _conv_flops_nhwc(h, h, c_in, planes, 1, 1, 1)
            f2, _, _ = _conv_flops_nhwc(h, h, planes, planes, 3, 3, stride)
            f3, _, _ = _conv_flops_nhwc(oh, oh, planes, planes * 4, 1, 1, 1)
            total += f1 + f2 + f3
            if i == 0:
                fp, _, _ = _conv_flops_nhwc(h, h, c_in, planes * 4, 1, 1, stride)
                total += fp
            c_in = planes * 4
            h = oh
    total += 2.0 * c_in * n_classes  # fc head
    return 3.0 * total


def _lenet_train_flops_per_example() -> float:
    fwd = (2.0 * 24 * 24 * 20 * 5 * 5 * 1      # conv1
           + 2.0 * 8 * 8 * 50 * 5 * 5 * 20     # conv2
           + 2.0 * 800 * 500                   # dense
           + 2.0 * 500 * 10)                   # out
    return 3.0 * fwd


def _lstm_train_flops_per_example(vocab, hidden, layers, t) -> float:
    """Analytic GravesLSTM stack fwd FLOPs per example; train ≈ 3× fwd."""
    per_step = 0.0
    n_in = vocab
    for _ in range(layers):
        per_step += 2.0 * n_in * 4 * hidden     # input projection
        per_step += 2.0 * hidden * 4 * hidden   # recurrent matmul
        n_in = hidden
    per_step += 2.0 * hidden * vocab            # rnn output layer
    return 3.0 * per_step * t


def _stage_batches(k, batch, shape, n_classes, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(k, batch) + shape).astype(np.float32)
    ys = np.eye(n_classes, dtype=np.float32)[
        rng.integers(0, n_classes, (k, batch))]
    return xs, ys


# NB: np.asarray (device→host transfer) is the completion barrier everywhere
# below.


def bench_lenet() -> dict:
    import jax
    from deeplearning4j_tpu.models import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    # bs1024: small-model MFU is dispatch/HBM-bound and scales with
    # batch (512: 3.2%, 1024: 6.9%, 2048: 8.3% measured); k=256 amortizes
    # per-update overhead further (k=32: 0.8-1.0M, k=256: 1.68M ex/s;
    # bf16 measured SLOWER here — layout conversions dominate tiny convs)
    batch, k, rounds = 1024, 256, 4
    net = MultiLayerNetwork(lenet()).init()
    xs, ys = _stage_batches(1, batch, (784,), 10, seed=7)
    x, y = jax.device_put(xs[0]), jax.device_put(ys[0])
    np.asarray(net.fit_repeated(x, y, k))  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(rounds):
        losses = net.fit_repeated(x, y, k)
    np.asarray(losses)
    dt = time.perf_counter() - t0
    steps = rounds * k
    eps = steps * batch / dt
    mfu = eps * _lenet_train_flops_per_example() / _peak_flops_per_sec()
    out = {"examples_per_sec": round(eps, 1), "mfu": round(mfu, 4),
           "step_ms": round(1000 * dt / steps, 3), "batch": batch}
    out.update(_mfu_crosscheck(
        "MultiLayerNetwork.train_repeat",
        _lenet_train_flops_per_example() * batch * k))
    return out


def _make_resnet():
    from deeplearning4j_tpu.models import resnet50
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph

    image = int(os.environ.get("BENCH_RESNET_IMAGE", "224"))
    batch = int(os.environ.get("BENCH_RESNET_BATCH", "128"))
    conf = resnet50(height=image, width=image,
                    dtype=os.environ.get("BENCH_RESNET_DTYPE", "mixed_bf16"))
    return ComputationGraph(conf).init(), image, batch


def bench_resnet50() -> dict:
    """ResNet-50 training MFU via the public ComputationGraph.fit_repeated
    API: K optimizer updates on one staged device batch per dispatch, so
    arbitrarily long on-chip runs cost one batch of HBM — isolating train-step
    compute the way a production input pipeline (prefetching while computing)
    would."""
    import jax

    net, image, batch = _make_resnet()
    k = int(os.environ.get("BENCH_RESNET_SCAN", "64"))  # 46.9 vs 47.6 ms at 32
    rounds = 2
    xs, ys = _stage_batches(1, batch, (image, image, 3), 1000, seed=11)
    x = jax.device_put(xs[0])
    y = jax.device_put(ys[0])

    np.asarray(net.fit_repeated([x], [y], k))  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(rounds):
        losses = net.fit_repeated([x], [y], k)
    np.asarray(losses)
    dt = time.perf_counter() - t0

    steps = rounds * k
    eps = steps * batch / dt
    mfu = (eps * _resnet50_train_flops_per_example(image)
           / _peak_flops_per_sec())
    out = {"examples_per_sec": round(eps, 1), "mfu": round(mfu, 4),
           "step_ms": round(1000 * dt / steps, 3), "batch": batch,
           "image": image}
    out.update(_mfu_crosscheck(
        "ComputationGraph.train_repeat",
        _resnet50_train_flops_per_example(image) * batch * k))
    return out


def bench_resnet50_pipeline() -> dict:
    """End-to-end variant: ``net.fit(AsyncDataSetIterator(...))`` over a
    device-staged pool (standing in for a decoded-image cache already moved
    to HBM) — demonstrating the public iterator + fit path adds negligible
    overhead over the synthetic loop.

    Host→device bandwidth is reported separately (``h2d_MBps``), measured
    on one batch before the pool is staged; the async prefetch overlaps
    such transfers with compute (AsyncDataSetIterator parity: reference
    ``AsyncDataSetIterator.java:36``)."""
    import jax
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import (
        AsyncDataSetIterator, ExistingDataSetIterator)

    net, image, batch = _make_resnet()
    k = int(os.environ.get("BENCH_RESNET_PIPE_SCAN", "8"))
    blocks = int(os.environ.get("BENCH_RESNET_PIPE_BLOCKS", "4"))

    pool_xs, pool_ys = _stage_batches(4, batch, (image, image, 3), 1000,
                                      seed=13)
    # measure h2d once (one batch), then stage the pool on device
    t0 = time.perf_counter()
    dev0 = jax.device_put(pool_xs[0])
    np.asarray(dev0[0, 0, 0, :1])  # transfer barrier
    h2d_s = time.perf_counter() - t0
    h2d_mbps = pool_xs[0].nbytes / 1e6 / h2d_s
    dev_xs = [dev0] + [jax.device_put(pool_xs[i]) for i in range(1, 4)]
    dev_ys = [jax.device_put(pool_ys[i]) for i in range(4)]

    def batches(n):
        for i in range(n):
            j = i % len(dev_xs)
            yield DataSet(dev_xs[j], dev_ys[j])

    def run(n):
        # the REAL product path: fit(iterator) → per-batch jitted fit_batch,
        # async dispatch overlapping the prefetch thread
        net.fit(AsyncDataSetIterator(ExistingDataSetIterator(batches(n)),
                                     queue_size=2 * k))
        np.asarray(net._score)

    run(k)  # warmup/compile
    t0 = time.perf_counter()
    run(blocks * k)
    dt = time.perf_counter() - t0
    steps = blocks * k
    eps = steps * batch / dt
    mfu = (eps * _resnet50_train_flops_per_example(image)
           / _peak_flops_per_sec())
    return {"examples_per_sec": round(eps, 1), "mfu": round(mfu, 4),
            "step_ms": round(1000 * dt / steps, 3), "batch": batch,
            "image": image, "h2d_MBps": round(h2d_mbps, 1)}


def bench_ingest() -> dict:
    """The fit-vs-synthetic gap (ISSUE 4 acceptance): end-to-end
    ``fit(iterator)`` over HOST numpy batches — exercising the default
    ingest stage (background device_put double-buffering), the bounded
    in-flight window, and lazy scores — against the synthetic
    ``fit_repeated`` on-chip loop for the same model. Reports the ingest
    metrics the run produced (queue depth, h2d MBps, host-gap histogram
    mean) alongside the step times; r4 measured this gap at +5% before
    the async-dispatch loop landed.
    """
    import jax
    from deeplearning4j_tpu.util import metrics as _metrics

    model = os.environ.get(
        "BENCH_INGEST_MODEL",
        "lenet" if os.environ.get("BENCH_SKIP_RESNET") == "1" else "resnet")
    if model == "resnet":
        net, image, batch = _make_resnet()
        shape, n_classes = (image, image, 3), 1000
        wrap = lambda a: [a]
    else:   # lenet: small/CPU-friendly fallback
        from deeplearning4j_tpu.models import lenet
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        net, batch = MultiLayerNetwork(lenet()).init(), 256
        shape, n_classes = (784,), 10
        wrap = lambda a: a

    k = int(os.environ.get("BENCH_INGEST_SCAN", "32"))
    blocks = int(os.environ.get("BENCH_INGEST_BLOCKS", "2"))
    xs, ys = _stage_batches(1, batch, shape, n_classes, seed=29)
    x, y = jax.device_put(xs[0]), jax.device_put(ys[0])

    # synthetic ceiling: K fused on-chip updates per dispatch (same K as
    # the warmup — K is a static argnum, a different one would recompile)
    np.asarray(net.fit_repeated(wrap(x), wrap(y), k))  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(blocks):
        losses = net.fit_repeated(wrap(x), wrap(y), k)
    np.asarray(losses)
    synth_ms = 1000 * (time.perf_counter() - t0) / (blocks * k)

    # end-to-end product path: fit() over HOST batches through the
    # default ingest stage (the staging thread pays the h2d, the loop
    # never reads a loss)
    hx, hy = np.asarray(xs[0]), np.asarray(ys[0])

    def batches(n):
        for _ in range(n):
            yield hx, hy

    net.fit(batches(k))                  # warmup (compiles the per-batch step)
    np.asarray(net._score)
    t0 = time.perf_counter()
    net.fit(batches(blocks * k))
    np.asarray(net._score)
    e2e_ms = 1000 * (time.perf_counter() - t0) / (blocks * k)

    reg = _metrics.REGISTRY
    h2d_b = reg.get("ingest_h2d_bytes_total")
    h2d_s = reg.get("ingest_h2d_seconds_total")
    gap_h = reg.get("fit_host_gap_seconds")
    mname = type(net).__name__
    out = {"fit_step_ms": round(e2e_ms, 3),
           "synthetic_step_ms": round(synth_ms, 3),
           "gap_pct": round(100 * (e2e_ms - synth_ms) / synth_ms, 2),
           "batch": batch, "model": model}
    depth = reg.get("ingest_queue_depth")     # absent under DL4JTPU_INGEST=0
    if depth is not None:
        out["queue_depth"] = depth.value(stage="fit")
    if h2d_b is not None and h2d_s is not None:
        secs = h2d_s.value(stage="fit")
        if secs > 0:
            out["h2d_MBps"] = round(h2d_b.value(stage="fit") / 1e6 / secs, 1)
    if gap_h is not None and gap_h.count(model=mname):
        out["host_gap_ms_mean"] = round(
            1000 * gap_h.sum(model=mname) / gap_h.count(model=mname), 3)
    return out


def bench_input_pipeline() -> dict:
    """Records-fed ResNet A/B vs the synthetic device-staged pool
    (ISSUE 14 acceptance): the SAME model and step count trained once
    from sharded record files through the full input pipeline (decode +
    shard/buffer shuffles + the jitted crop/flip/normalize augmentation
    + default ingest staging) and once from an HBM-resident pool (the
    input-cost-free ceiling every prior round used). Reports records/s,
    augment seconds/batch, and the ``fit_host_gap_seconds`` split for
    BOTH runs — the acceptance is the records-fed host gap staying ≤2%
    of step time (the input hides behind the step on its staging
    thread). Payload fields ``input_pipeline_records_per_s`` and
    ``input_host_gap_pct`` ride out of main().

    ``BENCH_SKIP_RESNET=1`` (CPU harness) swaps in ``resnet_tiny`` at
    CIFAR geometry — same DAG shape, so the pipeline/step overlap story
    is exercised end to end without the ImageNet compile cost."""
    import shutil
    import tempfile

    import jax
    from deeplearning4j_tpu.data.pipeline import (Augment, AugmentStage,
                                                  RecordDataSetIterator)
    from deeplearning4j_tpu.data.records import write_shard_set
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.util import ingest as _ingest

    if os.environ.get("BENCH_SKIP_RESNET") == "1":
        from deeplearning4j_tpu.models import resnet_tiny
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        image = int(os.environ.get("BENCH_INPUT_IMAGE", "32"))
        batch = int(os.environ.get("BENCH_INPUT_BATCH", "16"))
        n_classes = 10
        net = ComputationGraph(resnet_tiny(
            height=image, width=image, n_classes=n_classes)).init()
    else:
        net, image, batch = _make_resnet()
        n_classes = 1000
    steps = int(os.environ.get("BENCH_INPUT_STEPS", "24"))
    warm, shards = 4, 4
    mname = type(net).__name__
    eye = np.eye(n_classes, dtype=np.float32)
    tmp = tempfile.mkdtemp(prefix="bench_records_")

    def write(name, n_batches, seed):
        def examples():
            rng = np.random.default_rng(seed)
            for _ in range(n_batches * batch):
                yield {"features": rng.integers(
                            0, 256, (image, image, 3), dtype=np.uint8),
                       "labels": eye[int(rng.integers(0, n_classes))]}
        write_shard_set(tmp, name, examples(), shards)

    # uint8 records + on-device normalize: store bytes, augment in the
    # step's shadow (ImageNet-style mean/std). ONE shared AugmentStage:
    # the warm run must compile the SAME jitted program the timed run
    # dispatches, or its compile wall lands inside the measurement
    aug_stage = AugmentStage(
        Augment(crop_pad=max(1, image // 8), flip=True, scale=1 / 255.0,
                mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
        seed=5, stage_name="bench")

    def records_iter(name):
        return RecordDataSetIterator(
            tmp, name, batch_size=batch, seed=5, shuffle_shards=True,
            shuffle_buffer=2 * batch, augment=aug_stage,
            drop_remainder=True, stage_name="bench")

    gap_h = _ingest.host_gap_histogram()
    aug_c = _ingest.augment_seconds_counter()
    rec_c = _ingest.records_read_counter()

    def gap_state():
        return gap_h.sum(model=mname), gap_h.count(model=mname)

    try:
        t0 = time.perf_counter()
        write("warm", warm, 43)
        write("bench", steps, 47)
        write_s = time.perf_counter() - t0
        net.fit(records_iter("warm"))        # compile augment + train step
        np.asarray(net._score)
        g0, c0 = gap_state()
        a0 = aug_c.value(stage="bench")
        r0 = rec_c.value(stage="bench")
        t0 = time.perf_counter()
        net.fit(records_iter("bench"))
        np.asarray(net._score)
        dt = time.perf_counter() - t0
        g1, c1 = gap_state()
        a1 = aug_c.value(stage="bench")
        r1 = rec_c.value(stage="bench")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    rec_step_ms = 1000 * dt / steps
    rec_gap_ms = 1000 * (g1 - g0) / max(c1 - c0, 1)

    # B: the synthetic ceiling — same step, inputs already in HBM
    rng = np.random.default_rng(53)
    dev_xs = [jax.device_put(rng.normal(
        size=(batch, image, image, 3)).astype(np.float32))
        for _ in range(4)]
    dev_ys = [jax.device_put(eye[rng.integers(0, n_classes, batch)])
              for _ in range(4)]

    def pool(n):
        for i in range(n):
            yield DataSet(dev_xs[i % 4], dev_ys[i % 4])

    net.fit(pool(warm))
    np.asarray(net._score)
    g0, c0 = gap_state()
    t0 = time.perf_counter()
    net.fit(pool(steps))
    np.asarray(net._score)
    sdt = time.perf_counter() - t0
    g1, c1 = gap_state()
    syn_step_ms = 1000 * sdt / steps
    syn_gap_ms = 1000 * (g1 - g0) / max(c1 - c0, 1)

    return {"records_per_s": round(steps * batch / dt, 1),
            "records_read": int(r1 - r0),
            "step_ms_records": round(rec_step_ms, 3),
            "step_ms_synthetic": round(syn_step_ms, 3),
            "step_overhead_pct": round(
                100 * (rec_step_ms - syn_step_ms) / syn_step_ms, 2),
            "host_gap_ms_records": round(rec_gap_ms, 4),
            "host_gap_ms_synthetic": round(syn_gap_ms, 4),
            "gap_pct_records": round(100 * rec_gap_ms / rec_step_ms, 2),
            "gap_pct_synthetic": round(100 * syn_gap_ms / syn_step_ms, 2),
            "augment_ms_per_batch": round(1000 * (a1 - a0) / steps, 3),
            "shard_write_s": round(write_s, 2),
            "batch": batch, "image": image, "steps": steps,
            "shards": shards, "model": mname}


def bench_checkpoint() -> dict:
    """Async-checkpoint overhead (ISSUE 5 acceptance): steady-state
    ``fit(iterator)`` step time with durable checkpointing OFF vs ON
    (single-outstanding background writer, every ``frequency`` steps).
    The commit must never block a step for a full write — the measured
    delta plus the registry's ``checkpoint_write_seconds`` mean proves
    the write cost stayed off the critical path."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util import metrics as _metrics
    from deeplearning4j_tpu.util.durable import (AsyncCheckpointWriter,
                                                 CheckpointStore,
                                                 DurableSession)

    batch = int(os.environ.get("BENCH_CKPT_BATCH", "256"))
    steps = int(os.environ.get("BENCH_CKPT_STEPS", "64"))
    frequency = int(os.environ.get("BENCH_CKPT_FREQ", "8"))
    xs, ys = _stage_batches(1, batch, (784,), 10, seed=31)
    hx, hy = np.asarray(xs[0]), np.asarray(ys[0])

    def iterator():
        return ListDataSetIterator([DataSet(hx, hy)] * steps,
                                   batch_size=batch)

    def timed_fit(writer=None):
        net = MultiLayerNetwork(lenet()).init()
        net.fit(iterator())                  # warmup/compile
        np.asarray(net._score)
        session = None
        if writer is not None:
            session = DurableSession(net, writer.store,
                                     frequency=frequency, writer=writer)
        t0 = time.perf_counter()
        net.fit(iterator(), session=session)
        np.asarray(net._score)
        return 1000 * (time.perf_counter() - t0) / steps

    off_ms = timed_fit()

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        writer = AsyncCheckpointWriter(CheckpointStore(ckpt_dir, keep=2))
        on_ms = timed_fit(writer)
        writer.drain()
        writer.close()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    out = {"step_ms_off": round(off_ms, 3), "step_ms_on": round(on_ms, 3),
           "overhead_pct": round(100 * (on_ms - off_ms) / off_ms, 2),
           "frequency": frequency, "steps": steps, "batch": batch}
    hist = _metrics.REGISTRY.get("checkpoint_write_seconds")
    if hist is not None:
        snap = hist.snapshot()["series"]
        if snap and snap[0]["count"]:
            out["write_ms_mean"] = round(
                1000 * snap[0]["sum"] / snap[0]["count"], 2)
    commits = _metrics.REGISTRY.get("checkpoint_commits_total")
    if commits is not None:
        out["commits"] = sum(s["value"] for s in
                             commits.snapshot()["series"])
    return out


def bench_health_stats() -> dict:
    """On-device training-health stats A/B (ISSUE 15 acceptance): the
    SAME model/batch trained with the plain train step vs the
    stats-collecting variant (per-layer norms, update:param ratios,
    activation stats, log-bucket histograms fused into the dispatch).
    Acceptance: ``health_stats_overhead_pct`` ≤ 2% with ZERO added host
    syncs outside listener windows (nothing reads the stats pytree until
    a consumer asks). A second phase attaches a ``HealthListener`` at
    ``frequency`` and pins exactly one sync per window, reporting the
    rules engine's verdicts as the ``training_health`` payload field."""
    import jax
    from deeplearning4j_tpu.models import lenet
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util import health as _health
    from deeplearning4j_tpu.util.ingest import sync_counter

    batch = int(os.environ.get("BENCH_HEALTH_BATCH", "256"))
    steps = int(os.environ.get("BENCH_HEALTH_STEPS", "60"))
    rounds = int(os.environ.get("BENCH_HEALTH_ROUNDS", "3"))
    xs, ys = _stage_batches(1, batch, (784,), 10, seed=37)
    x, y = jax.device_put(xs[0]), jax.device_put(ys[0])

    def arm(stats: bool) -> float:
        """Best-of-rounds steady-state fit_batch step time (ms)."""
        net = MultiLayerNetwork(lenet()).init()
        if stats:
            net.enable_health_stats()
        net.fit_batch(x, y)                   # warmup/compile
        np.asarray(net._score)
        best = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(steps):
                net.fit_batch(x, y)
            np.asarray(net._score)            # completion barrier
            dt = 1000 * (time.perf_counter() - t0) / steps
            best = dt if best is None else min(best, dt)
        return best

    off_ms = arm(False)
    s0 = sync_counter().total()
    on_ms = arm(True)           # listener-free: nothing reads the stats
    syncs_outside_windows = sync_counter().total() - s0

    # listener phase: one sync per frequency window, rules evaluated
    freq = int(os.environ.get("BENCH_HEALTH_FREQ", "10"))
    net = MultiLayerNetwork(lenet()).init()
    listener = _health.HealthListener(frequency=freq, model="bench_lenet")
    net.set_listeners(listener)
    net.fit_batch(x, y)                       # warmup (enables stats)
    np.asarray(net._score)
    s0 = sync_counter().total()
    n = 3 * freq
    it0 = net.iteration_count
    for _ in range(n):
        net.fit_batch(x, y)
    np.asarray(net._score)
    listener_syncs = sync_counter().total() - s0
    windows = sum(1 for i in range(it0 + 1, it0 + n + 1) if i % freq == 0)

    return {
        "step_ms_off": round(off_ms, 3), "step_ms_on": round(on_ms, 3),
        "health_stats_overhead_pct": round(
            100 * (on_ms - off_ms) / off_ms, 2),
        "syncs_outside_windows": syncs_outside_windows,
        "listener_windows": windows, "listener_syncs": listener_syncs,
        "batch": batch, "steps": steps,
        "training_health": listener.engine.last_report,
    }


def bench_lstm() -> dict:
    """Char-RNN GravesLSTM (BASELINE config #3): tokens/s through
    MultiLayerNetwork.fit_repeated on one-hot char sequences."""
    import jax
    from deeplearning4j_tpu.models.char_rnn import char_rnn_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    vocab = int(os.environ.get("BENCH_LSTM_VOCAB", "80"))
    hidden = int(os.environ.get("BENCH_LSTM_HIDDEN", "512"))
    layers = 2
    t_len = int(os.environ.get("BENCH_LSTM_T", "64"))
    # 512: the largest batch still plausible for char-RNN training;
    # MFU scales with M (128->17.5%, 512->26%, 2048->31.5% measured)
    batch = int(os.environ.get("BENCH_LSTM_BATCH", "512"))
    # k=64 amortizes dispatch further: 2.40M -> 2.96M tokens/s measured
    k, rounds = 64, 2

    conf = char_rnn_lstm(vocab, hidden=hidden, layers=layers,
                         tbptt_length=t_len, dtype="mixed_bf16")
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(17)
    ids = rng.integers(0, vocab, (batch, t_len + 1))
    eye = np.eye(vocab, dtype=np.float32)
    x = jax.device_put(eye[ids[:, :-1]])   # [b, t, vocab]
    y = jax.device_put(eye[ids[:, 1:]])

    np.asarray(net.fit_repeated(x, y, k))  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(rounds):
        losses = net.fit_repeated(x, y, k)
    np.asarray(losses)
    dt = time.perf_counter() - t0
    steps = rounds * k
    eps = steps * batch / dt
    tokens = eps * t_len
    mfu = (eps * _lstm_train_flops_per_example(vocab, hidden, layers, t_len)
           / _peak_flops_per_sec())
    out = {"tokens_per_sec": round(tokens, 1),
           "examples_per_sec": round(eps, 1), "mfu": round(mfu, 4),
           "step_ms": round(1000 * dt / steps, 3), "batch": batch,
           "seq_len": t_len, "hidden": hidden, "vocab": vocab}
    out.update(_mfu_crosscheck(
        "MultiLayerNetwork.train_repeat",
        _lstm_train_flops_per_example(vocab, hidden, layers, t_len)
        * batch * k))
    return out


def bench_word2vec() -> dict:
    """Word2Vec skip-gram negative sampling (BASELINE config #4): training
    pairs/s through nlp.learning.ns_step_scan (the product kernel driving
    SequenceVectors)."""
    import jax
    from deeplearning4j_tpu.nlp import learning

    vocab = int(os.environ.get("BENCH_W2V_VOCAB", "100000"))
    dim = int(os.environ.get("BENCH_W2V_DIM", "128"))
    # 65536 pairs/step, k=128 fused updates: 6.0M pairs/s measured
    # (32k/k64: 5.2M; 131k batches risk stale in-batch gradients)
    b = int(os.environ.get("BENCH_W2V_BATCH", "65536"))
    negs = 5
    k, rounds = 128, 2

    params = learning.init_params(vocab, dim, seed=3, use_neg=True)
    params = jax.device_put(params)
    rng = np.random.default_rng(23)
    centers = jax.device_put(
        rng.integers(0, vocab, (k, b)).astype(np.int32))
    targets = jax.device_put(
        rng.integers(0, vocab, (k, b)).astype(np.int32))
    negss = jax.device_put(
        rng.integers(0, vocab, (k, b, negs)).astype(np.int32))

    lr = np.float32(0.025)
    params, losses = learning.ns_step_scan(
        params, centers, targets, negss, None, None, lr)
    np.asarray(losses)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(rounds):
        params, losses = learning.ns_step_scan(
            params, centers, targets, negss, None, None, lr)
    np.asarray(losses)
    dt = time.perf_counter() - t0
    pairs = rounds * k * b / dt
    return {"pairs_per_sec": round(pairs, 1), "batch": b, "dim": dim,
            "vocab": vocab, "negatives": negs,
            "step_ms": round(1000 * dt / (rounds * k), 3)}


def bench_flash_attention() -> dict:
    """Long-context attention (beyond the BASELINE set): the Pallas flash
    kernel vs the XLA fused path at bf16 t=8192 — the long-sequence hot op
    behind SelfAttentionLayer / sequence models. See PERF.md."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.attention import dot_product_attention
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    b, t, h, d = 4, 8192, 8, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
    f_xla = jax.jit(lambda q, k, v: jnp.sum(
        dot_product_attention(q, k, v, causal=True).astype(jnp.float32)))
    f_flash = jax.jit(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True).astype(jnp.float32)))

    def _grad(attn):
        def f(q, k, v):
            g = jax.grad(lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))(
                    q, k, v)
            return sum(jnp.sum(x.astype(jnp.float32)) for x in g)
        return jax.jit(f)

    g_xla = _grad(lambda q, k, v: dot_product_attention(q, k, v,
                                                        causal=True))
    g_flash = _grad(lambda q, k, v: flash_attention(q, k, v, True))

    def _t(f, iters=15):
        float(f(q, k, v))
        t0 = time.perf_counter()
        for _ in range(iters):
            s = f(q, k, v)
        float(s)
        return (time.perf_counter() - t0) / iters * 1e3

    prior_flag = os.environ.get("DL4JTPU_FLASH_ATTENTION")
    os.environ["DL4JTPU_FLASH_ATTENTION"] = "0"   # force the XLA route
    try:
        ms_xla = _t(f_xla)
        ms_xla_grad = _t(g_xla, iters=10)
    finally:
        if prior_flag is None:
            os.environ.pop("DL4JTPU_FLASH_ATTENTION", None)
        else:
            os.environ["DL4JTPU_FLASH_ATTENTION"] = prior_flag
    ms_flash = _t(f_flash)
    ms_flash_grad = _t(g_flash, iters=10)
    flops = 4.0 * b * h * t * t * d / 2  # causal
    return {"xla_ms": round(ms_xla, 2), "flash_ms": round(ms_flash, 2),
            "speedup": round(ms_xla / ms_flash, 2),
            "xla_grad_ms": round(ms_xla_grad, 2),
            "flash_grad_ms": round(ms_flash_grad, 2),
            "grad_speedup": round(ms_xla_grad / ms_flash_grad, 2),
            "flash_tflops": round(flops / ms_flash / 1e9, 1),
            "seq_len": t, "dtype": "bfloat16"}


def _transformer_train_flops_per_token(d_model, n_layers, d_ff, vocab,
                                       t) -> float:
    """Analytic train FLOPs per token for the decoder-only LM, stated
    once (the MFU numerator's single source of truth, PERF.md r8):

        3 × [ 2·(L·(4·d² + 2·d·d_ff) + d·V)  +  L·2·(T/2)·d·2 ]

    i.e. train ≈ 3× forward; forward = 2 FLOPs per matmul-parameter MAC
    (Wqkv 3d² + Wo d² + FFN 2·d·d_ff per layer, plus the d·V vocab head —
    the embedding GATHER does no FLOPs, which is the point of the
    integer-id input path), plus the causal attention matmuls (QKᵀ and
    PV: 2 matmuls × 2 FLOPs × T/2 average attended keys × d per layer).
    LayerNorm/softmax/residual vector work is excluded, same convention
    as the ResNet formula above."""
    matmul_params = (n_layers * (4.0 * d_model * d_model
                                 + 2.0 * d_model * d_ff)
                     + d_model * vocab)
    attn = n_layers * 2.0 * (t / 2.0) * d_model * 2.0
    return 3.0 * (2.0 * matmul_params + attn)


def bench_transformer_lm() -> dict:
    """Transformer-LM flagship (ROADMAP item 1): GPT-2-class config —
    d_model 768, 12 layers, 12 heads, T=2048, V=32768 — trained through
    the PUBLIC fit_repeated path on integer token ids (the one-hot
    [b, T, V] construction dies at V≫8; ids are 4 bytes/token), with the
    Pallas flash attention kernel forced on (fwd+bwd; T=2048 sits below
    the auto-route threshold but well inside the kernel's measured-win
    band). Reports MFU from the analytic FLOPs formula above — the
    metric the >40% north star is stated in, reachable here because
    transformer GEMMs (K≈768–3072) sit in this chip's 55–67 TF shape
    band (PERF.md r4 probes), unlike ResNet's conv mix."""
    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph

    V = int(os.environ.get("BENCH_TLM_VOCAB", "32768"))
    T = int(os.environ.get("BENCH_TLM_T", "2048"))
    b = int(os.environ.get("BENCH_TLM_BATCH", "8"))
    d_model = int(os.environ.get("BENCH_TLM_DMODEL", "768"))
    n_layers = int(os.environ.get("BENCH_TLM_LAYERS", "12"))
    n_heads = d_model // 64
    d_ff = 4 * d_model
    k, rounds = int(os.environ.get("BENCH_TLM_SCAN", "8")), 2

    prior = os.environ.get("DL4JTPU_FLASH_ATTENTION")
    os.environ["DL4JTPU_FLASH_ATTENTION"] = "1"
    try:
        net = ComputationGraph(transformer_lm(
            V, n_layers=n_layers, d_model=d_model, n_heads=n_heads,
            d_ff=d_ff, learning_rate=3e-4, dtype="mixed_bf16",
            input_ids=True)).init()
        rng = np.random.default_rng(19)
        ids = rng.integers(0, V, (b, T + 1)).astype(np.int32)
        x, y = ids[:, :-1], ids[:, 1:]
        np.asarray(net.fit_repeated([x], [y], k))  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(rounds):
            losses = net.fit_repeated([x], [y], k)
        np.asarray(losses)
        step_s = (time.perf_counter() - t0) / (rounds * k)
    finally:
        if prior is None:
            os.environ.pop("DL4JTPU_FLASH_ATTENTION", None)
        else:
            os.environ["DL4JTPU_FLASH_ATTENTION"] = prior
    tokens_per_sec = b * T / step_s
    fpt = _transformer_train_flops_per_token(d_model, n_layers, d_ff, V, T)
    mfu = tokens_per_sec * fpt / _peak_flops_per_sec()
    out = {"step_ms": round(step_s * 1e3, 2),
           "tokens_per_sec": round(tokens_per_sec, 1),
           "mfu": round(mfu, 4),
           "model_flops_per_token": round(fpt, 1),
           "batch": b, "seq_len": T, "d_model": d_model,
           "n_layers": n_layers, "n_heads": n_heads, "d_ff": d_ff,
           "vocab": V, "input_mode": "ids", "dtype": "mixed_bf16",
           "attention": "pallas_flash"}
    out.update(_mfu_crosscheck("ComputationGraph.train_repeat",
                               fpt * b * T * k))
    # the measured-MFU column: same step timing, but the NUMERATOR is the
    # compiled program's cost-analysis FLOPs instead of the formula
    if "compiled_flops_per_dispatch" in out:
        out["measured_mfu"] = round(
            out["compiled_flops_per_dispatch"] / (b * T * k)
            * tokens_per_sec / _peak_flops_per_sec(), 4)
    return out


def _bench_prefix_cache(net, baseline_engine, vocab, lanes, page_size,
                        pages_per_seq, block_len) -> dict:
    """Shared-prefix serving A/B + int8 KV-quantization quality/capacity
    (ISSUE 19), appended to the decode payload:

    - **prefix_hit_ttft_ms** — TTFT for requests whose WHOLE prompt is
      resident in the prefix index (a warm 2-page system prompt): the
      acceptance claim is that a full hit skips prefill entirely and
      pays roughly one decode-step dispatch. Partial hits (shared
      prefix + private tail) and the same Poisson schedule replayed on
      the warm prefix-off engine give the contrast rows.
    - **kv_prefix_hit_rate** — covered prompt tokens / total prompt
      tokens over the measured schedule (plus the admission-outcome
      counts from ``kv_prefix_hits_total``).
    - **int8_logit_max_err** — max |Δ log p| of the int8 paged forward
      vs the dense float oracle (``oracle_stream_probs``) over a
      4-page sequence, plus the greedy-divergence rate: the measured
      quality bound PERF.md records for the quantized arena.
    - **concurrent_lanes_at_fixed_arena** — lanes a fixed arena byte
      budget sustains at fp vs int8 pools (int8 codes + per-(page,
      head) scales ≈ ¼ the bytes → ~4× pages), cross-checked by
      actually running the int8 engine at the computed lane count and
      recording the peak concurrently-active lanes.
    """
    from deeplearning4j_tpu.models.transformer import (
        attention_vertices, oracle_stream_probs, paged_decode_forward)
    from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                                   PagedDecodeEngine)
    from deeplearning4j_tpu.serving.kv_cache import PagedKVArena
    from deeplearning4j_tpu.util.metrics import MetricsRegistry

    out = {}
    ps = page_size
    rng = np.random.default_rng(53)
    sys_prompt = rng.integers(0, vocab, 2 * ps).astype(np.int32)
    tails = rng.integers(0, vocab, (12, ps // 2)).astype(np.int32)
    # 11 exact repeats of the system prompt (full hits once seeded) +
    # 12 shared-prefix-plus-private-tail prompts (partial hits)
    schedule = [sys_prompt] * 11 + [np.concatenate([sys_prompt, t])
                                    for t in tails]
    order = rng.permutation(len(schedule))
    arrivals = np.cumsum(rng.exponential(0.002, len(schedule)))
    max_new = 8

    def poisson(sched):
        reqs = [None] * len(schedule)
        t0 = time.perf_counter()
        for i, k in enumerate(order):
            dt = arrivals[i] - (time.perf_counter() - t0)
            if dt > 0:
                time.sleep(dt)
            reqs[k] = sched.submit(schedule[k], max_new)
        for r in reqs:
            r.wait(600)
        return reqs

    reg = MetricsRegistry()
    eng = PagedDecodeEngine(net, max_batch=lanes, page_size=ps,
                            pages_per_seq=pages_per_seq, prefill_chunk=ps,
                            block_len=block_len, prefix_cache=True,
                            registry=reg)
    eng.warmup()
    sched = DecodeScheduler(eng, registry=reg, max_queue=64,
                            request_timeout_s=600.0)
    # seed the index (the measured schedule runs against a warm cache)
    seed = sched.submit(sys_prompt, max_new)
    seed.wait(600)
    reqs = poisson(sched)
    sched.stop()

    # the same schedule on the WARM prefix-off fused engine — the
    # prefill-every-time TTFT the hit rows are read against
    base_sched = DecodeScheduler(baseline_engine, max_queue=64,
                                 request_timeout_s=600.0)
    base_reqs = poisson(base_sched)
    base_sched.stop()
    for r, b in zip(reqs, base_reqs):
        assert r.tokens == b.tokens, \
            "prefix-cache greedy output diverged from the prefill path"

    def p50(ttfts):
        s = sorted(ttfts)
        return round(1000 * s[len(s) // 2], 3) if s else None

    full = [r for r in reqs
            if r.prefix_covered_tokens >= len(r.prompt)]
    partial = [r for r in reqs
               if 0 < r.prefix_covered_tokens < len(r.prompt)]
    hits = reg.get("kv_prefix_hits_total")
    out["prefix_hit_ttft_ms"] = p50(
        [r.t_first_token - r.t_submit for r in full])
    out["prefix_partial_ttft_ms"] = p50(
        [r.t_first_token - r.t_submit for r in partial])
    out["prefill_ttft_ms"] = p50(
        [r.t_first_token - r.t_submit for r in base_reqs])
    out["kv_prefix_hit_rate"] = round(
        sum(r.prefix_covered_tokens for r in reqs)
        / sum(len(r.prompt) for r in reqs), 4)
    out["kv_prefix_hits"] = {
        k: int(hits.value(result=k)) for k in ("full", "partial", "miss")}
    out["kv_prefix_cow_detaches"] = int(
        reg.get("kv_pages_cow_total").value())

    # ---- int8 quality bound vs the dense float oracle ----------------
    dims = {}
    for name in attention_vertices(net):
        layer = net.conf.vertices[name].layer
        dims[name] = (layer.n_heads, layer.n_in // layer.n_heads)
    t = 4 * ps
    seq = rng.integers(0, vocab, t).astype(np.int32)
    oracle = oracle_stream_probs(net, seq)                  # [t, V]
    q8 = PagedKVArena(dims, num_pages=pages_per_seq, page_size=ps,
                      kv_dtype="int8", with_allocator=False)
    probs, _, _ = paged_decode_forward(
        net, net.params, q8.k_pools, q8.v_pools, seq[None],
        np.arange(pages_per_seq, dtype=np.int32)[None],
        np.arange(t, dtype=np.int32)[None], np.zeros(1, np.int32))
    probs = np.asarray(probs, np.float64)[0]
    out["int8_logit_max_err"] = round(float(np.max(np.abs(
        np.log(np.maximum(probs, 1e-12))
        - np.log(np.maximum(oracle, 1e-12))))), 5)
    out["int8_greedy_divergence"] = round(float(np.mean(
        np.argmax(probs, axis=-1) != np.argmax(oracle, axis=-1))), 4)

    # ---- lane capacity at fixed arena bytes --------------------------
    per_fp = PagedKVArena(dims, num_pages=1, page_size=ps,
                          with_allocator=False).nbytes()
    per_q8 = PagedKVArena(dims, num_pages=1, page_size=ps,
                          kv_dtype="int8", with_allocator=False).nbytes()
    arena_bytes = lanes * pages_per_seq * per_fp
    q8_pages = int(arena_bytes // per_q8)
    q8_lanes = q8_pages // pages_per_seq
    qreg = MetricsRegistry()
    qeng = PagedDecodeEngine(net, max_batch=q8_lanes, page_size=ps,
                             pages_per_seq=pages_per_seq,
                             num_pages=q8_pages, prefill_chunk=ps,
                             block_len=block_len, kv_dtype="int8",
                             registry=qreg)
    qsched = DecodeScheduler(qeng, registry=qreg,
                             max_queue=q8_lanes + 8,
                             request_timeout_s=600.0)
    qprompts = rng.integers(0, vocab, (q8_lanes, ps)).astype(np.int32)
    qreqs = [qsched.submit(p, 24) for p in qprompts]
    peak = 0
    while not all(r.done for r in qreqs):
        peak = max(peak, qsched.active_count())
        time.sleep(0.005)
    qsched.stop()
    out["concurrent_lanes_at_fixed_arena"] = {
        "arena_mib": round(arena_bytes / 2 ** 20, 2),
        "fp_lanes": lanes,
        "int8_lanes": q8_lanes,
        "int8_sustained_active_lanes": peak,
        "capacity_ratio": round(q8_lanes / lanes, 2),
    }
    return out


def bench_decode() -> dict:
    """Decode-serving A/B under one OPEN-LOOP Poisson arrival schedule
    (ISSUE 9 + ISSUE 11 acceptance): sustained tokens/s plus p50/p99
    TTFT and time-per-output-token for FOUR decode-step shapes over the
    same model, same greedy sampling, same arrivals:

      A. **fused** — the headline: continuous batching with the N-step
         fused device loop (``block_len``; one dispatch, one host sync
         per block) — the `serving_decode_tokens_per_s` secondary
         metric cites THIS path;
      B. **ticked** — the PR-6 continuous-batching baseline (block_len=1,
         one host round-trip per token): the fused path must be no
         worse on the CPU harness;
      C. **speculative** — draft/verify blocks with the target model
         drafting for itself (the acceptance-rate UPPER BOUND: greedy
         target-as-draft accepts every token, so this row measures the
         spec machinery's ceiling and its two-dispatch overhead; a
         trained 2-layer draft's real rate lands with the device-day
         payload);
      D. **wave oracle** — the dense-cache wave-batched floor carried
         since ISSUE 9 (`speedup_vs_wave` trajectory).

    The acceptance numbers are RELATIVE plus the sync-count gauge
    (`decode_host_syncs_per_token` ≤ 1/block_len for the fused path) —
    on the CPU harness the absolute tokens/s measures the host, not the
    chip; TPU absolutes land via this same payload on a device day.
    Decode metrics (occupancy, pages, retire reasons, the
    `decode_host_tick_seconds` split) ride the process registry — which
    the FUSED run owns — into the BENCH payload.
    """
    import warnings

    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.models.transformer import sample_token
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
    from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                                   PagedDecodeEngine)
    from deeplearning4j_tpu.util import metrics as _metrics
    from deeplearning4j_tpu.util.metrics import MetricsRegistry

    vocab = int(os.environ.get("BENCH_DECODE_VOCAB", "256"))
    d_model = int(os.environ.get("BENCH_DECODE_DMODEL", "64"))
    n_layers = int(os.environ.get("BENCH_DECODE_LAYERS", "2"))
    lanes = int(os.environ.get("BENCH_DECODE_LANES", "8"))
    n_req = int(os.environ.get("BENCH_DECODE_REQS", "96"))
    block_len = int(os.environ.get("BENCH_DECODE_BLOCK", "8"))
    draft_k = int(os.environ.get("BENCH_DECODE_DRAFT_K", "4"))
    page_size, pages_per_seq = 16, 8
    window = page_size * pages_per_seq            # 128
    lp = 16                                       # prompt length
    iat_s = float(os.environ.get("BENCH_DECODE_IAT_MS", "2")) / 1000.0

    conf = transformer_lm(vocab, n_layers=n_layers, d_model=d_model,
                          n_heads=d_model // 16, d_ff=4 * d_model,
                          input_ids=True, max_cache_t=window)
    net = ComputationGraph(conf).init()

    rng = np.random.default_rng(37)
    prompts = rng.integers(0, vocab, (n_req, lp)).astype(np.int32)
    # mixed output lengths: the head of short chats + the long tail that
    # strands a wave's lanes (mean 25, wave max ≈ 96 → a wave burns
    # ~3/4 of its step-slots on finished lanes)
    lens = rng.choice([4, 8, 16, 96], size=n_req,
                      p=[0.35, 0.35, 0.1, 0.2])
    arrivals = np.cumsum(rng.exponential(iat_s, n_req))

    def poisson_run(registry, tracer=None, engine=None, **engine_kw):
        """One continuous-batching run over the shared schedule; every
        mode gets its own registry so sync/token accounting is clean.
        ``engine`` reuses an already-warm engine (same compiled ladder)
        for an A/B where only the scheduler config differs."""
        if engine is None:
            engine = PagedDecodeEngine(net, max_batch=lanes,
                                       page_size=page_size,
                                       pages_per_seq=pages_per_seq,
                                       prefill_chunk=lp,
                                       registry=registry, **engine_kw)
            engine.warmup()             # compile the whole trace ladder
        sched = DecodeScheduler(engine, registry=registry,
                                max_queue=n_req + 8,
                                request_timeout_s=600.0, tracer=tracer)
        t0 = time.perf_counter()
        reqs = []
        for i in range(n_req):
            dt = arrivals[i] - (time.perf_counter() - t0)
            if dt > 0:
                time.sleep(dt)
            reqs.append(sched.submit(prompts[i], int(lens[i])))
        for r in reqs:
            r.wait(600)
        wall = time.perf_counter() - t0
        sched.stop()
        tokens = sum(len(r.tokens) for r in reqs)
        ttfts = sorted(r.t_first_token - r.t_submit for r in reqs)
        tpots = [(r.t_done - r.t_first_token) / (len(r.tokens) - 1)
                 for r in reqs if len(r.tokens) > 1]
        syncs = registry.get("decode_host_syncs_total").value()
        return {"tokens_per_s": tokens / wall,
                "tokens": tokens,
                "ttft_p50_ms": 1000 * ttfts[len(ttfts) // 2],
                "ttft_p99_ms": 1000 * ttfts[int(0.99 * (len(ttfts) - 1))],
                "tpot_ms": 1000 * float(np.mean(tpots)),
                "host_syncs_per_token": syncs / max(tokens, 1),
                "registry": registry,
                "engine": engine,
                "reqs": reqs,
                "outputs": [r.tokens for r in reqs]}

    # ---- A: FUSED continuous batching (owns the process registry) ---
    fused = poisson_run(_metrics.REGISTRY, block_len=block_len)
    # HEADLINE-run registry snapshots, captured before the A/B reruns
    # below keep writing into the same process registry: totals (goodput
    # split, evicted pages) and the tick-split means must describe the
    # headline fused run alone, not 6 stacked schedules
    _goodput = _metrics.REGISTRY.get("decode_goodput_tokens_total")
    goodput_met = int(_goodput.value(slo="met"))
    goodput_missed = int(_goodput.value(slo="missed"))
    _evicted = _metrics.REGISTRY.get("kv_pages_evicted_total")
    kv_evicted_headline = (int(_evicted.value())
                           if _evicted is not None else None)
    _occ = _metrics.REGISTRY.get("decode_batch_occupancy")
    occ_headline = ((_occ.sum(), _occ.count())
                    if _occ is not None else (0.0, 0))
    _tick = _metrics.REGISTRY.get("decode_host_tick_seconds")
    tick_headline = (_tick.snapshot()["series"]
                     if _tick is not None else [])
    # ---- A': same engine + schedule with per-request tracing ON — the
    # measured cost of the request-timeline instrumentation (PERF
    # acceptance: ≤1% on tokens/s) and the source of the sample
    # timeline + TTFT decomposition in this payload. One Poisson run is
    # ~0.3s of wall, so single-run tokens/s jitters by several percent;
    # the A/B compares BEST-of-3 per side on the shared warm engine
    from deeplearning4j_tpu.util import timeline as _timeline
    from deeplearning4j_tpu.util.tracing import Tracer
    fused_best = fused["tokens_per_s"]
    for _ in range(2):
        rep = poisson_run(_metrics.REGISTRY, engine=fused["engine"])
        assert rep["outputs"] == fused["outputs"]
        fused_best = max(fused_best, rep["tokens_per_s"])
    tracer = Tracer(max_spans=100000)
    traced, traced_best = None, 0.0
    for _ in range(3):
        t = poisson_run(_metrics.REGISTRY, tracer=tracer,
                        engine=fused["engine"])
        assert t["outputs"] == fused["outputs"]
        if t["tokens_per_s"] > traced_best:
            traced_best, traced = t["tokens_per_s"], t
    # ---- B: the PR-6 host-ticked baseline ----------------------------
    ticked = poisson_run(MetricsRegistry())
    # ---- C: speculative (target-as-draft acceptance ceiling) ---------
    spec = poisson_run(MetricsRegistry(), draft_net=net, draft_k=draft_k)
    assert fused["outputs"] == ticked["outputs"] == spec["outputs"], \
        "greedy decode diverged between step shapes"
    spec_reg = spec["registry"]
    acc = spec_reg.get("decode_draft_tokens_total").value(result="accepted")
    rej = spec_reg.get("decode_draft_tokens_total").value(result="rejected")
    cont, cont_tokens = fused, fused["tokens"]

    # ---- B: wave-batched oracle (dense cache, padded waves) ----------
    def wave_step(x):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")     # window warnings
            return np.asarray(net.rnn_time_step(x))

    # warmup both wave shapes
    net.rnn_clear_previous_state()
    wave_step(np.zeros((lanes, lp, 1), np.int32))
    wave_step(np.zeros((lanes, 1, 1), np.int32))

    t0 = time.perf_counter()
    idx, wave_tokens = 0, 0
    wave_ttfts = []
    while idx < n_req:
        now = time.perf_counter() - t0
        if arrivals[idx] > now:
            time.sleep(arrivals[idx] - now)
            now = arrivals[idx]
        take = [idx]
        while (len(take) < lanes and idx + len(take) < n_req
               and arrivals[idx + len(take)] <= now):
            take.append(idx + len(take))
        b = len(take)
        x = np.zeros((lanes, lp, 1), np.int32)    # padded to fixed lanes
        x[:b, :, 0] = prompts[take]
        net.rnn_clear_previous_state()
        probs = wave_step(x)[:, -1]
        t_first = time.perf_counter() - t0
        wave_ttfts += [t_first - arrivals[j] for j in take]
        need = lens[take]
        toks = np.zeros(lanes, np.int32)
        produced = np.zeros(b, np.int64)
        for i in range(b):
            toks[i] = sample_token(probs[i])
            produced[i] = 1
        # the wave holds EVERY lane until its longest member finishes
        for _ in range(int(need.max()) - 1):
            probs = wave_step(toks[:, None, None])[:, 0]
            for i in range(b):
                if produced[i] < need[i]:
                    toks[i] = sample_token(probs[i])
                    produced[i] += 1
        wave_tokens += int(produced.sum())
        idx += b
    wave_wall = time.perf_counter() - t0
    wave_tps = wave_tokens / wave_wall
    wave_ttfts.sort()

    assert cont_tokens == wave_tokens == int(lens.sum())
    out = {"continuous_tokens_per_s": round(cont["tokens_per_s"], 1),
           "ticked_tokens_per_s": round(ticked["tokens_per_s"], 1),
           "spec_tokens_per_s": round(spec["tokens_per_s"], 1),
           "wave_tokens_per_s": round(wave_tps, 1),
           "speedup_vs_wave": round(cont["tokens_per_s"] / wave_tps, 2),
           "speedup_vs_ticked": round(
               cont["tokens_per_s"] / ticked["tokens_per_s"], 3),
           "block_len": block_len, "draft_k": draft_k,
           "decode_host_syncs_per_token": round(
               cont["host_syncs_per_token"], 4),
           "ticked_host_syncs_per_token": round(
               ticked["host_syncs_per_token"], 4),
           "spec_host_syncs_per_token": round(
               spec["host_syncs_per_token"], 4),
           "draft_acceptance_rate": round(acc / max(acc + rej, 1), 4),
           "spec_draft": "target-as-draft (acceptance upper bound)",
           "ttft_p50_ms": round(cont["ttft_p50_ms"], 2),
           "ttft_p99_ms": round(cont["ttft_p99_ms"], 2),
           "ticked_tpot_ms": round(ticked["tpot_ms"], 3),
           "spec_tpot_ms": round(spec["tpot_ms"], 3),
           "wave_ttft_p50_ms": round(
               1000 * wave_ttfts[len(wave_ttfts) // 2], 2),
           "wave_ttft_p99_ms": round(
               1000 * wave_ttfts[int(0.99 * (len(wave_ttfts) - 1))], 2),
           "tpot_ms": round(cont["tpot_ms"], 3),
           "requests": n_req, "lanes": lanes, "window": window,
           "page_size": page_size, "prompt_len": lp,
           "output_lens": "4/8/16/96 @ .35/.35/.1/.2",
           "total_tokens": cont_tokens,
           "arrival_iat_ms": round(1000 * iat_s, 1)}
    occ_sum, occ_count = occ_headline
    if occ_count:
        out["mean_decode_occupancy"] = round(occ_sum / occ_count, 2)
    if kv_evicted_headline is not None:
        out["kv_pages_evicted"] = kv_evicted_headline
    # the measured host-tick split (ISSUE 11 satellite): mean seconds per
    # component across the HEADLINE fused run's scheduler ticks (the
    # snapshot predates the A/B reruns)
    for s in tick_headline:
        if s["count"]:
            out[f"tick_{s['labels']['component']}_mean_ms"] = round(
                1000 * s["sum"] / s["count"], 4)
    # ---- request-timeline observability (ISSUE 13) -------------------
    # goodput next to the throughput row: served tokens by SLO outcome
    out["goodput_tokens_met"] = goodput_met
    out["goodput_tokens_missed"] = goodput_missed
    # measured tracing cost: same engine, same schedule, spans on vs
    # off, best-of-3 each side
    out["traced_tokens_per_s"] = round(traced_best, 1)
    out["tracing_overhead_pct"] = round(
        100.0 * (1.0 - traced_best / fused_best), 2)
    # the TTFT decomposition must SUM to the measured TTFT (acceptance:
    # within 5%); report the worst request so regressions are visible
    errs = []
    for r in traced["reqs"]:
        if r.ttft_breakdown and r.t_first_token is not None:
            ttft = r.t_first_token - r.t_submit
            if ttft > 0:
                errs.append(
                    abs(sum(r.ttft_breakdown.values()) - ttft) / ttft)
    if errs:
        out["ttft_decomposition_max_err_pct"] = round(
            100.0 * max(errs), 4)
        mean_bd = {k: 0.0 for k in
                   ("queue_wait", "prefill", "compile", "dispatch")}
        n_bd = 0
        for r in traced["reqs"]:
            if r.ttft_breakdown:
                n_bd += 1
                for k, v in r.ttft_breakdown.items():
                    mean_bd[k] += v
        out["ttft_breakdown_mean_ms"] = {
            k: round(1000 * v / max(n_bd, 1), 3)
            for k, v in mean_bd.items()}
    # one fully-rendered request timeline (the longest request) as the
    # payload's worked example of the span tree
    timelines = _timeline.request_timelines(tracer)
    if timelines:
        sample = max(timelines,
                     key=lambda t: t["attributes"].get("tokens", 0))
        out["sample_request_timeline"] = json.loads(
            json.dumps(sample, default=repr))
    # ---- prefix caching + int8 KV quantization (ISSUE 19) ------------
    out.update(_bench_prefix_cache(net, fused["engine"], vocab, lanes,
                                   page_size, pages_per_seq, block_len))
    return out


def bench_fleet() -> dict:
    """Serving-fleet tier (ISSUE 20): aggregate decode throughput at 1
    vs 4 routed replicas, plus a rolling ``set_model`` across the
    4-replica fleet under light load with zero shed increase.

    Honest-measurement note: this harness has ONE CPU core, so raw
    engine throughput cannot scale with replica count. Per-dispatch
    DEVICE time is therefore simulated — a FaultPlan hook on the
    ``serving.decode_step`` seam sleeps ``SIM_STEP_S`` inside every
    engine dispatch (sleeps release the GIL, so replica engines overlap
    exactly the way independent accelerators would, while the tiny real
    model keeps the host path honest). What the scaling number measures
    is the FLEET tier itself: router pick quality, HTTP proxying,
    heartbeat/capacity staleness, and scheduler admission — the real
    end-to-end path a multi-host fleet exercises, minus the chips."""
    import tempfile
    import threading
    import urllib.request

    from deeplearning4j_tpu.models import transformer_lm
    from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
    from deeplearning4j_tpu.parallel.elastic import \
        InMemoryCoordinationStore
    from deeplearning4j_tpu.serving import (FleetRouter, InferenceServer,
                                            ReplicaAgent)
    from deeplearning4j_tpu.util import faults
    from deeplearning4j_tpu.util.serialization import save_model

    VOCAB, WINDOW = 32, 32
    SIM_STEP_S = 0.05           # simulated device time per dispatch
    MAX_NEW = 16
    TIMEOUT_S = 120.0

    def _net(seed=7):
        conf = transformer_lm(VOCAB, n_layers=1, d_model=32, n_heads=2,
                              d_ff=64, seed=seed, input_ids=True,
                              max_cache_t=WINDOW)
        return ComputationGraph(conf).init()

    def _post(port, payload):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=TIMEOUT_S + 10) as r:
            return json.loads(r.read())

    def build_fleet(n):
        store = InMemoryCoordinationStore()
        servers = [InferenceServer(
            _net(), port=0,
            decode={"max_batch": 2, "page_size": 8, "pages_per_seq": 4,
                    "prefill_chunk": 8, "request_timeout_s": TIMEOUT_S})
            for _ in range(n)]
        agents = [ReplicaAgent(s, store, replica=f"r{i}",
                               lease_s=2.0).start()
                  for i, s in enumerate(servers)]
        router = FleetRouter(store, lease_s=2.0,
                             request_timeout_s=TIMEOUT_S,
                             attempt_timeout_s=TIMEOUT_S)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if router._health()["ready"] == n:
                break
            time.sleep(0.05)
        return store, servers, agents, router

    def teardown(servers, agents, router):
        router.stop()
        for a in agents:
            a.stop(deregister=False)
        for s in servers:
            s.stop(drain=False)

    def measure(router, n_requests, concurrency):
        """Closed-loop: `concurrency` clients drain a shared request
        counter back-to-back; tokens/s over the whole drain."""
        it = iter(range(n_requests))
        lock = threading.Lock()
        done = {"tokens": 0, "errors": 0}

        def worker():
            while True:
                with lock:
                    i = next(it, None)
                if i is None:
                    return
                try:
                    body = _post(router.port,
                                 {"prompt_ids": [1 + i % 6] * 6,
                                  "max_new_tokens": MAX_NEW,
                                  "timeout_s": TIMEOUT_S})
                    with lock:
                        done["tokens"] += len(body["tokens"])
                except Exception:
                    with lock:
                        done["errors"] += 1
        threads = [threading.Thread(target=worker)
                   for _ in range(concurrency)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return done["tokens"] / wall, done["errors"]

    out = {"sim_step_s": SIM_STEP_S, "max_new_tokens": MAX_NEW}
    plan = faults.FaultPlan()
    plan.always("serving.decode_step",
                exc=lambda payload: time.sleep(SIM_STEP_S))

    # ---- scaling: same closed-loop offered load per replica ----------
    for n in (1, 4):
        store, servers, agents, router = build_fleet(n)
        try:
            plan.install()
            try:
                tps, errors = measure(router, n_requests=24 * n,
                                      concurrency=6 * n)
            finally:
                plan.uninstall()
            out[f"tokens_per_s_{n}r"] = round(tps, 1)
            out[f"errors_{n}r"] = errors
            if n == 4:
                reqs = router.registry.get("fleet_requests_total")
                out["router_ok"] = int(reqs.value(outcome="ok"))
                out["failovers"] = int(router.registry.get(
                    "fleet_failovers_total").total())
                # ---- rolling deploy across the 4 replicas under light
                # load (no sim sleeps: swap_net re-warms in the fence
                # and the acceptance is zero shed, not speed)
                shed = router.registry.get("serving_shed_total")
                shed_before = shed.value(reason="no_replica")
                with tempfile.TemporaryDirectory() as d:
                    path = os.path.join(d, "next.zip")
                    save_model(_net(seed=11), path)
                    stop = threading.Event()
                    codes = []

                    def light_load():
                        i = 0
                        while not stop.is_set():
                            i += 1
                            try:
                                _post(router.port,
                                      {"prompt_ids": [1, 2, 3],
                                       "max_new_tokens": 2,
                                       "idempotency_key": f"roll-{i}"})
                                codes.append(200)
                            except Exception:
                                codes.append(-1)
                            time.sleep(0.05)
                    loader = threading.Thread(target=light_load)
                    loader.start()
                    t0 = time.perf_counter()
                    try:
                        rolled = router.rolling_set_model(
                            path, ready_timeout_s=180)
                    finally:
                        stop.set()
                        loader.join(timeout=60)
                    out["rolling_deploy"] = {
                        "replicas": len(rolled),
                        "all_ok": all(r["ok"] for r in rolled),
                        "seconds": round(time.perf_counter() - t0, 2),
                        "requests_during_roll": len(codes),
                        "request_failures": sum(c != 200 for c in codes),
                        "shed_increase": shed.value(reason="no_replica")
                                         - shed_before,
                    }
        finally:
            teardown(servers, agents, router)
    out["fleet_scaling_x"] = round(
        out["tokens_per_s_4r"] / max(out["tokens_per_s_1r"], 1e-9), 2)
    return out


def main() -> None:
    import jax
    from deeplearning4j_tpu.util.xla import use_compile_cache
    use_compile_cache()
    device = str(jax.devices()[0].device_kind)
    out = {"device": device}

    lenet_res = out["lenet"] = bench_lenet()
    resnet_res = None
    if os.environ.get("BENCH_SKIP_RESNET") != "1":
        resnet_res = out["resnet50"] = bench_resnet50()
        out["resnet50_pipeline"] = bench_resnet50_pipeline()
    out["ingest"] = bench_ingest()
    input_res = out["input_pipeline"] = bench_input_pipeline()
    out["checkpoint"] = bench_checkpoint()
    health_res = out["health_stats"] = bench_health_stats()
    out["lstm"] = bench_lstm()
    out["word2vec"] = bench_word2vec()
    out["flash_attention"] = bench_flash_attention()
    tlm_res = out["transformer_lm"] = bench_transformer_lm()
    decode_res = out["decode"] = bench_decode()
    fleet_res = out["fleet"] = bench_fleet()

    # snapshot the process-default metrics registry into the payload so
    # the perf trajectory carries whatever the run recorded (retry
    # counters, batch-size + latency histograms from any instrumented
    # path that defaulted to REGISTRY)
    try:
        from deeplearning4j_tpu.util import metrics as _metrics
        snap = _metrics.REGISTRY.snapshot()
        if snap:
            out["metrics"] = snap
    except Exception:
        pass    # metrics must never erase a round's evidence

    # compile-cost summary + measured-vs-analytic verdict: the compile
    # histogram details ride out["metrics"]["xla_compile_seconds"]; here
    # is the one-line version a human (or the round driver) reads first
    try:
        from deeplearning4j_tpu.util import metrics as _metrics
        hist = _metrics.REGISTRY.get("xla_compile_seconds")
        if hist is not None:
            series = hist.snapshot()["series"]
            out["xla_compile_summary"] = {
                "compiles": int(sum(s["count"] for s in series)),
                "total_seconds": round(sum(s["sum"] for s in series), 2),
            }
        deviations = {
            name: res["flops_deviation_pct"]
            for name, res in out.items()
            if isinstance(res, dict) and "flops_deviation_pct" in res}
        if deviations:
            worst = max(deviations.values(), key=abs)
            out["mfu_crosscheck"] = {
                "deviation_pct_by_config": deviations,
                "worst_deviation_pct": worst,
                "exceeds_warn": abs(worst) > MFU_DEVIATION_WARN_PCT,
            }
    except Exception:
        pass

    # decode-serving row: sustained continuous-batched tokens/s under
    # Poisson load — since ISSUE 11 the headline cites the FUSED
    # multi-token path (block_len decode steps per dispatch), with the
    # PR-6 ticked path and the speculative path as A/B columns;
    # vs_baseline stays the ratio over the wave-batched oracle divided
    # by the 2x acceptance target (the absolute tokens/s measures the
    # host on the CPU harness — the RELATIVE numbers are the acceptance
    # criteria; TPU absolutes land via this same field)
    if decode_res is not None and "continuous_tokens_per_s" in decode_res:
        out["serving_decode_tokens_per_s"] = {
            "metric": "serving_decode_tokens_per_s",
            "value": decode_res["continuous_tokens_per_s"],
            "unit": "tokens/s",
            "path": "fused",
            "block_len": decode_res.get("block_len"),
            "vs_baseline": round(decode_res["speedup_vs_wave"] / 2.0, 4),
            "speedup_vs_wave": decode_res["speedup_vs_wave"],
            "speedup_vs_ticked": decode_res.get("speedup_vs_ticked"),
            "decode_host_syncs_per_token": decode_res.get(
                "decode_host_syncs_per_token"),
            "draft_acceptance_rate": decode_res.get(
                "draft_acceptance_rate"),
            "ttft_p50_ms": decode_res["ttft_p50_ms"],
            "ttft_p99_ms": decode_res["ttft_p99_ms"],
            "tpot_ms": decode_res["tpot_ms"],
        }

    # fleet-scaling row (ISSUE 20): aggregate routed decode throughput
    # at 4 replicas over 1 (target >= 3.2x — fleet-tier overhead bounded
    # at <=20% of linear), plus the rolling-deploy zero-shed evidence;
    # device time is simulated per-dispatch on this 1-core harness (see
    # bench_fleet docstring), so the ratio isolates the fleet tier
    if fleet_res is not None and "fleet_scaling_x" in fleet_res:
        out["fleet_decode_scaling"] = {
            "metric": "fleet_decode_scaling",
            "value": fleet_res["fleet_scaling_x"],
            "unit": "x_at_4_replicas",
            "vs_baseline": round(fleet_res["fleet_scaling_x"] / 3.2, 4),
            "tokens_per_s_1r": fleet_res["tokens_per_s_1r"],
            "tokens_per_s_4r": fleet_res["tokens_per_s_4r"],
            "failovers": fleet_res.get("failovers"),
            "rolling_deploy": fleet_res.get("rolling_deploy"),
        }

    # input-pipeline row (ISSUE 14): records/s through the full
    # records → decode → shuffle → jit-augment → stage() → fit path,
    # with the host-gap split proving the input hides behind the step
    # (acceptance: records-fed gap ≤ 2% of step time, measured by the
    # existing fit_host_gap_seconds gauge)
    if input_res is not None and "records_per_s" in input_res:
        out["input_pipeline_records_per_s"] = {
            "metric": "input_pipeline_records_per_s",
            "value": input_res["records_per_s"],
            "unit": "records/s",
            "input_host_gap_pct": input_res["gap_pct_records"],
            "synthetic_host_gap_pct": input_res["gap_pct_synthetic"],
            "step_overhead_pct": input_res["step_overhead_pct"],
            "augment_ms_per_batch": input_res["augment_ms_per_batch"],
        }
        out["input_host_gap_pct"] = input_res["gap_pct_records"]

    # training-health telemetry row (ISSUE 15): stats-on-vs-off overhead
    # (acceptance ≤2%, same bar family as tracing's ≤1%) plus the rules
    # engine's verdicts from the listener phase — the round's evidence
    # that model-internals observability rides inside the train dispatch
    if health_res is not None and "health_stats_overhead_pct" in health_res:
        out["health_stats_overhead_pct"] = health_res[
            "health_stats_overhead_pct"]
        out["training_health"] = {
            "overhead_pct": health_res["health_stats_overhead_pct"],
            "syncs_outside_windows": health_res["syncs_outside_windows"],
            "listener_windows": health_res["listener_windows"],
            "listener_syncs": health_res["listener_syncs"],
            "report": health_res.get("training_health"),
        }

    # transformer flagship row: a SECOND named metric alongside the
    # ResNet headline (which keeps the vs_baseline trajectory unbroken);
    # same denominator convention — measured MFU ÷ the 40% north star
    if tlm_res is not None and "mfu" in tlm_res:
        out["transformer_lm_mfu"] = {
            "metric": "transformer_lm_mfu",
            "value": tlm_res["mfu"],
            "unit": "mfu",
            "vs_baseline": round(tlm_res["mfu"] / 0.40, 4),
            "tokens_per_sec": tlm_res["tokens_per_sec"],
            "model_flops_per_token": tlm_res["model_flops_per_token"],
        }

    if resnet_res is not None:
        out.update({
            "metric": "resnet50_train_throughput_per_chip",
            "value": resnet_res["examples_per_sec"],
            "unit": "examples/sec",
            "vs_baseline": round(resnet_res["mfu"] / 0.40, 4),
        })
    elif lenet_res is not None:
        out.update({
            "metric": "lenet_mnist_train_throughput",
            "value": lenet_res["examples_per_sec"],
            "unit": "examples/sec",
            "vs_baseline": round(lenet_res["mfu"] / 0.40, 4),
        })
    else:
        out.update({"metric": "bench_failed", "value": 0.0,
                    "unit": "examples/sec", "vs_baseline": 0.0})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
