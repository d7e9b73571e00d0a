"""Host ingest & async dispatch: the pipeline stage between a batch source
and the jitted train step.

Parity: the reference splits this concern across ``AsyncDataSetIterator``
(L4 — ETL/compute overlap via a prefetch thread) and ``ParallelWrapper``
(L6 — dispatch overlap across workers). JAX dispatch is already
asynchronous, so the residual host costs in ``fit()`` are (1) blocking on
``float(loss)`` every step, (2) synchronous ``jax.device_put`` of each
host batch on the consumer thread, and (3) per-step Python dispatch
overhead. This module removes all three without changing training
numerics:

- :class:`LazyScore` — a loss that stays on device until somebody reads
  it. Listeners receive it through ``iteration_done``; ``float(score)``
  (or ``.value()``) performs the device→host sync and counts it into
  ``training_host_syncs_total``, so a listener at ``frequency=N`` costs
  exactly one sync per N steps and a listener that never reads the score
  costs zero.
- :class:`InflightWindow` — bounds how many dispatched steps may be in
  flight (default 2, ``DL4JTPU_MAX_INFLIGHT``). Blocking waits on the
  OLDEST step's completion (``block_until_ready``), which is a device
  fence, not a host transfer — the loss value never moves to the host.
- :func:`stage` — wraps any (x, y, mask) batch iterable with a
  background thread that ``jax.device_put``s each batch and blocks until
  the transfer lands, so the queue holds HBM-resident batches and the
  h2d DMA overlaps the previous step's compute. This is applied to every
  ``fit(iterator)`` call by default (``DL4JTPU_INGEST=0`` disables).
- :func:`coalesced` — opportunistically groups runs of K consecutive
  same-shape maskless batches for a single ``fit_scan`` dispatch.
  Off by default (the fused path derives per-step rng differently, so
  flipping it silently would change training draws); enable with
  ``DL4JTPU_COALESCE_K`` or ``fit(..., coalesce=K)``.

Observability (all into the PR-2 metrics registry): prefetch queue depth
gauge, h2d bytes/seconds counters, staged-batch counts, a
host-gap-between-dispatches histogram and the step's phases recorded by
the fit loop, and optional per-batch ingest spans when a tracer is
attached. Every phase is timed by ``util.tracing.region`` (``fit.step``
⊃ ``fit.hooks`` / ``fit.dispatch`` / ``fit.device_wait``,
``fit.source_wait`` between steps, ``ingest.stage`` in the staging
thread), so a profiler session shows them on the device trace's clock.
"""

from __future__ import annotations

import collections
import logging
import os
import queue
import threading
from typing import Iterable, Iterator, Optional, Tuple

import numpy as np

from . import faults as _faults
from . import flightrecorder as _flight
from . import metrics as _metrics
from .tracing import region

logger = logging.getLogger("deeplearning4j_tpu")


# ----------------------------------------------------------------------
# the shared producer/queue core (also backs the async dataset iterators)
# ----------------------------------------------------------------------

class ProducerQueue:
    """Bounded queue + stop-flag poison + sentinel + fail-fast error
    hand-off: the one copy of the producer/consumer machinery shared by
    :func:`stage` and ``datasets.iterator.AsyncDataSetIterator``.

    Producer side: ``put`` (gives up promptly once ``stop`` is set — the
    reset/close poison), ``fail(exc)`` then ``finish()`` in a finally.
    Consumer side: ``get`` returns the next item or ``SENTINEL``; pending
    producer errors raise as soon as they are observed, BEFORE any
    queued item is handed out. ``drain_and_join`` discards staged items
    (unblocking a producer stuck on a full queue) and reports whether
    the producer thread actually exited.
    """

    SENTINEL = object()

    def __init__(self, maxsize: int):
        self.queue: "queue.Queue" = queue.Queue(maxsize=max(1, maxsize))
        self.stop = threading.Event()
        self.error: Optional[BaseException] = None

    # -- producer side -------------------------------------------------

    def put(self, item, timeout: float = 0.05) -> bool:
        while not self.stop.is_set():
            try:
                self.queue.put(item, timeout=timeout)
                return True
            except queue.Full:
                continue
        return False

    def fail(self, exc: BaseException) -> None:
        self.error = exc

    def finish(self) -> None:
        self.put(self.SENTINEL)

    # -- consumer side -------------------------------------------------

    def raise_pending(self) -> None:
        if self.error is not None:
            err, self.error = self.error, None
            raise err

    def get(self, timeout: float = 0.05):
        """Next item or ``SENTINEL``. Fail fast: a producer error raises
        at the first observation, even with items still queued — and a
        sentinel re-checks, so an error set right before ``finish()``
        cannot slip out as a clean end-of-stream."""
        while True:
            self.raise_pending()
            try:
                item = self.queue.get(timeout=timeout)
            except queue.Empty:
                continue
            if item is self.SENTINEL:
                self.raise_pending()
            return item

    def drain_and_join(self, thread: threading.Thread,
                       join_timeout: float = 5.0) -> bool:
        """Poison the producer, discard staged items, wait for the thread.
        Returns False if the thread is still alive (stuck inside the
        source) — callers that would restart over the same source must
        treat that as an error, not race a second producer against it."""
        self.stop.set()
        try:
            while True:
                self.queue.get_nowait()
        except queue.Empty:
            pass
        thread.join(timeout=join_timeout)
        return not thread.is_alive()


# ----------------------------------------------------------------------
# knobs
# ----------------------------------------------------------------------

def max_inflight_default() -> int:
    """Bounded dispatch window for fit() (``DL4JTPU_MAX_INFLIGHT``, default
    2: the current step computes while the next one stages + dispatches)."""
    n = int(os.environ.get("DL4JTPU_MAX_INFLIGHT", "2"))
    if n < 1:
        raise ValueError(f"DL4JTPU_MAX_INFLIGHT must be >= 1, got {n}")
    return n


def staging_enabled() -> bool:
    return os.environ.get("DL4JTPU_INGEST", "1") != "0"


def coalesce_k_default() -> int:
    """Run length for same-shape batch coalescing (0/1 = off)."""
    return int(os.environ.get("DL4JTPU_COALESCE_K", "0"))


def already_staged(data) -> bool:
    """True when the source already ships device-resident batches (an
    AsyncDataSetIterator constructed with ``device_put=True``) — wrapping
    it again would only add a queue hop."""
    return bool(getattr(data, "device_put", False))


# ----------------------------------------------------------------------
# metric families (get-or-create: idempotent across pipelines)
# ----------------------------------------------------------------------

_GAP_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 1.0)


def _reg(registry=None) -> _metrics.MetricsRegistry:
    return registry if registry is not None else _metrics.REGISTRY


def sync_counter(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "training_host_syncs_total",
        "Device->host loss transfers forced by score readers")


def retrace_counter(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "jit_retraces_total",
        "Distinct abstract signatures (= compilations) seen per guarded "
        "jitted function", ("fn",))


def _queue_gauge(registry=None) -> _metrics.Gauge:
    return _reg(registry).gauge(
        "ingest_queue_depth", "Staged batches waiting in the prefetch queue",
        ("stage",))


def _h2d_bytes(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "ingest_h2d_bytes_total", "Host bytes shipped to device by ingest",
        ("stage",))


def _h2d_seconds(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "ingest_h2d_seconds_total",
        "Producer-thread seconds spent staging (device_put + transfer wait)",
        ("stage",))


def _staged_counter(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "ingest_batches_staged_total", "Batches staged by ingest", ("stage",))


def host_gap_histogram(registry=None) -> _metrics.Histogram:
    return _reg(registry).histogram(
        "fit_host_gap_seconds",
        "Host time between consecutive step dispatches in fit() (batch "
        "fetch + listener work; device compute excluded)", ("model",),
        buckets=_GAP_BUCKETS)


def records_read_counter(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "pipeline_records_read_total",
        "Records decoded from shard files by the record input pipeline "
        "(data.pipeline)", ("stage",))


def records_skipped_counter(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "pipeline_records_skipped_total",
        "Corrupt records dropped by the skip-with-counter policy — any "
        "nonzero value on a production run means a shard needs fsck",
        ("stage",))


def augment_seconds_counter(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "pipeline_augment_seconds_total",
        "Producer-side seconds spent in the jitted augmentation stage "
        "(host dispatch wall — the device compute overlaps the step)",
        ("stage",))


def pipeline_batches_counter(registry=None) -> _metrics.Counter:
    return _reg(registry).counter(
        "pipeline_batches_total",
        "Batches assembled by the record input pipeline", ("stage",))


def step_phase_histogram(registry=None) -> _metrics.Histogram:
    return _reg(registry).histogram(
        "fit_step_phase_seconds",
        "One fit() step split where it happens: dispatch (fit_batch / "
        "fit_scan returns), device_wait (blocked on the oldest in-flight "
        "step), hooks (flight record, fault seam, session)",
        ("model", "phase"), buckets=_GAP_BUCKETS)


# ----------------------------------------------------------------------
# LazyScore
# ----------------------------------------------------------------------

class LazyScore:
    """A training loss that stays on device until read.

    ``float(score)`` / ``score.value()`` transfers it to the host (once;
    the result is cached) and increments ``training_host_syncs_total``.
    Listeners that gate on ``iteration % frequency`` therefore pay one
    sync per window; listeners that never read the score pay none.
    """

    __slots__ = ("_device", "_host", "_registry")

    def __init__(self, device_value, registry=None):
        self._device = device_value
        self._host: Optional[float] = None
        self._registry = registry

    @property
    def resolved(self) -> bool:
        return self._host is not None

    def value(self) -> float:
        if self._host is None:
            sync_counter(self._registry).inc()
            v, self._device = self._device, None
            self._host = float(v)
        return self._host

    def __float__(self) -> float:
        return self.value()

    def __repr__(self) -> str:
        return (f"LazyScore({self._host})" if self.resolved
                else "LazyScore(<on device>)")


def as_listener_score(loss, registry=None):
    """Wrap a device loss for listener delivery; host scalars (the
    fit_scan/fit_repeated replay path, which already paid one bulk
    transfer for all K losses) pass through untouched."""
    if isinstance(loss, (int, float, np.floating, np.integer)):
        return loss
    return LazyScore(loss, registry)


# ----------------------------------------------------------------------
# InflightWindow
# ----------------------------------------------------------------------

class InflightWindow:
    """Bound the number of dispatched-but-unfinished train steps.

    ``push`` records one step's output (any array pytree leaf works; the
    loss is the natural token). Once more than ``max_inflight`` steps are
    outstanding, the oldest is waited on with ``block_until_ready`` — a
    completion fence that keeps the dispatch queue short without ever
    transferring the value to the host.
    """

    def __init__(self, max_inflight: Optional[int] = None):
        self.max_inflight = (max_inflight_default() if max_inflight is None
                             else max(1, int(max_inflight)))
        self._pending: collections.deque = collections.deque()

    def push(self, token) -> None:
        self._pending.append(token)
        while len(self._pending) > self.max_inflight:
            oldest = self._pending.popleft()
            if hasattr(oldest, "block_until_ready"):
                oldest.block_until_ready()

    def drain(self) -> None:
        while self._pending:
            oldest = self._pending.popleft()
            if hasattr(oldest, "block_until_ready"):
                oldest.block_until_ready()


# ----------------------------------------------------------------------
# background device staging
# ----------------------------------------------------------------------

class _StagedStream:
    """Iterator over device-staged batches produced by a background thread.

    The producer pulls (x, y, mask)-style tuples from ``source``,
    ``jax.device_put``s every array element (descending into lists, so
    MultiDataSet-style multi-input batches stage too), BLOCKS until the
    transfer completes (so queued batches are HBM-resident, and the DMA
    overlaps the consumer's current step), and enqueues. Errors from the
    source surface on the consumer as soon as they are observed.
    ``close()`` (also called on exhaustion/GC) stops the producer
    promptly.
    """

    def __init__(self, source: Iterable[Tuple], *, stage_name: str,
                 device=None, device_put: bool = True, queue_size: int = 2,
                 registry=None, tracer=None):
        self.stage_name = stage_name
        self.device = device
        self.device_put = device_put
        self.registry = registry
        self.tracer = tracer
        self._source = source
        self._pq = ProducerQueue(queue_size)
        self._finished = False
        self._depth = _queue_gauge(registry)
        self._depth.set_function(self._pq.queue.qsize, stage=stage_name)
        self._bytes = _h2d_bytes(registry)
        self._seconds = _h2d_seconds(registry)
        self._staged = _staged_counter(registry)
        self._thread = threading.Thread(
            target=self._producer, name=f"ingest-{stage_name}", daemon=True)
        self._thread.start()

    # -- producer side -------------------------------------------------

    def _stage_one(self, batch: Tuple) -> Tuple:
        import jax
        host_bytes = 0

        def put_el(el):
            nonlocal host_bytes
            if isinstance(el, (list, tuple)):   # multi-input/-output batch
                return type(el)(put_el(e) for e in el)
            if el is None or not hasattr(el, "shape"):
                return el
            if not isinstance(el, jax.Array):
                host_bytes += int(getattr(el, "nbytes", 0))
            return jax.device_put(el, self.device)

        with region("ingest.stage", tracer=self.tracer,
                    attributes={"stage": self.stage_name}) as staging:
            staged = tuple(put_el(el) for el in batch)
            # wait for the DMA here, on the producer thread — that wait
            # IS the overlap with the consumer's in-flight step
            for leaf in jax.tree_util.tree_leaves(staged):
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
            staging.set_attribute("bytes", host_bytes)
        self._seconds.inc(staging.seconds, stage=self.stage_name)
        if host_bytes:
            self._bytes.inc(host_bytes, stage=self.stage_name)
        self._staged.inc(stage=self.stage_name)
        return staged

    def _producer(self) -> None:
        try:
            for batch in self._source:
                if self._pq.stop.is_set():
                    return
                if self.device_put:
                    batch = self._stage_one(batch)
                else:
                    self._staged.inc(stage=self.stage_name)
                if not self._pq.put(batch):
                    return
        except BaseException as e:   # surfaced on the consumer side
            self._pq.fail(e)
        finally:
            self._pq.finish()

    # -- consumer side -------------------------------------------------

    def __iter__(self) -> Iterator[Tuple]:
        return self

    def __next__(self) -> Tuple:
        if self._finished:
            raise StopIteration
        try:
            item = self._pq.get()
        except BaseException:
            self._finished = True
            raise
        if item is ProducerQueue.SENTINEL:
            self._finished = True
            raise StopIteration
        return item

    def close(self) -> None:
        """Stop the producer (bounded by one in-flight batch) and release
        the queue. Best effort: nothing restarts over this source, so a
        producer stuck inside it is left to die with the process."""
        self._pq.drain_and_join(self._thread)
        self._finished = True

    def __del__(self):
        try:
            self._pq.stop.set()
        except Exception:
            pass


def stage(source: Iterable[Tuple], *, stage_name: str = "fit", device=None,
          device_put: bool = True, queue_size: int = 2, registry=None,
          tracer=None) -> _StagedStream:
    """Wrap a batch iterable with background device staging (double-
    buffered by default: one batch staging while one waits).

    ``device_put=False`` keeps batches on host and only overlaps the
    source's own batch-preparation work — the right mode for sharded
    trainers that place inputs with their own shardings.
    """
    return _StagedStream(source, stage_name=stage_name, device=device,
                         device_put=device_put, queue_size=queue_size,
                         registry=registry, tracer=tracer)


# ----------------------------------------------------------------------
# same-shape coalescing
# ----------------------------------------------------------------------

def _batch_sig(x, y) -> Optional[Tuple]:
    if not (hasattr(x, "shape") and hasattr(y, "shape")):
        return None
    return (tuple(x.shape), str(getattr(x, "dtype", "?")),
            tuple(y.shape), str(getattr(y, "dtype", "?")))


def coalesced(batches: Iterable[Tuple], k: int) -> Iterator[Tuple[str, Tuple]]:
    """Group runs of K consecutive same-shape maskless batches.

    Yields ``("scan", (xs, ys))`` with ``xs``/``ys`` stacked along a new
    leading axis for exactly-K runs, and ``("step", (x, y, mask))`` for
    everything else (masked batches, shape changes, sub-K tails — tails
    run as single steps rather than compiling a second scan length).
    Multi-input graph batches (lists of arrays) are never coalesced.
    """
    if k < 2:
        for b in batches:
            yield ("step", b)
        return
    import jax.numpy as jnp
    buf: list = []
    sig = None

    def _flush():
        for x, y in buf:
            yield ("step", (x, y, None))
        buf.clear()

    for b in batches:
        x, y, m = b[0], b[1], (b[2] if len(b) > 2 else None)
        s = _batch_sig(x, y) if m is None else None
        if s is None:
            yield from _flush()
            sig = None
            yield ("step", b)
            continue
        if buf and s != sig:
            yield from _flush()
        sig = s
        buf.append((x, y))
        if len(buf) == k:
            xs = jnp.stack([x for x, _ in buf])
            ys = jnp.stack([y for _, y in buf])
            buf.clear()
            yield ("scan", (xs, ys))
    yield from _flush()


# ----------------------------------------------------------------------
# the shared async fit loop (MultiLayerNetwork + ComputationGraph)
# ----------------------------------------------------------------------

def run_fit_loop(net, data, labels, mask, epochs: int,
                 coalesce: Optional[int], *, model_label: str,
                 session=None) -> None:
    """The dispatch-asynchronous epoch loop behind both runtimes' ``fit``.

    Per epoch: lazily reset the source (at epoch START, so the final
    epoch never restarts a producer whose work would be discarded), wrap
    iterator sources in background device staging, then dispatch steps
    behind an :class:`InflightWindow`, recording the host gap between
    consecutive dispatches. Coalescing (``k >= 2``) routes exact-K
    same-shape runs through ``fit_scan``; with listeners attached it
    stays off unless the caller passed ``coalesce`` explicitly (listeners
    get replayed host scores there, i.e. per-step host values).

    Every dispatched step first passes the ``"training.step"`` fault seam
    (chaos tests script kills/hangs at exact step boundaries). With a
    ``session`` (``util.durable.DurableSession``) attached, the loop also
    taps the batch stream for data-source cursors (BEFORE staging, so
    cursors are recorded in production order), reports each step for
    checkpointing/watchdog petting, and — when the session asks to stop
    (preemption, max_steps) — drains the in-flight window and returns
    cleanly WITHOUT counting the partial epoch.

    Observability riders: every dispatched step lands a ``train_step``
    flight-recorder event (the black box a watchdog/preemption dump
    replays); each step runs under a ``fit.step`` profiler step
    annotation with its phases timed into
    ``fit_step_phase_seconds{model, phase}`` (the wait for the next batch
    between steps stays ``fit_host_gap_seconds``); and
    ``DL4JTPU_PROFILE_STEPS=start:stop[:dir]``
    brackets exactly that dispatch range (0-based, stop-exclusive,
    counted across epochs within this call) with a ``jax.profiler``
    capture — the in-flight window is drained before the profiler stops,
    so the bracketed steps' device work lands inside the trace.
    """
    single = (labels is not None or hasattr(data, "shape")
              or hasattr(data, "features")
              or (isinstance(data, tuple) and len(data) in (2, 3)))
    k = coalesce_k_default() if coalesce is None else int(coalesce)
    if net.listeners and coalesce is None and k >= 2:
        # listeners demand per-step host-value semantics; the env opt-in
        # alone does not override them — say so instead of silently
        # benchmarking without fusion
        logger.info(
            "DL4JTPU_COALESCE_K=%d ignored: %d listener(s) attached — "
            "pass fit(..., coalesce=%d) to fuse anyway (listeners then "
            "get replayed host scores)", k, len(net.listeners), k)
        k = 0
    elif net.listeners and coalesce is None:
        k = 0
    from jax.profiler import StepTraceAnnotation

    from . import profiling as _profiling
    gap_hist = host_gap_histogram()
    phase_hist = step_phase_histogram()

    def phase(name: str) -> region:
        return region(f"fit.{name}", phase_hist, model=model_label,
                      phase=name)

    profile_range = _profiling.profile_steps_env()
    capture = (_profiling.StepCapture(profile_range[2])
               if profile_range is not None else None)
    dispatch_idx = 0
    # a session resuming a mid-epoch cursor must not "revive" the source
    # on its first epoch: a cursor at the exact end of the data means
    # zero batches remain, not restart-from-scratch
    revive_ok = not (session is not None
                     and getattr(session, "resuming", False))
    window = None
    try:
        for epoch in range(epochs):
            if hasattr(data, "reset") and (
                    epoch > 0 or (revive_ok and hasattr(data, "has_next")
                                  and not data.has_next())):
                data.reset()
            for l in net.listeners:
                l.on_epoch_start(net, net.epoch_count)
            window = InflightWindow()
            source = net._as_batches(data, labels, mask)
            if session is not None:
                source = session.tap(source, data)
            staged = None
            if not single and staging_enabled() and not already_staged(data):
                staged = stage(source, stage_name="fit",
                               tracer=getattr(net, "ingest_tracer", None))
                source = staged
            n_batches = 0
            gap = None           # seconds the last next() of the source took
            stopped = False
            batches = iter(coalesced(source, k))
            try:
                while True:
                    # rebinding `payload` lets go of the previous batch's
                    # device buffers: part of the gap, as it always was
                    with region("fit.source_wait") as wait:
                        kind, payload = next(batches, (None, None))
                    if kind is None:
                        break
                    if n_batches:
                        # the epoch's first fetch starts the producer and
                        # is not a gap between two dispatches
                        gap = wait.seconds
                        gap_hist.observe(gap, model=model_label)
                    with StepTraceAnnotation("fit.step",
                                             step_num=net.iteration_count):
                        with phase("hooks"):
                            if (capture is not None and not capture.active
                                    and dispatch_idx == profile_range[0]):
                                capture.start()
                            _flight.record(
                                "train_step", model=model_label,
                                epoch=net.epoch_count,
                                iteration=net.iteration_count,
                                dispatch=kind,
                                host_gap_s=(None if gap is None
                                            else round(gap, 6)))
                            _faults.check("training.step", {
                                "model": model_label,
                                "epoch": net.epoch_count,
                                "iteration": net.iteration_count,
                                "kind": kind})
                        with phase("dispatch"):
                            if kind == "scan":
                                xs, ys = payload
                                token = net.fit_scan(xs, ys)
                                consumed = int(xs.shape[0])
                            else:
                                token = net.fit_batch(*payload)
                                consumed = 1
                        with phase("device_wait"):
                            window.push(token)
                        dispatch_idx += 1
                        n_batches += consumed
                        if (capture is not None and capture.active
                                and dispatch_idx >= profile_range[1]):
                            # the bracketed steps' device work must land
                            # inside the capture, not after it
                            with phase("device_wait"):
                                window.drain()
                            capture.stop()
                        with phase("hooks"):
                            carry_on = (session is None
                                        or session.on_step(net, consumed))
                        if not carry_on:
                            # clean stop (preemption / max_steps): every
                            # dispatched step must land before the caller
                            # checkpoints the stop instant
                            with phase("device_wait"):
                                window.drain()
                            stopped = True
                            break
            finally:
                if staged is not None:
                    staged.close()
            if stopped:
                return      # partial epoch: no epoch_end, no count bump
            if n_batches == 0 and epoch > 0:
                raise ValueError(
                    f"epoch {epoch} yielded no batches — the data "
                    "iterator is exhausted and has no reset(); pass a "
                    "resettable iterator (e.g. "
                    "datasets.ListDataSetIterator) when epochs > 1")
            for l in net.listeners:
                l.on_epoch_end(net, net.epoch_count)
            net.epoch_count += 1
            if session is not None:
                session.on_epoch_boundary(net)
    finally:
        if capture is not None and capture.active:
            # same contract as the in-loop stop: the bracketed steps'
            # device work must land inside the trace, even when the fit
            # ran out of batches (or raised) before reaching `stop`
            if window is not None:
                try:
                    window.drain()
                except Exception:
                    pass    # a failed dispatch still ends the capture
            capture.stop()
