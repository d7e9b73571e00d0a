"""Profiling / tracing helpers: device trace capture, step timing, MFU.

Parity: the reference's three timing systems (SURVEY §5) —
``PerformanceListener.java:71-86`` (samples/sec), the Spark phase timers
(``StatsUtils.java:69-92``), and StatsListener's fwd/bwd breakdown — plus
the capability the reference never had: capturing a compiler-level device
trace. TPU-native: wraps ``jax.profiler`` (XPlane traces viewable in
TensorBoard / Perfetto) and provides the analytic-FLOPs MFU arithmetic used
by bench.py, so users chase utilization the way PERF.md does.

On-demand capture (the TensorBoard-profiler "capture profile" button,
minus TensorBoard): :func:`capture_trace` records for N seconds under a
process-wide single-capture guard (:class:`ProfilerBusy` while one is
running — the serving/UI servers' ``POST /profile`` maps it to 409), and
:class:`StepCapture` is the piecewise form ``run_fit_loop`` uses to
bracket an exact step range (``DL4JTPU_PROFILE_STEPS=start:stop[:dir]``,
0-based, stop-exclusive) — production profiling with no code changes.
"""

from __future__ import annotations

import contextlib
import logging
import os
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np

logger = logging.getLogger("deeplearning4j_tpu")

# bf16 peak FLOP/s per chip by TPU generation (public spec sheets)
PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,     # jax device_kind string for v5e
    "v5p": 459e12,
    "v6e": 918e12,
}


def peak_flops_per_sec(device=None) -> Optional[float]:
    """bf16 peak of the attached chip (first device by default), or None
    for an unknown device kind (CPU, GPU, a TPU generation not in the
    table) — callers decide what "no denominator" means for them:
    ``bench.py`` and :func:`mfu` raise (an MFU without a known chip is an
    error), and the live ``measured_mfu`` gauge degrades to a flops/sec
    gauge (util/ingest.py)."""
    import jax
    d = device or jax.devices()[0]
    kind = getattr(d, "device_kind", "").lower()
    for key, peak in PEAK_FLOPS.items():
        if key in kind:
            return peak
    return None


def mfu(examples_per_sec: float, flops_per_example: float,
        peak: Optional[float] = None) -> float:
    """Model FLOPs utilization: useful analytic FLOPs over peak. The
    standard convention — no recompute/rematerialization inflation.
    Raises ValueError when no ``peak`` is given and the attached device's
    peak is unknown (CPU/unknown kinds have no meaningful MFU)."""
    if peak is None:
        peak = peak_flops_per_sec()
        if peak is None:
            import jax
            raise ValueError(
                f"unknown device kind "
                f"{getattr(jax.devices()[0], 'device_kind', '?')!r} has no "
                "published peak — pass peak= explicitly (MFU is undefined "
                "without a denominator)")
    return examples_per_sec * flops_per_example / peak


# ----------------------------------------------------------------------
# device trace capture (single-capture guard)
# ----------------------------------------------------------------------

class ProfilerBusy(RuntimeError):
    """A device-trace capture is already in progress (the profiler
    supports exactly one at a time). HTTP surfaces answer 409."""


# one capture at a time, process-wide: jax.profiler.start_trace raises on
# a second concurrent start, so the guard turns a crash into a clean
# "busy" the HTTP endpoints can answer as 409
_capture_lock = threading.Lock()


def capture_in_progress() -> bool:
    return _capture_lock.locked()


def _acquire_capture() -> None:
    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusy(
            "a profiler capture is already in progress (one at a time)")


def default_capture_dir() -> str:
    """Capture root: ``DL4JTPU_PROFILE_DIR`` or the system temp dir."""
    return (os.environ.get("DL4JTPU_PROFILE_DIR")
            or os.path.join(tempfile.gettempdir(), "dl4jtpu_profile"))


def _new_run_dir(log_dir: Optional[str]) -> str:
    d = os.path.join(
        log_dir or default_capture_dir(),
        f"capture_{time.strftime('%Y%m%d_%H%M%S')}_{os.getpid()}")
    os.makedirs(d, exist_ok=True)
    return d


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a device trace (XPlane) into ``log_dir``; view in
    TensorBoard's profile plugin or Perfetto. Holds the single-capture
    guard: raises :class:`ProfilerBusy` if another capture is running."""
    import jax
    _acquire_capture()
    try:
        jax.profiler.start_trace(log_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    finally:
        _capture_lock.release()


def capture_trace(seconds: float, log_dir: Optional[str] = None) -> str:
    """Blocking on-demand capture: trace whatever the process's devices do
    for the next ``seconds``, into a fresh timestamped run directory
    (under ``log_dir`` / ``DL4JTPU_PROFILE_DIR`` / the temp dir). Returns
    the run directory; raises :class:`ProfilerBusy` while another capture
    is running — the ``POST /profile?seconds=N`` implementation."""
    seconds = float(seconds)
    if not 0 < seconds <= 300:
        raise ValueError(f"seconds must be in (0, 300], got {seconds}")
    run_dir = _new_run_dir(log_dir)
    with trace(run_dir):
        time.sleep(seconds)
    return run_dir


class StepCapture:
    """Piecewise capture for ``run_fit_loop``'s step bracketing: the
    profiler starts before step ``start`` and stops after step ``stop-1``
    (two separate calls, possibly epochs apart), holding the
    single-capture guard for the whole window. A busy profiler skips the
    capture with a warning instead of failing the training run."""

    def __init__(self, log_dir: Optional[str] = None):
        self.log_dir = log_dir
        self.run_dir: Optional[str] = None
        self.active = False

    def start(self) -> bool:
        import jax
        try:
            _acquire_capture()
        except ProfilerBusy:
            logger.warning(
                "DL4JTPU_PROFILE_STEPS capture skipped: another profiler "
                "capture is in progress")
            return False
        try:
            self.run_dir = _new_run_dir(self.log_dir)
            jax.profiler.start_trace(self.run_dir)
        except Exception:
            _capture_lock.release()
            raise
        self.active = True
        logger.info("profiler capture started into %s", self.run_dir)
        return True

    def stop(self) -> Optional[str]:
        if not self.active:
            return None
        import jax
        try:
            jax.profiler.stop_trace()
        finally:
            self.active = False
            _capture_lock.release()
        logger.info("profiler capture written to %s", self.run_dir)
        return self.run_dir


def profile_request(query: Dict[str, list]) -> Tuple[dict, int]:
    """The ``POST /profile?seconds=N[&dir=...]`` implementation shared by
    the serving and UI servers: parse-qs style query dict in,
    (json body, http code) out. Blocks the calling handler thread for
    the capture window; a concurrent capture answers 409."""
    try:
        seconds = float(query.get("seconds", ["1"])[0])
    except (TypeError, ValueError) as e:
        return {"error": f"bad seconds: {e}"}, 400
    log_dir = query.get("dir", [None])[0]
    try:
        run_dir = capture_trace(seconds, log_dir)
    except ProfilerBusy as e:
        return {"error": str(e)}, 409
    except ValueError as e:
        return {"error": str(e)}, 400
    return {"ok": True, "dir": run_dir, "seconds": seconds}, 200


# (kind label, jax memory_stats key) for the device_memory_bytes gauge
_MEMORY_KINDS = (("in_use", "bytes_in_use"),
                 ("peak", "peak_bytes_in_use"),
                 ("limit", "bytes_limit"))


def register_device_memory_gauges(registry=None):
    """Per-device callback gauges ``device_memory_bytes{device,kind}``
    (kind = in_use/peak/limit) sampled live at exposition time — HBM
    pressure on ``/metrics``, not just the UI pane. Idempotent; on
    backends without ``memory_stats()`` (CPU) the callbacks raise at
    exposition and the series are dropped, leaving only the family
    header."""
    from . import metrics as _metrics
    reg = registry if registry is not None else _metrics.REGISTRY
    g = reg.gauge(
        "device_memory_bytes",
        "Per-device memory from the backend's memory_stats(), sampled at "
        "exposition time (kind: in_use/peak/limit; series absent when "
        "the backend exposes no stats)", ("device", "kind"))
    try:
        import jax
        devices = jax.devices()
    except Exception:
        return g

    def sampler(dev, key):
        def fn() -> float:
            stats = dev.memory_stats()
            if not stats or key not in stats:
                raise LookupError(f"{key} unavailable on {dev}")
            return float(stats[key])
        return fn

    for d in devices:
        label = f"{d.platform}:{d.id}"
        for kind, key in _MEMORY_KINDS:
            g.set_function(sampler(d, key), device=label, kind=kind)
    return g


def profile_steps_env() -> Optional[Tuple[int, int, Optional[str]]]:
    """Parse ``DL4JTPU_PROFILE_STEPS=start:stop[:dir]`` (0-based step
    indices within one fit() call, stop-exclusive): the range of
    dispatched steps ``run_fit_loop`` brackets with a profiler capture.
    Returns (start, stop, dir) or None when unset."""
    raw = os.environ.get("DL4JTPU_PROFILE_STEPS", "").strip()
    if not raw:
        return None
    parts = raw.split(":", 2)
    if len(parts) < 2:
        raise ValueError(
            f"DL4JTPU_PROFILE_STEPS={raw!r} is not start:stop[:dir]")
    start, stop = int(parts[0]), int(parts[1])
    if start < 0 or stop <= start:
        raise ValueError(
            f"DL4JTPU_PROFILE_STEPS={raw!r}: need 0 <= start < stop")
    return start, stop, (parts[2] or None) if len(parts) > 2 else None


@dataclass
class StepTiming:
    mean_ms: float
    min_ms: float
    max_ms: float
    steps: int


def time_steps(step_fn: Callable[[], object], steps: int = 10,
               warmup: int = 2) -> StepTiming:
    """Wall-time a step callable with a proper device barrier per sample.

    The completion barrier is a device→host transfer of (a tiny slice of)
    the step result.
    """
    def run_once() -> float:
        t0 = time.perf_counter()
        out = step_fn()
        _barrier(out)
        return (time.perf_counter() - t0) * 1000.0

    for _ in range(warmup):
        run_once()
    samples = [run_once() for _ in range(steps)]
    return StepTiming(mean_ms=float(np.mean(samples)),
                      min_ms=float(np.min(samples)),
                      max_ms=float(np.max(samples)), steps=steps)


def _barrier(out) -> None:
    """d2h-read fence over EVERY device leaf of ``out`` — a multi-output
    step (params, opt_state, loss) can have its later outputs still
    executing when the first one lands, so fencing only the first leaf
    reports completion early."""
    import jax
    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "addressable_shards") or hasattr(leaf, "device"):
            flat = jax.numpy.ravel(leaf)
            np.asarray(flat[:1])
    # no device values returned: nothing to fence


# ----------------------------------------------------------------------
# Analytic FLOPs for common layer shapes (used by bench.py's configs)
# ----------------------------------------------------------------------

def conv2d_flops(out_h: int, out_w: int, kh: int, kw: int, cin: int,
                 cout: int) -> float:
    """MACs×2 for one example's conv forward."""
    return 2.0 * out_h * out_w * kh * kw * cin * cout


def dense_flops(n_in: int, n_out: int) -> float:
    return 2.0 * n_in * n_out


def lstm_flops(seq_len: int, n_in: int, hidden: int) -> float:
    """Gates: 4 matmuls of [n_in+hidden, hidden] per timestep."""
    return 2.0 * seq_len * 4 * (n_in + hidden) * hidden


def train_flops(forward_flops: float) -> float:
    """Training step ≈ 3× forward (fwd + dx + dW), the standard accounting."""
    return 3.0 * forward_flops
