"""XLA compile-time tuning knobs for the hot train-step programs.

The reference's analog is the cuDNN algo-selection knobs threaded through
``CudnnConvolutionHelper`` (``/root/reference/deeplearning4j-cuda/src/main/
java/org/deeplearning4j/nn/layers/convolution/CudnnConvolutionHelper.java:48``
— algo mode, workspace limits). Here the backend seam is the XLA TPU
compiler: per-program ``compiler_options`` passed to ``jax.jit``.

No options are set by default (measured on ResNet-50 @ v5e: the
latency-hiding scheduler is within noise of the default schedule once
buffers are donated; see PERF.md). Opt in via the ``DL4JTPU_XLA_OPTS`` env
var — comma-separated ``flag=value`` pairs, e.g.
``DL4JTPU_XLA_OPTS=xla_tpu_scoped_vmem_limit_kib=32768``. Set it to the
literal ``off`` to disable all options (including any future defaults).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import threading
from typing import Any, Callable, Dict, Optional, Tuple

logger = logging.getLogger("deeplearning4j_tpu")

_TRAIN_DEFAULTS: Dict[str, str] = {}


def scan_unroll() -> int:
    """lax.scan unroll factor for the K-step train loops (fit_scan /
    fit_repeated). 2 by default — XLA removes inter-iteration carry copies
    between the paired bodies (~1.2 ms/step on ResNet-50 @ v5e); override
    with DL4JTPU_SCAN_UNROLL (8 measured slower, larger only pads compile
    time)."""
    n = int(os.environ.get("DL4JTPU_SCAN_UNROLL", "2"))
    if n < 1:
        raise ValueError(f"DL4JTPU_SCAN_UNROLL must be >= 1, got {n}")
    return n


def train_step_options() -> Optional[Dict[str, str]]:
    """compiler_options dict for train-step jits (None = compiler defaults)."""
    raw = os.environ.get("DL4JTPU_XLA_OPTS", "")
    if raw.strip().lower() == "off":
        return None
    import jax
    if jax.default_backend() != "tpu":
        # TPU flags are rejected by the CPU/GPU compilers (tests run on a
        # virtual CPU mesh) — apply only the user's explicit opts there
        opts = {}
    else:
        opts = dict(_TRAIN_DEFAULTS)
    for pair in raw.split(","):
        pair = pair.strip()
        if not pair:
            continue
        if "=" not in pair:
            raise ValueError(
                f"DL4JTPU_XLA_OPTS entry {pair!r} is not flag=value")
        k, v = pair.split("=", 1)
        opts[k.strip()] = v.strip()
    return opts or None


# ----------------------------------------------------------------------
# persistent compilation cache
# ----------------------------------------------------------------------

def use_compile_cache() -> str:
    """Point this process at the persistent XLA compilation cache and
    return its directory. Where the caller placed one
    (``JAX_COMPILATION_CACHE_DIR``) JAX already uses it and this sets no
    other; otherwise the cache lives at a FIXED path inside the checkout,
    ``<repo>/.jax_cache`` — never a temp name, pid or timestamp, because
    the directory is part of the cache key and one that moves never hits.
    Every program is cached whatever its compile time, so a second run of
    the same command finds all of them. For entry-point scripts
    (``chip_smoke.py``, ``bench.py``); library code never calls this."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(repo, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ----------------------------------------------------------------------
# trace-time routing flags
# ----------------------------------------------------------------------

_interpret_kernels = False


@contextlib.contextmanager
def interpret_kernels():
    """Ask for the Pallas kernels to run in interpret mode while the
    context is active: how the CPU test suite (``tests/conftest.py``) and
    ``chip_smoke.py --small`` exercise the kernel math without a TPU.
    Read at TRACE time like the routing flags (:func:`trace_env_key`
    carries it). Interpret mode is only ever ASKED for, never inferred
    from the platform, so a program that was meant for the chip cannot
    end up in a slow interpreted kernel without anyone noticing."""
    global _interpret_kernels
    prev, _interpret_kernels = _interpret_kernels, True
    try:
        yield
    finally:
        _interpret_kernels = prev


def kernel_mode() -> Optional[str]:
    """The ONE place that decides how a Pallas kernel runs in this
    process: ``"interpret"`` inside :func:`interpret_kernels`,
    ``"mosaic"`` (compiled by the TPU compiler) on the ``tpu`` backend,
    ``None`` anywhere else. None means there is no kernel to run: the
    routers (``flash_available`` / ``ring_flash_available``) take the XLA
    path whatever ``DL4JTPU_FLASH_ATTENTION`` says."""
    if _interpret_kernels:
        return "interpret"
    import jax
    return "mosaic" if jax.default_backend() == "tpu" else None


def trace_env_key() -> str:
    """Cache-key suffix for jitted step functions capturing everything
    that is read at TRACE time and baked into the compiled program
    (the flash-attention routing flags and :func:`kernel_mode`). The
    runtimes append it to their ``_jit_cache`` keys, so flipping
    ``DL4JTPU_FLASH_ATTENTION`` / ``DL4JTPU_FLASH_BWD`` takes effect on
    the next call — a fresh trace under the new routing — without manual
    jit-cache clearing."""
    return (f"fa={os.environ.get('DL4JTPU_FLASH_ATTENTION', 'auto')}"
            f"|fabwd={os.environ.get('DL4JTPU_FLASH_BWD', 'pallas')}"
            f"|kern={kernel_mode()}")


def pow2_bucket(n: int, cap: int) -> int:
    """Round ``n`` up to the next power of two, capped at ``cap`` (itself
    a power of two): the shared rule for every trace-ladder axis (the
    decode engine's lane buckets AND its fused block length), so any
    requested size maps into a FIXED, enumerable trace set and
    ``jit_retraces_total`` stays pinned however callers configure it."""
    if n < 1:
        raise ValueError(f"bucketed size must be >= 1, got {n}")
    if cap < 1 or (cap & (cap - 1)):
        raise ValueError(f"cap must be a power of two, got {cap}")
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


def keyed_jit(cache: Dict[str, Any], fn: Callable, *, extra: str = "",
              wrap: Optional[Callable[[Callable], Callable]] = None,
              name: Optional[str] = None, registry=None, **jit_kw):
    """ONE copy of the trace-env-keyed jit-cache lookup the sharded
    trainers use: returns the jit of ``fn`` cached under the CURRENT
    :func:`trace_env_key`, compiling a fresh one when a routing flag has
    flipped since the cached trace (the trainer-side analog of the net
    runtimes' ``_jit_cache`` key suffix).

    ``extra`` extends the key for callers that maintain several traces per
    flag state (e.g. the decode engine's per-bucket step functions);
    ``wrap`` post-processes a freshly built jit exactly once (e.g.
    :func:`retrace_guard`), so the wrapper's own state survives cache
    hits. ``name`` (when ``wrap`` is not given) wraps the fresh jit in a
    :func:`retrace_guard` under that name — retrace counting plus the
    compile-time/cost-analysis metrics — so every keyed trainer step is a
    measured jit site without each caller re-spelling the guard."""
    import jax
    key = trace_env_key() + (f"|{extra}" if extra else "")
    jitted = cache.get(key)
    if jitted is None:
        jitted = jax.jit(fn, **jit_kw)
        if wrap is not None:
            jitted = wrap(jitted)
        elif name is not None:
            jitted = retrace_guard(jitted, name, registry)
        cache[key] = jitted
    return jitted


# ----------------------------------------------------------------------
# compiled-cost metrics: measured FLOPs/bytes + compile wall time
# ----------------------------------------------------------------------

# compile times span ms (tiny eval programs) to minutes (large train
# steps on a real TPU) — the default RPC-latency buckets top out at 10s
_COMPILE_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                    10.0, 30.0, 60.0, 120.0, 300.0)


def _reg(registry=None):
    from . import metrics as _metrics
    return registry if registry is not None else _metrics.REGISTRY


def compile_seconds_histogram(registry=None):
    return _reg(registry).histogram(
        "xla_compile_seconds",
        "Wall time of each fresh compilation per guarded jitted function: "
        "the ahead-of-time lower().compile() of a program compiled before "
        "its first call (the decode ladder), else the compiling first call "
        "(trace + XLA compile + the launch)", ("fn",),
        buckets=_COMPILE_BUCKETS)


def compile_stage_counter():
    return _reg().counter(
        "xla_compile_stage_seconds_total",
        "Seconds JAX reports for each stage of every compilation of the "
        "process, summed over threads (work, not wall): trace and lower "
        "run under the interpreter's lock, backend is the XLA compile or, "
        "on a persistent-cache hit, the look-up, whose own seconds are "
        "cache_retrieval", ("stage",))


def compile_cache_counter():
    return _reg().counter(
        "xla_compile_cache_total",
        "Compilations that asked the persistent cache (request) and those "
        "it served (hit); a miss is a request that was no hit",
        ("result",))


def slowest_compile_gauge():
    return _reg().gauge(
        "xla_compile_slowest_seconds",
        "The largest single sample of xla_compile_seconds so far, whatever "
        "registry took it: the floor of a concurrent warm-up's wall")


def startup_phase_histogram(registry=None):
    return _reg(registry).histogram(
        "startup_phase_seconds",
        "Wall time of each phase of start-up: pre_init (process start to "
        "the first init()), init, engine_build, warmup and its parts "
        "warmup.plan, warmup.compile, warmup.run, and cost_analysis (the "
        "second lowering behind compiled_flops / compiled_bytes)",
        ("phase",), buckets=_COMPILE_BUCKETS)


def compiled_flops_gauge(registry=None):
    return _reg(registry).gauge(
        "compiled_flops",
        "HLO cost-analysis FLOPs of the most recently compiled program "
        "per guarded jitted function (measured from the lowered module, "
        "not an analytic formula)", ("fn",))


def compiled_bytes_gauge(registry=None):
    return _reg(registry).gauge(
        "compiled_bytes",
        "HLO cost-analysis bytes accessed of the most recently compiled "
        "program per guarded jitted function", ("fn",))


def cost_analysis_enabled() -> bool:
    """``DL4JTPU_COST_ANALYSIS=0`` skips the per-compile HLO cost
    analysis (the lowering re-walk costs ~0.1s per fresh signature on a
    small transformer — ~4% of its compile time — but a caller compiling
    thousands of tiny programs may want it off)."""
    return os.environ.get("DL4JTPU_COST_ANALYSIS", "1") != "0"


def compiled_costs(fn: Callable, *args, **kwargs) -> Optional[Dict[str, float]]:
    """Measured cost of the program ``fn`` compiles for these arguments:
    ``{"flops": ..., "bytes_accessed": ...}`` from the lowered module's
    HLO cost analysis, or None when unavailable.

    Uses ``Lowered.cost_analysis()`` — NO second backend compile: after
    the jit call itself compiled, re-lowering rides the warm jaxpr cache
    and the analysis walks unoptimized HLO (matmul FLOPs are identical to
    the optimized program's; elementwise counts differ by <1% on the
    models in-tree). Safe after donation: lowering only needs avals,
    never the (possibly consumed) buffers."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        ca = lower(*args, **kwargs).cost_analysis()
    except Exception:
        return None
    if ca is None:
        return None
    out: Dict[str, float] = {}
    if ca.get("flops"):
        out["flops"] = float(ca["flops"])
    if ca.get("bytes accessed"):
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out or None


# ----------------------------------------------------------------------
# start-up, timed from inside
# ----------------------------------------------------------------------

_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
# JAX's own cache_misses event fires only when an entry is WRITTEN, under
# size and time thresholds: a miss is a request that was no hit
_CACHE_RESULT_OF_EVENT = {
    "/jax/compilation_cache/compile_requests_use_cache": "request",
    "/jax/compilation_cache/cache_hits": "hit",
}
_lock = threading.Lock()
_done_once = set()


def _first_time(what: str) -> bool:
    with _lock:
        first = what not in _done_once
        _done_once.add(what)
    return first


def listen_to_compiles() -> None:
    """Tell every compilation of the process apart, by stage and by what
    the persistent cache said: ``jax.monitoring`` listeners, registered
    once a process (a second call adds nothing), that keep
    ``xla_compile_stage_seconds_total{stage}`` and
    ``xla_compile_cache_total{result}`` in the process registry. Called
    where a guarded jit site is built and where a net is initialised, so
    the counts start before the first program compiles."""
    if not _first_time("listen"):
        return
    import jax
    stages, cache = compile_stage_counter(), compile_cache_counter()

    def on_duration(event, duration_secs, **_):
        stage = _STAGE_OF_EVENT.get(event)
        if stage is not None:
            stages.inc(duration_secs, stage=stage)

    def on_event(event, **_):
        result = _CACHE_RESULT_OF_EVENT.get(event)
        if result is not None:
            cache.inc(result=result)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def process_age_s() -> Optional[float]:
    """Seconds since the kernel started this process (its record in
    ``/proc/self/stat``), or None where there is no ``/proc``."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
    except OSError:
        return None
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def startup_region(name: str, registry=None, parent=None):
    """The :class:`~.tracing.region` of one start-up phase: observed into
    ``startup_phase_seconds{phase}`` (the phase is the region's name
    without a leading ``startup.``), a host span on the profiler's clock,
    and a span of whatever trace is open on this thread (or of
    ``parent``'s, for a phase on a worker thread)."""
    from . import tracing as _tracing
    return _tracing.region(name, startup_phase_histogram(registry),
                           **_tracing.joining(parent),
                           phase=name.removeprefix("startup."))


@contextlib.contextmanager
def init_region(net):
    """What both runtimes' ``init()`` run inside: the ``startup.init``
    phase, which ends once the parameters and the updater's state are on
    the device. The first one of the process also stamps ``pre_init``:
    interpreter, imports and configuration, from the process's start."""
    import jax
    listen_to_compiles()
    if _first_time("pre_init"):
        age = process_age_s()
        if age is not None:
            startup_phase_histogram().observe(age, phase="pre_init")
    with startup_region("startup.init"):
        yield
        jax.block_until_ready((net.params, net.state, net.updater_state))


# ----------------------------------------------------------------------
# retrace guard
# ----------------------------------------------------------------------

def _abstract_signature(args: tuple, kwargs: dict) -> Tuple:
    """The (shape, dtype) skeleton jit keys its compilation cache on —
    arrays by shape+dtype, python scalars/static args by value, anything
    else by type."""
    import jax

    def leaf_sig(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return ("a", tuple(leaf.shape), str(leaf.dtype))
        if leaf is None or isinstance(leaf, (bool, int, float, str)):
            return ("v", leaf)
        return ("t", type(leaf).__name__)

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (str(treedef), tuple(leaf_sig(l) for l in leaves))


def retrace_guard(fn: Callable, name: str, registry=None) -> Callable:
    """Wrap a jitted callable to count compilations into
    ``jit_retraces_total{fn=name}`` and record each fresh compile's
    measured cost.

    Each call computes the abstract signature of its arguments (shape +
    dtype skeleton — the same thing jit keys its cache on); a signature
    never seen by THIS wrapper increments the counter. Steady-state
    training therefore pins the counter at exactly 1 per guarded step
    function, and the no-retrace regression test enforces it on CPU.

    A fresh signature additionally records:

    - ``xla_compile_seconds{fn}`` — wall time of the compiling call
      (trace + XLA compile; dispatch is async, so execution is excluded),
      a ``compile.program`` region. A program compiled ahead of its first
      call (``wrapped.precompile(args)``, the decode ladder's warm-up)
      observes its ``lower().compile()`` there instead, and its first
      call, a look-up and a launch, observes nothing;
    - ``compiled_flops{fn}`` / ``compiled_bytes{fn}`` — the lowered
      program's HLO cost analysis (:func:`compiled_costs`), the MEASURED
      counterpart of the analytic formulas in bench.py — plus the latest
      analysis on ``wrapped.compiled_costs``; the second lowering it
      takes is the ``cost_analysis`` phase of ``startup_phase_seconds``;
    - a ``compile`` flight-recorder event (retraces after the first carry
      the differing signature, so a post-mortem dump names the churning
      input).

    ``DL4JTPU_RETRACE_WARN=1`` additionally logs every retrace after the
    first with the differing abstract signature — the fastest way to find
    which input's shape/dtype is churning the compile cache.
    """
    from . import flightrecorder as _flight
    from . import ingest as _ingest
    from . import tracing as _tracing
    listen_to_compiles()
    counter = _ingest.retrace_counter(registry)
    compile_hist = compile_seconds_histogram(registry)
    flops_gauge = compiled_flops_gauge(registry)
    bytes_gauge = compiled_bytes_gauge(registry)
    slowest = slowest_compile_gauge()
    seen: Dict[Tuple, int] = {}
    last: list = []
    ahead: Dict[Tuple, float] = {}    # signature -> its precompile's seconds

    def compile_region(parent=None):
        """The region a compilation of this program is timed by. Entered
        with ``with``, never through a helper that makes the call: a
        Python frame between the guard and the jitted call is part of
        what is lowered (the locations of the program's operations, which
        a Mosaic kernel's body keeps), and two of them cost the train
        step 0.9 s of lowering and a cache entry of its own (PR 37)."""
        return _tracing.region("compile.program", compile_hist,
                               attributes={"fn": name},
                               **_tracing.joining(parent), fn=name)

    def compiled_in(seconds: float) -> float:
        with _lock:
            if seconds > slowest.value():
                slowest.set(seconds)
        return seconds

    def precompile(args: tuple, parent=None) -> None:
        """Lower and compile the program for ``args`` now, on the calling
        thread (XLA compiles outside the interpreter's lock, so a pool of
        these overlaps): THIS is the sample ``xla_compile_seconds{fn}``
        gets for the signature. ``parent`` is the span that caused it,
        for a task on a worker thread."""
        with compile_region(parent) as r:
            fn.lower(*args).compile()
        ahead[_abstract_signature(args, {})] = compiled_in(r.seconds)

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        key = _abstract_signature(args, kwargs)
        if key in seen:
            return fn(*args, **kwargs)
        idx = seen[key] = len(seen)
        counter.inc(fn=name)
        if idx > 0 and os.environ.get("DL4JTPU_RETRACE_WARN") == "1":
            logger.warning(
                "retrace #%d of %s — new abstract signature:\n  now:  "
                "%s\n  prev: %s", idx, name, key[1],
                last[0][1] if last else "?")
        prev = last[0][1] if last else None
        last[:] = [key]
        if key in ahead:
            # compiled ahead of time: this call finds the executable
            out, dt = fn(*args, **kwargs), ahead[key]
        else:
            # the compiling call: trace + compile happen synchronously
            # inside it, execution is dispatched async — so the wall time
            # here IS the compile cost the caller paid
            with compile_region() as r:
                out = fn(*args, **kwargs)
            dt = compiled_in(r.seconds)
        event = {"fn": name, "signature_idx": idx,
                 "compile_seconds": round(dt, 4)}
        costs = None
        if cost_analysis_enabled():
            with startup_region("startup.cost_analysis", registry):
                costs = compiled_costs(fn, *args, **kwargs)
        if costs is not None:
            wrapped.compiled_costs = costs
            if "flops" in costs:
                flops_gauge.set(costs["flops"], fn=name)
                event["flops"] = costs["flops"]
            if "bytes_accessed" in costs:
                bytes_gauge.set(costs["bytes_accessed"], fn=name)
        if idx > 0:
            event["signature"] = str(key[1])
            event["prev_signature"] = str(prev)
        _flight.record("compile", **event)
        return out

    wrapped.signatures_seen = seen
    wrapped.compiled_costs = None
    wrapped.precompile = precompile
    return wrapped
