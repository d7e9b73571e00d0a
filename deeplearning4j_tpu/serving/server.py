"""HTTP inference server with micro-batching + continuous-batched decode.

Endpoints:
  POST /predict   {"inputs": [[...], ...]} → {"outputs": [[...], ...]}
  POST /generate  {"prompt_ids": [...], "max_new_tokens": N,
                   "temperature": T, "eos_id": id, "timeout_s": s}
                  → {"tokens": [...], "finish_reason": "eos|max_tokens|
                     deadline", "ttft_ms": ..., "n_generated": N}
                  (requires ``decode=`` — the continuous-batching
                  scheduler over the paged KV arena, serving/decode.py)
  GET  /healthz   {"ok": true, "live": true, "ready": true,
                   "ready_reasons": [], "model": "...", "served": N,
                   "queue_depth": n, "queue_capacity": n,
                   "breaker": "closed|open|half_open", "draining": bool,
                   "model_digest": "...", "model_generation": n,
                   "decode": {"active": n, "queued": n} when enabled}
  GET  /livez     200 {"live": true} while the process can still answer
                  (the batcher loop is up); the *process-restart* signal
  GET  /readyz    200 {"ready": true} only when the replica should be
                  admitted traffic; 503 + the gating reasons while it is
                  draining, fencing for set_model, warming up, or its
                  breaker is open — the *route-around* signal. /healthz
                  historically conflated the two; it now carries both
  GET  /metrics   Prometheus text exposition of this server's registry
  GET  /debug/flightrecorder
                  the process flight recorder's current event ring as
                  JSON (util/flightrecorder.py — the black box)
  GET  /debug/timeline
                  per-request decode timelines + all traces from this
                  server's tracer (util/timeline.py), nested by
                  parentage; ?trace_id= filters to one trace. Incoming
                  ``traceparent`` headers parent the request spans
                  (Dapper-style propagation) and every response carries
                  a ``traceparent`` back
  GET  /debug/health
                  training-health telemetry (util/health.py): latest
                  rule report, stats snapshot, and NaN layer-of-origin
                  attribution
  POST /profile?seconds=N
                  capture a jax.profiler device trace (XPlane) for N
                  seconds (default 1, max 300) into a fresh run
                  directory; returns {"dir": ...}. One capture at a
                  time — 409 while one is in progress.
  POST /model     swap the served model from a checkpoint zip path
                  {"path": "/path/to/model.zip"} — refused (409) while
                  generative sequences are in flight; fenced to a decode
                  step boundary otherwise

Design: requests land in a queue; a batcher thread coalesces up to
``max_batch`` examples (waiting at most ``batch_timeout_ms`` after the
first) into ONE ``model.output`` call — the serving analog of
AsyncDataSetIterator's prefetch coalescing, and the right shape for a
compiled accelerator backend (per-request dispatch would be latency-bound).
Fixed batch buckets avoid per-size recompilation under jit.

Resilience (rides :mod:`deeplearning4j_tpu.util.resilience`):

- **Load shedding**: the request queue is bounded (``max_queue``
  examples); an overloaded server answers 503 + ``Retry-After``
  immediately instead of stacking unbounded latency.
- **Per-request deadlines**: every request carries a deadline
  (``request_timeout_s``); the batcher never spends a model call on a
  request whose client has already given up (expired entries answer 504).
- **Circuit breaker**: consecutive model failures trip the breaker — new
  predicts answer 503 + ``Retry-After`` for the cool-down instead of
  feeding a broken model; one probe batch then decides recovery.
- **Graceful drain**: ``stop(drain=True)`` stops admitting work, answers
  everything already queued, then shuts down — no request is dropped
  mid-flight on a planned restart.

Observability (rides :mod:`deeplearning4j_tpu.util.metrics` /
:mod:`~deeplearning4j_tpu.util.tracing`):

- ``GET /metrics``: request latency histogram split by phase
  (queue_wait / batch_assembly / model_call), responses by code, shed
  by reason, deadline expiries, batch-size histogram, live gauges for
  queue depth / pending requests / breaker state, and breaker state
  transitions (via the breaker's ``on_transition`` hook).
- With a :class:`~deeplearning4j_tpu.util.tracing.Tracer` attached,
  every predict produces parented spans: ``predict`` → ``queue`` (time
  in the bounded queue) and ``batch`` → ``model`` (the coalesced call),
  and the ``serving.infer`` fault seam records which span a scripted
  fault landed in.

Fault seam: ``"serving.infer"`` around the batched model call.
"""

from __future__ import annotations

import contextlib
import json
import queue
import threading
import time
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

import numpy as np

from ..util import faults as _faults
from ..util import metrics as _metrics
from ..util import tracing as _tracing
from ..util.resilience import (SYSTEM_CLOCK, STATE_VALUES, CircuitBreaker,
                               Clock, Deadline)


class ModelSwapRefused(RuntimeError):
    """set_model refused because generative sequences are in flight —
    retriable after drain (HTTP 409 on the /model endpoint)."""


def drain_counter(registry=None) -> _metrics.Counter:
    """``serving_drain_total{result}`` — graceful drains by outcome.

    ``result="ok"`` when everything admitted was answered within the
    timeout; ``result="timeout"`` for the half-drained state, which also
    emits a ``serving_drain_timeout`` flight-recorder event naming the
    requests still in flight."""
    reg = registry if registry is not None else _metrics.REGISTRY
    return reg.counter(
        "serving_drain_total",
        "Graceful drains by result (ok = fully drained within the "
        "timeout; timeout = half-drained, detailed by the "
        "serving_drain_timeout flight event)", ("result",))


class _Pending:
    __slots__ = ("x", "event", "result", "error", "code", "deadline",
                 "enqueued_at", "span", "queue_span")

    def __init__(self, x: np.ndarray, deadline: Deadline):
        self.x = x
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[str] = None
        self.code: int = 500
        self.deadline = deadline
        self.enqueued_at = time.perf_counter()
        self.span = None          # request-root tracing span
        self.queue_span = None    # child span covering queue wait


class InferenceServer:
    """Serve ``model.output`` over HTTP (parity: DL4jServeRouteBuilder)."""

    def __init__(self, model, port: int = 0, *, max_batch: int = 64,
                 batch_timeout_ms: float = 5.0,
                 pad_to_buckets: bool = True,
                 max_queue: int = 256,
                 request_timeout_s: float = 30.0,
                 breaker: Optional[CircuitBreaker] = None,
                 clock: Clock = SYSTEM_CLOCK,
                 registry: Optional[_metrics.MetricsRegistry] = None,
                 tracer=None, decode=None,
                 warmup_background: bool = False):
        self._model = model
        self.max_batch = int(max_batch)
        self.batch_timeout_s = float(batch_timeout_ms) / 1000.0
        self.pad_to_buckets = pad_to_buckets
        self.request_timeout_s = float(request_timeout_s)
        self.clock = clock
        self.tracer = tracer
        # per-server registry by default so two servers in one process
        # (tests, blue/green) don't blur each other's numbers; pass
        # metrics.REGISTRY to aggregate into the process default
        self.registry = registry if registry is not None \
            else _metrics.MetricsRegistry()
        self._init_metrics()
        self.breaker = breaker or CircuitBreaker(
            failure_threshold=3, reset_timeout_s=5.0, clock=clock,
            name="serving-model")
        self._chain_breaker_hook()
        # readiness state (distinct from liveness): warming / swapping /
        # draining each gate admission without implying the process is
        # unhealthy — see /readyz vs /livez
        self._warming = False
        self._swapping = False
        self._model_generation = 0
        self._model_digest: Optional[str] = None
        # continuous-batched generative decode (serving/decode.py):
        # pass a prebuilt DecodeScheduler, or a dict of engine/scheduler
        # kwargs to build one over THIS model and THIS registry
        self.decode = None
        if decode is not None:
            from .decode import DecodeScheduler, PagedDecodeEngine
            if isinstance(decode, DecodeScheduler):
                self.decode = decode
            else:
                cfg = dict(decode)
                sched_kw = {k: cfg.pop(k) for k in
                            ("max_queue", "default_max_new_tokens",
                             "request_timeout_s", "start_thread")
                            if k in cfg}
                # cross-request prefix caching is on by default for
                # served engines (production traffic repeats system
                # prompts); pass prefix_cache=False to opt out
                cfg.setdefault("prefix_cache", True)
                # the root of a start-up's spans (the engine's phases and
                # every compilation join the trace open on their thread)
                with _tracing.region("startup", tracer=tracer) as startup:
                    engine = PagedDecodeEngine(
                        model, registry=self.registry, **cfg)
                    # compile the whole bucket ladder before the loop
                    # starts: server START pays it, not the first live
                    # requests' SLO deadlines
                    if not warmup_background:
                        engine.warmup()
                self.decode = DecodeScheduler(
                    engine, clock=clock, registry=self.registry,
                    tracer=tracer, **sched_kw)
                if warmup_background:
                    # fleet replicas warm AFTER the HTTP server is up so
                    # they can register and report ready=false while the
                    # bucket ladder compiles; the dispatch lock is held
                    # so scheduler ticks (and the set_model fence) queue
                    # behind the warmup instead of racing its dispatches
                    self._warming = True

                    def _warm(sched=self.decode, eng=engine):
                        try:
                            with sched._dispatch_lock, _tracing.region(
                                    "startup.background",
                                    **_tracing.joining(startup.span)):
                                eng.warmup()
                        finally:
                            self._warming = False

                    threading.Thread(target=_warm, daemon=True,
                                     name="serving-warmup").start()
        self._queue: "queue.Queue[_Pending]" = queue.Queue(
            maxsize=int(max_queue))
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._draining = False
        # admitted-but-unanswered requests; drain() waits on this, not on
        # queue emptiness (an item leaves the queue before it is answered)
        self._pending = 0
        self._pending_lock = threading.Lock()
        # weakly bound, like the scheduler's and the allocator's gauges: a
        # stopped server (and through it the net, its parameters and the
        # decode arena) must stay collectable while its registry lives on
        # in a caller's hands (a 9 GB model served and then compared with
        # a reference on the same chip has no room for both); a dead ref
        # raises, dropping the series at exposition
        ref = weakref.ref(self)

        def _sample(get):
            def fn():
                server = ref()
                if server is None:
                    raise LookupError("server retired")
                return float(get(server))
            return fn

        self._m_queue_depth.set_function(
            _sample(lambda s: s._queue.qsize()))
        self._m_pending.set_function(_sample(lambda s: s._pending))
        self._m_breaker_state.set_function(
            _sample(lambda s: STATE_VALUES.get(s.breaker.state, -1.0)))
        self._batcher = threading.Thread(target=self._batch_loop, daemon=True)
        self._batcher.start()

        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _json(self, obj, code=200, headers=None):
                body = json.dumps(obj).encode()
                outer._m_responses.inc(code=str(code))
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                headers = dict(headers or {})
                # header in → header out: a caller's trace context is
                # echoed (or replaced by the request's own span) so the
                # client can find its spans in /debug/timeline
                tp = headers.pop("traceparent",
                                 self.headers.get("traceparent"))
                if tp:
                    self.send_header("traceparent", tp)
                for k, v in headers.items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                url = urlparse(self.path)
                path = url.path
                if path == "/healthz":
                    self._json(outer._health())
                elif path == "/livez":
                    live = outer.live
                    self._json({"live": live}, 200 if live else 503)
                elif path == "/readyz":
                    reasons = outer.readiness_reasons()
                    self._json({"ready": not reasons,
                                "reasons": reasons},
                               200 if not reasons else 503)
                elif path == "/metrics":
                    _metrics.write_exposition(self, outer.registry)
                    outer._m_responses.inc(code="200")
                elif path == "/debug/flightrecorder":
                    from ..util import flightrecorder as _flight
                    self._json({"events": _flight.jsonable_events()})
                elif path == "/debug/timeline":
                    from ..util import timeline as _timeline
                    q = parse_qs(url.query)
                    # a prebuilt DecodeScheduler may carry its own
                    # tracer — that is where the request spans live
                    tracer = outer.tracer
                    if tracer is None and outer.decode is not None:
                        tracer = outer.decode.tracer
                    if tracer is None:
                        tracer = _tracing.TRACER
                    tid = q.get("trace_id", [None])[0]
                    payload = {
                        "requests": _timeline.request_timelines(
                            tracer, trace_id=tid),
                        "traces": _timeline.trace_summaries(
                            tracer, trace_id=tid)}
                    # repr-stringify odd attribute values, like the
                    # flight-recorder endpoint — debug inspection must
                    # not 500 on one unserializable attribute
                    self._json(json.loads(
                        json.dumps(payload, default=repr)))
                elif path == "/debug/health":
                    # training-health telemetry: latest rule report +
                    # stats snapshot + NaN attribution (util.health)
                    from ..util import health as _health
                    self._json(json.loads(
                        json.dumps(_health.debug_payload(), default=repr)))
                else:
                    self._json({"error": "not found"}, 404)

            def do_POST(self):
                url = urlparse(self.path)
                if url.path == "/profile":
                    # no JSON body — parameters ride the query string so
                    # `curl -X POST .../profile?seconds=5` just works
                    from ..util.profiling import profile_request
                    body, code = profile_request(parse_qs(url.query))
                    self._json(body, code)
                    return
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    payload = json.loads(self.rfile.read(length).decode())
                except Exception as e:
                    self._json({"error": f"bad request: {e}"}, 400)
                    return
                trace_ctx = self.headers.get("traceparent")
                if url.path == "/predict":
                    try:
                        x = np.asarray(payload["inputs"], dtype=np.float32)
                    except Exception as e:
                        self._json({"error": f"bad inputs: {e}"}, 400)
                        return
                    out, err, code, retry_after, tp = outer._predict(
                        x, trace_ctx=trace_ctx)
                    headers = {}
                    if retry_after is not None:
                        headers["Retry-After"] = f"{retry_after:.0f}"
                    if tp is not None:
                        headers["traceparent"] = tp
                    if err is not None:
                        self._json({"error": err}, code, headers)
                    else:
                        self._json({"outputs": out.tolist()}, 200,
                                   headers)
                elif url.path == "/generate":
                    body, code, retry_after, tp = outer._generate(
                        payload, trace_ctx=trace_ctx)
                    headers = {}
                    if retry_after is not None:
                        headers["Retry-After"] = f"{retry_after:.0f}"
                    if tp is not None:
                        headers["traceparent"] = tp
                    self._json(body, code, headers)
                elif url.path == "/model":
                    try:
                        outer.swap_model_from(payload["path"])
                        self._json({"ok": True})
                    except ModelSwapRefused as e:
                        # retriable conflict, not a bad request: drain
                        # the in-flight decodes and POST again
                        self._json({"error": str(e)}, 409,
                                   {"Retry-After": "1"})
                    except Exception as e:
                        self._json({"error": str(e)}, 400)
                else:
                    self._json({"error": "not found"}, 404)

        self._httpd = ThreadingHTTPServer(("127.0.0.1", int(port)), Handler)
        self.port = self._httpd.server_address[1]
        self._serve_thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._serve_thread.start()

    # ------------------------------------------------------------------
    # metrics
    # ------------------------------------------------------------------

    def _init_metrics(self) -> None:
        reg = self.registry
        self._m_responses = reg.counter(
            "serving_responses_total", "HTTP responses by status code",
            ("code",))
        self._m_shed = reg.counter(
            "serving_shed_total",
            "Predict requests shed with 503 before reaching the model",
            ("reason",))
        self._m_deadline_expired = reg.counter(
            "serving_deadline_expired_total",
            "Queued requests answered 504 after their deadline passed")
        self._m_drain = drain_counter(reg)
        self._m_served = reg.counter(
            "serving_examples_served_total",
            "Examples answered 200 through the batched model call")
        # a fixed powers-of-two ladder (the jit bucket shape), NOT derived
        # from max_batch: servers with different max_batch can then share
        # one registry without a bucket-mismatch error
        self._m_batch_size = reg.histogram(
            "serving_batch_size", "Examples coalesced per model call",
            buckets=[float(1 << i) for i in range(11)])   # 1..1024
        self._m_latency = reg.histogram(
            "serving_request_latency_seconds",
            "Per-phase request latency: time in the bounded queue "
            "(queue_wait), coalescing window (batch_assembly), and the "
            "batched model call (model_call)", ("phase",))

        # HBM pressure next to the serving numbers it explains
        from ..util.profiling import register_device_memory_gauges
        register_device_memory_gauges(reg)
        self._m_queue_depth = reg.gauge(
            "serving_queue_depth", "Requests waiting in the bounded queue")
        self._m_pending = reg.gauge(
            "serving_pending_requests", "Admitted but unanswered requests")
        self._m_breaker_state = reg.gauge(
            "serving_breaker_state",
            "Model circuit breaker state (0=closed, 1=half_open, 2=open)")

    def _chain_breaker_hook(self) -> None:
        """Record breaker transitions into this server's registry, on top
        of any hook the injected breaker already carries."""
        from ..util.resilience import metrics_transition_hook
        record = metrics_transition_hook(self.registry)
        prior = self.breaker.on_transition

        def hook(name: str, old: str, new: str) -> None:
            record(name, old, new)
            if prior is not None:
                prior(name, old, new)

        self.breaker.on_transition = hook

    # back-compat: the pre-metrics bare-int attributes, now read-only
    # views over the registry (the racy ``+= 1`` writers are gone)

    @property
    def served(self) -> int:
        """Examples answered 200 (back-compat for /healthz and tests)."""
        return int(self._m_served.value())

    @property
    def shed(self) -> int:
        """Requests shed for load (queue full / draining) — the pre-metrics
        semantics. Breaker rejections are NOT load shedding; they appear
        only as serving_shed_total{reason="breaker_open"} and
        ``breaker.rejected``."""
        return int(self._m_shed.value(reason="queue_full")
                   + self._m_shed.value(reason="draining"))

    # ------------------------------------------------------------------
    # liveness vs readiness (the /healthz split)
    # ------------------------------------------------------------------

    @property
    def live(self) -> bool:
        """Process-level liveness: the serving loops are up. False means
        restart the replica; it says nothing about routability."""
        return not self._stop.is_set() and self._batcher.is_alive()

    def readiness_reasons(self) -> List[str]:
        """Why this replica should NOT be admitted traffic right now
        (empty = ready). Draining, fencing for ``set_model``, warming the
        decode ladder, and an open breaker all gate admission WITHOUT
        implying the process is unhealthy — a router (or LB) routes
        around a not-ready replica instead of shedding at it."""
        reasons = []
        if self._warming:
            reasons.append("warming")
        if self._draining:
            reasons.append("draining")
        if self._swapping:
            reasons.append("model_swap")
        if self._stop.is_set():
            reasons.append("stopped")
        if self.breaker.state == "open":
            reasons.append("breaker_open")
        return reasons

    @property
    def ready(self) -> bool:
        return not self.readiness_reasons()

    @property
    def model_digest(self) -> str:
        """Content digest of the served params (cached; invalidated on
        ``set_model``). Generation-stamped into the fleet registration so
        a rolling deploy can gate on "replica serves the NEW model"."""
        if self._model_digest is None:
            params = getattr(self._model, "params", None)
            if params is None:
                self._model_digest = type(self._model).__name__
            else:
                from ..util.durable import params_digest
                self._model_digest = params_digest(params)[:16]
        return self._model_digest

    @property
    def model_generation(self) -> int:
        """Monotonic count of completed model swaps on this replica."""
        return self._model_generation

    def _health(self) -> dict:
        reasons = self.readiness_reasons()
        h = {"ok": not self._draining
                   and self.breaker.state != "open",
             "live": self.live,
             "ready": not reasons,
             "ready_reasons": reasons,
             "model": type(self._model).__name__,
             "model_digest": self.model_digest,
             "model_generation": self._model_generation,
             "served": self.served,
             "shed": self.shed,
             "queue_depth": self._queue.qsize(),
             "queue_capacity": self._queue.maxsize,
             "breaker": self.breaker.state,
             "draining": self._draining}
        if self.decode is not None:
            h["decode"] = {"active": self.decode.active_count(),
                           "queued": self.decode.queue_depth()}
            eng = self.decode.engine
            index = eng.arena.prefix_index
            if index is not None:
                hits = self.registry.get("kv_prefix_hits_total")
                hit_pages = self.registry.get("kv_prefix_hit_pages_total")
                alloc = eng.arena.allocator
                h["decode"]["prefix_cache"] = {
                    "hits_full": (hits.value(result="full")
                                  if hits else 0.0),
                    "hits_partial": (hits.value(result="partial")
                                     if hits else 0.0),
                    "misses": (hits.value(result="miss")
                               if hits else 0.0),
                    "hit_pages": (hit_pages.value()
                                  if hit_pages else 0.0),
                    "cached_pages": index.cached_pages,
                    "shared_pages": alloc.shared_pages,
                    "kv_dtype": eng.arena.kv_dtype or "fp",
                }
        return h

    def _generate(self, payload: dict, trace_ctx: Optional[str] = None
                  ) -> Tuple[dict, int, Optional[float], Optional[str]]:
        """POST /generate → (body, http_code, retry_after_s,
        traceparent_out). Blocks the handler thread until the scheduler
        finishes the request (the continuous-batching loop runs it
        concurrently with every other in-flight sequence). The caller's
        ``traceparent`` parents the request's decode spans; the response
        header carries the request root span's context back."""
        from .decode import SchedulerDraining, SchedulerSaturated
        if self.decode is None:
            return ({"error": "generative decode not enabled on this "
                              "server (pass decode=)"}, 400, None, None)
        try:
            prompt = payload["prompt_ids"]
        except KeyError:
            return {"error": "missing prompt_ids"}, 400, None, None
        try:
            # coerce up front: a numeric STRING would pass Deadline's
            # float() inside submit and then blow up in the wait
            # arithmetic below with no HTTP response at all
            timeout_s = (None if payload.get("timeout_s") is None
                         else float(payload["timeout_s"]))
        except (TypeError, ValueError) as e:
            return {"error": f"bad timeout_s: {e}"}, 400, None, None
        try:
            req = self.decode.submit(
                prompt, payload.get("max_new_tokens"),
                temperature=float(payload.get("temperature", 0.0)),
                eos_id=payload.get("eos_id"),
                timeout_s=timeout_s,
                seed=payload.get("seed"),
                top_k=int(payload.get("top_k", 0)),
                top_p=float(payload.get("top_p", 1.0)),
                trace_ctx=trace_ctx)
        except SchedulerDraining:
            return {"error": "server is draining"}, 503, 1.0, None
        except SchedulerSaturated as e:
            return ({"error": "server overloaded (decode queue full)"},
                    503, e.retry_after, None)
        except (ValueError, TypeError) as e:
            return {"error": f"bad request: {e}"}, 400, None, None
        tp = (_tracing.inject(req.span) if req.span is not None else None)
        budget = (timeout_s if timeout_s is not None
                  else self.decode.request_timeout_s)
        req.wait(timeout=budget + 5.0)
        if req.finish_reason is None:      # scheduler wedged — honest 504
            return {"error": "generation timeout"}, 504, None, tp
        if req.finish_reason == "error":
            # the request died with the ENGINE (pools rebuilt), not on
            # its own terms: return the preserved partial output and a
            # retryable verdict — the contract a fleet router's
            # idempotent replay depends on
            return ({"error": req.error or "decode failed",
                     "retryable": True,
                     "tokens": [int(t) for t in req.tokens],
                     "n_generated": len(req.tokens)}, 500, None, tp)
        if req.finish_reason == "shutdown":
            return ({"error": "server shutting down",
                     "retryable": True}, 503, None, tp)
        if req.finish_reason == "deadline" and not req.tokens:
            return {"error": "request deadline exceeded"}, 504, None, tp
        body = {"tokens": [int(t) for t in req.tokens],
                "finish_reason": req.finish_reason,
                "n_generated": len(req.tokens)}
        if req.t_first_token is not None:
            body["ttft_ms"] = round(
                1000.0 * (req.t_first_token - req.t_submit), 3)
        if req.span is not None:
            body["trace_id"] = req.span.trace_id
        return body, 200, None, tp

    def _predict(self, x: np.ndarray, trace_ctx: Optional[str] = None
                 ) -> Tuple[Optional[np.ndarray], Optional[str],
                            int, Optional[float], Optional[str]]:
        """Returns (outputs, error, http_code, retry_after_s,
        traceparent_out). ``trace_ctx`` (an incoming traceparent header)
        parents the predict span on the caller's trace."""
        if self._draining or self._stop.is_set():
            self._m_shed.inc(reason="draining")
            return None, "server is draining", 503, 1.0, None
        if not self.breaker.allow():
            self._m_shed.inc(reason="breaker_open")
            retry = max(1.0, self.breaker.retry_after())
            return (None, "model circuit open (failing upstream)", 503,
                    retry, None)
        p = _Pending(x, Deadline(self.request_timeout_s, self.clock))
        tp = None
        if self.tracer is not None:
            p.span = self.tracer.start(
                "predict", parent=_tracing.extract(trace_ctx),
                attributes={"examples": int(x.shape[0])})
            p.queue_span = self.tracer.start("queue", parent=p.span)
            tp = _tracing.inject(p.span)
        with self._pending_lock:
            self._pending += 1
        try:
            self._queue.put_nowait(p)
        except queue.Full:
            # bounded-queue load shedding: an honest 503 now beats an
            # unbounded queue that times every client out later
            with self._pending_lock:
                self._pending -= 1
            self._m_shed.inc(reason="queue_full")
            self._end_spans(p, "shed")
            return (None, "server overloaded (queue full)", 503,
                    max(1.0, self.batch_timeout_s), tp)
        p.event.wait(timeout=self.request_timeout_s + 1.0)
        if p.error is not None:
            return None, p.error, p.code, None, tp
        if p.result is None:
            return None, "inference timeout", 504, None, tp
        return p.result, None, 200, None, tp

    @staticmethod
    def _end_spans(p: _Pending, status: Optional[str] = None) -> None:
        if p.queue_span is not None:
            p.queue_span.end(status)
        if p.span is not None:
            p.span.end(status)

    def _finish(self, p: _Pending) -> None:
        """Answer a pending request (exactly once per admitted request)."""
        if p.span is not None:
            # an answer arriving after the deadline was 504'd to the
            # client — the trace must not claim a clean 200
            late = p.error is None and p.deadline.expired
            p.span.set_attribute("code", p.code if p.error is not None
                                 else 200)
            if late:
                p.span.set_attribute("late", True)
            self._end_spans(p, "error" if p.error is not None
                            else ("late" if late else None))
        p.event.set()
        with self._pending_lock:
            self._pending -= 1

    def _dequeued(self, p: _Pending) -> None:
        """Bookkeeping when the batcher pops a request off the queue."""
        self._m_latency.observe(time.perf_counter() - p.enqueued_at,
                                phase="queue_wait")
        if p.queue_span is not None:
            p.queue_span.end()

    def _batch_loop(self) -> None:
        while not self._stop.is_set():
            try:
                first = self._queue.get(timeout=0.2)
            except queue.Empty:
                continue
            self._dequeued(first)
            assembly_t0 = time.perf_counter()
            batch = [first]
            n = first.x.shape[0]
            deadline = assembly_t0 + self.batch_timeout_s
            while n < self.max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    p = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                self._dequeued(p)
                batch.append(p)
                n += p.x.shape[0]
            self._m_latency.observe(time.perf_counter() - assembly_t0,
                                    phase="batch_assembly")
            # expired requests: their client already gave up — answer
            # 504 and spend the model call on the live ones only
            live = []
            for p in batch:
                if p.deadline.expired:
                    p.error = "request deadline exceeded"
                    p.code = 504
                    self._m_deadline_expired.inc()
                    self._finish(p)
                else:
                    live.append(p)
            if live:
                self._run_batch(live)

    def _bucket(self, n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return min(b, max(self.max_batch, n))

    def _run_batch(self, batch: List[_Pending]) -> None:
        batch_span = None
        model_t0 = None
        if self.tracer is not None:
            batch_span = self.tracer.start(
                "batch", parent=batch[0].span,
                attributes={"requests": len(batch)})
        try:
            x = np.concatenate([p.x for p in batch], axis=0)
            n = x.shape[0]
            if batch_span is not None:
                batch_span.set_attribute("examples", n)
            self._m_batch_size.observe(float(n))
            if self.pad_to_buckets:
                b = self._bucket(n)
                if b > n:  # pad to a power-of-two bucket: one jit cache
                    x = np.concatenate(
                        [x, np.zeros((b - n,) + x.shape[1:], x.dtype)])
            model_t0 = time.perf_counter()
            # span() (not start) so the serving.infer seam sees the model
            # span as this thread's active span
            model_ctx = (self.tracer.span("model", parent=batch_span)
                         if self.tracer is not None
                         else contextlib.nullcontext())
            with self._lock, model_ctx:
                _faults.check("serving.infer", {"batch": n})
                out = np.asarray(self._model.output(x))[:n]
            self._m_latency.observe(time.perf_counter() - model_t0,
                                    phase="model_call")
            ofs = 0
            for p in batch:
                k = p.x.shape[0]
                p.result = out[ofs:ofs + k]
                ofs += k
                self._finish(p)
            self._m_served.inc(n)
            self.breaker.record_success()
            if batch_span is not None:
                batch_span.end()
        except Exception as e:
            # a failing model call still has a latency — the histogram
            # must not go blind during the exact window the breaker trips
            if model_t0 is not None:
                self._m_latency.observe(time.perf_counter() - model_t0,
                                        phase="model_call")
            self.breaker.record_failure()
            if batch_span is not None:
                batch_span.end("error")
            for p in batch:
                p.error = f"{type(e).__name__}: {e}"
                p.code = 500
                self._finish(p)

    # ------------------------------------------------------------------

    def set_model(self, model) -> None:
        """Hot-swap the served model (atomic w.r.t. in-flight batches).

        With generative decode enabled the swap is FENCED to a decode
        step boundary and REFUSED while sequences are in flight: a
        mid-decode swap would mis-read every live K/V page (the cache
        holds the old model's activations). Drain first."""
        if self.decode is not None:
            # readiness gates admission for the whole fence window, so a
            # router stops sending BEFORE the swap instead of bouncing
            # off ModelSwapRefused
            self._swapping = True
            try:
                with self.decode.fence() as in_flight:
                    if in_flight:
                        raise ModelSwapRefused(
                            f"refusing model swap: {in_flight} generative "
                            "sequence(s) in flight — drain() first")
                    self.decode.engine.swap_net(model)
                    with self._lock:
                        self._model = model
            finally:
                self._swapping = False
            self._model_digest = None
            self._model_generation += 1
            return
        with self._lock:
            self._model = model
        self._model_digest = None
        self._model_generation += 1

    def swap_model_from(self, path: str) -> None:
        """Load a checkpoint zip (util.serialization) and serve it."""
        from ..util.serialization import load_model
        self.set_model(load_model(path))

    def drain(self, timeout: float = 30.0) -> bool:
        """Stop admitting new work (predicts AND generates answer 503)
        and wait until everything already accepted has been answered —
        including in-flight generative sequences, which keep decoding
        until they finish or hit their own SLO deadline. True if fully
        drained within ``timeout``.

        Outcome is never silent: every drain counts into
        ``serving_drain_total{result}``, and a timeout additionally
        records a ``serving_drain_timeout`` flight event NAMING the
        requests still in flight — half-drained is an operator page with
        attribution, not a bare False."""
        self._draining = True
        deadline = time.perf_counter() + timeout
        ok = True
        if self.decode is not None:
            ok = self.decode.drain(timeout=timeout)
        drained = False
        while time.perf_counter() < deadline:
            with self._pending_lock:
                if self._pending == 0:
                    drained = True
                    break
            time.sleep(0.005)
        if not drained:
            with self._pending_lock:
                drained = self._pending == 0
        ok = ok and drained
        self._m_drain.inc(result="ok" if ok else "timeout")
        if not ok:
            from ..util import flightrecorder as _flight
            _flight.record("serving_drain_timeout",
                           pending_predicts=self._pending,
                           in_flight=self._in_flight_decodes())
        return ok

    def _in_flight_decodes(self) -> List[dict]:
        """Identify the generative requests still active — lane, progress
        and trace id — so a drain timeout names exactly what it left
        behind (the payload of the ``serving_drain_timeout`` event)."""
        if self.decode is None:
            return []
        out = []
        for seq in list(self.decode._active.values()):
            req = seq.req
            out.append({"lane": seq.lane,
                        "prompt_len": len(req.prompt),
                        "generated": len(req.tokens),
                        "max_new_tokens": req.max_new_tokens,
                        "trace_id": (req.span.trace_id
                                     if req.span is not None else None)})
        return out

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Graceful shutdown: by default drains queued requests first so a
        planned restart drops nothing mid-flight."""
        if drain:
            self.drain(timeout)
        if self.decode is not None:
            self.decode.stop()
        self._stop.set()
        # answer anything still queued (drain=False or drain timeout)
        while True:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                break
            p.error = "server shutting down"
            p.code = 503
            self._finish(p)
        self._httpd.shutdown()
        self._batcher.join(timeout=5.0)
