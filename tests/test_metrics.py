"""Metrics + tracing substrate tests.

Fast registry/tracer unit tests run in tier-1 on every push (the metrics
smoke); the serving-integration scenarios ride the ``chaos`` marker with
FaultPlan/ManualClock — deterministic, no sleep-based waiting. The
Prometheus checks are parser round-trips: scrape → parse → assert format
invariants (TYPE/HELP lines, label escaping, histogram monotonicity),
not string-contains.
"""

import json
import math
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.util import faults
from deeplearning4j_tpu.util.metrics import (EXPOSITION_CONTENT_TYPE,
                                             REGISTRY, MetricsRegistry)
from deeplearning4j_tpu.util.tracing import Tracer

# ---------------------------------------------------------------------------
# a small Prometheus text-format parser (the round-trip half of the tests)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    return v.replace(r"\n", "\n").replace(r'\"', '"').replace(r"\\", "\\")


def _parse_value(s: str) -> float:
    if s == "+Inf":
        return math.inf
    if s == "-Inf":
        return -math.inf
    return float(s)


def parse_prometheus(text: str) -> dict:
    """-> {family: {"type": str, "help": str, "samples":
    [(sample_name, labels_dict, value)]}}; raises AssertionError on any
    malformed line."""
    families = {}
    current = None
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_text = rest.partition(" ")
            families.setdefault(name, {"samples": []})["help"] = help_text
            current = name
        elif line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), f"bad TYPE: {line!r}"
            families.setdefault(name, {"samples": []})["type"] = kind
            current = name
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"malformed sample line: {line!r}"
            sname, labelstr, value = m.group(1), m.group(2), m.group(3)
            labels = {}
            if labelstr:
                consumed = 0
                for lm in _LABEL_RE.finditer(labelstr):
                    labels[lm.group(1)] = _unescape(lm.group(2))
                    consumed = lm.end()
                rest = labelstr[consumed:].strip(", ")
                assert not rest, f"unparsed labels {rest!r} in {line!r}"
            base = re.sub(r"_(bucket|sum|count)$", "", sname)
            fam = base if base in families else sname
            assert current is not None, f"sample before any family: {line!r}"
            assert fam in families, f"sample {sname!r} without TYPE/HELP"
            families[fam]["samples"].append(
                (sname, labels, _parse_value(value)))
    return families


def assert_valid_prometheus(text: str) -> dict:
    """Full format validation; returns the parsed families."""
    families = parse_prometheus(text)
    for name, fam in families.items():
        assert "type" in fam, f"{name}: missing TYPE"
        assert "help" in fam, f"{name}: missing HELP"
        if fam["type"] != "histogram":
            continue
        # histogram invariants per labelset: buckets cumulative and
        # nondecreasing in le order, +Inf == _count, _sum present
        by_labelset = {}
        for sname, labels, value in fam["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            d = by_labelset.setdefault(
                key, {"buckets": [], "sum": None, "count": None})
            if sname.endswith("_bucket"):
                d["buckets"].append((_parse_value(labels["le"]), value))
            elif sname.endswith("_sum"):
                d["sum"] = value
            elif sname.endswith("_count"):
                d["count"] = value
        for key, d in by_labelset.items():
            assert d["sum"] is not None, f"{name}{key}: no _sum"
            assert d["count"] is not None, f"{name}{key}: no _count"
            les = [le for le, _ in d["buckets"]]
            assert les == sorted(les), f"{name}{key}: le out of order"
            assert les and les[-1] == math.inf, f"{name}{key}: no +Inf"
            counts = [c for _, c in d["buckets"]]
            assert counts == sorted(counts), \
                f"{name}{key}: buckets not cumulative: {counts}"
            assert counts[-1] == d["count"], \
                f"{name}{key}: +Inf bucket != _count"
    return families


# ---------------------------------------------------------------------------
# registry unit tests (fast — the tier-1 metrics smoke)
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("requests_total", "Requests", ("code",))
        c.inc(code="200")
        c.inc(2, code="200")
        c.inc(code="500")
        assert c.value(code="200") == 3
        assert c.value(code="500") == 1
        assert c.total() == 4
        with pytest.raises(ValueError):
            c.inc(-1, code="200")
        with pytest.raises(ValueError):
            c.inc(code="200", extra="nope")

    def test_gauge_set_inc_dec_and_function(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth", "Depth")
        g.set(5)
        g.inc()
        g.dec(2)
        assert g.value() == 4
        live = {"v": 7.0}
        g2 = reg.gauge("live_depth", "Live")
        g2.set_function(lambda: live["v"])
        assert g2.value() == 7.0
        live["v"] = 9.0
        assert g2.value() == 9.0

    def test_histogram_buckets_sum_count(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", "Latency", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count() == 5
        assert h.sum() == pytest.approx(56.05)
        fam = assert_valid_prometheus(reg.expose())["lat"]
        buckets = {labels["le"]: v for (n, labels, v) in fam["samples"]
                   if n == "lat_bucket"}
        assert buckets["0.1"] == 1
        assert buckets["1"] == 3
        assert buckets["10"] == 4
        assert buckets["+Inf"] == 5

    def test_get_or_create_idempotent_and_mismatch_raises(self):
        reg = MetricsRegistry()
        c1 = reg.counter("x_total", "X", ("a",))
        c2 = reg.counter("x_total", "X", ("a",))
        assert c1 is c2
        with pytest.raises(ValueError):
            reg.gauge("x_total", "X")            # type mismatch
        with pytest.raises(ValueError):
            reg.counter("x_total", "X", ("b",))  # label mismatch
        with pytest.raises(ValueError):
            reg.counter("bad name", "nope")
        h1 = reg.histogram("h", "H", buckets=(1.0, 2.0))
        assert reg.histogram("h", "H", buckets=(2.0, 1.0)) is h1  # same set
        with pytest.raises(ValueError):
            reg.histogram("h", "H", buckets=(1.0, 8.0))  # bucket mismatch

    def test_exposition_label_escaping_roundtrip(self):
        reg = MetricsRegistry()
        c = reg.counter("weird_total", "Weird", ("path",))
        nasty = 'a"b\\c\nnewline'
        c.inc(path=nasty)
        fam = assert_valid_prometheus(reg.expose())["weird_total"]
        (_, labels, value), = fam["samples"]
        assert labels["path"] == nasty
        assert value == 1

    def test_exposition_has_type_and_help(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "A counter")
        reg.gauge("b", "A gauge").set(1)
        text = reg.expose()
        assert "# HELP a_total A counter" in text
        assert "# TYPE a_total counter" in text
        assert "# TYPE b gauge" in text
        assert_valid_prometheus(text)

    def test_snapshot_json_serializable(self):
        reg = MetricsRegistry()
        reg.counter("c_total", "c", ("k",)).inc(k="v")
        reg.histogram("h", "h", buckets=(1.0,)).observe(0.5)
        reg.gauge("g", "g").set(3)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["c_total"]["series"][0]["value"] == 1
        assert snap["h"]["series"][0]["count"] == 1
        assert snap["g"]["series"][0]["value"] == 3

    def test_thread_safety_under_contention(self):
        reg = MetricsRegistry()
        c = reg.counter("contended_total", "n")
        h = reg.histogram("contended_h", "h", buckets=(0.5,))

        def worker():
            for _ in range(1000):
                c.inc()
                h.observe(0.25)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == 8000
        assert h.count() == 8000


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

class TestTracing:
    def test_nested_spans_parent_and_trace_id(self):
        tr = Tracer()
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                assert tr.current() is inner
            assert tr.current() is outer
        assert tr.current() is None
        by_name = {s.name: s for s in tr.finished}
        assert by_name["inner"].parent_id == by_name["outer"].span_id
        assert by_name["inner"].trace_id == by_name["outer"].trace_id
        assert by_name["outer"].parent_id is None
        assert by_name["outer"].duration_ms >= 0

    def test_explicit_cross_thread_parenting(self):
        tr = Tracer()
        root = tr.start("request")
        child_done = threading.Event()

        def worker():
            s = tr.start("work", parent=root)
            s.end()
            child_done.set()

        threading.Thread(target=worker).start()
        assert child_done.wait(5)
        root.end()
        by_name = {s.name: s for s in tr.finished}
        assert by_name["work"].parent_id == by_name["request"].span_id
        assert by_name["work"].trace_id == by_name["request"].trace_id

    def test_error_status_on_raise(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert tr.finished[0].status == "error"

    def test_jsonl_export(self, tmp_path):
        tr = Tracer()
        with tr.span("a", attributes={"k": 1}):
            with tr.span("b"):
                pass
        p = str(tmp_path / "spans.jsonl")
        assert tr.export_jsonl(p) == 2
        lines = [json.loads(l) for l in open(p) if l.strip()]
        by_name = {d["name"]: d for d in lines}
        assert by_name["b"]["parent_id"] == by_name["a"]["span_id"]
        assert by_name["a"]["attributes"] == {"k": 1}
        assert by_name["a"]["duration_ms"] is not None

    def test_span_cap_keeps_newest(self):
        tr = Tracer(max_spans=5)
        for i in range(12):
            with tr.span(f"s{i}"):
                pass
        names = [s.name for s in tr.finished]
        assert names == ["s7", "s8", "s9", "s10", "s11"]

    @pytest.mark.chaos
    def test_fault_seam_records_active_span(self):
        """A scripted fault captures WHICH span it landed in."""
        tr = Tracer()
        plan = faults.FaultPlan().fail_at("test.seam", call=2,
                                         exc=RuntimeError("injected"))
        with plan.active():
            with tr.span("warmup"):
                faults.check("test.seam")        # call 1: passes
            with tr.span("hot"):
                with pytest.raises(RuntimeError):
                    faults.check("test.seam")    # call 2: scripted fault
        assert plan.triggered == [("test.seam", 2)]
        (ctx,) = plan.trigger_context
        assert ctx["site"] == "test.seam" and ctx["call"] == 2
        assert ctx["span"]["name"] == "hot"
        hot = next(s for s in tr.finished if s.name == "hot")
        assert ctx["span"]["span_id"] == hot.span_id


class TestTraceContext:
    """Cross-scope propagation (ISSUE 13): serializable contexts, span
    provenance, the bounded ring's drop accounting, and the flight-
    recorder trace stamp."""

    def test_inject_extract_roundtrip(self):
        from deeplearning4j_tpu.util import tracing
        tr = Tracer()
        with tr.span("root") as root:
            header = tracing.inject(root)
        assert header == f"00-{root.trace_id}-{root.span_id}-01"
        ctx = tracing.extract(header)
        assert ctx.trace_id == root.trace_id
        assert ctx.span_id == root.span_id
        # an extracted context is a valid remote parent
        child = tr.start("remote_child", parent=ctx)
        child.end()
        assert child.trace_id == root.trace_id
        assert child.parent_id == root.span_id

    def test_extract_rejects_malformed(self):
        from deeplearning4j_tpu.util import tracing
        for bad in (None, "", "garbage", "00-short-short-01",
                    "00-" + "z" * 32 + "-" + "a" * 16 + "-01"):
            assert tracing.extract(bad) is None

    def test_spans_carry_host_and_pid(self):
        import os as _os
        tr = Tracer(host="logical-h3")
        with tr.span("x") as s:
            pass
        d = s.to_dict()
        assert d["host"] == "logical-h3"
        assert d["pid"] == _os.getpid()
        # default host is the machine hostname
        tr2 = Tracer()
        with tr2.span("y") as s2:
            pass
        assert s2.host == __import__("socket").gethostname()

    def test_env_context(self, monkeypatch):
        from deeplearning4j_tpu.util import tracing
        monkeypatch.delenv(tracing.TRACEPARENT_ENV, raising=False)
        assert tracing.env_context() is None
        monkeypatch.setenv(tracing.TRACEPARENT_ENV,
                           "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01")
        ctx = tracing.env_context()
        assert ctx.trace_id == "ab" * 16 and ctx.span_id == "cd" * 8

    def test_ring_overflow_counts_drops(self):
        """Satellite: the silent oldest-span drop is now counted and
        warned about once."""
        reg = MetricsRegistry()
        tr = Tracer(max_spans=4, registry=reg)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        ctr = reg.get("tracer_spans_dropped_total")
        assert ctr is not None and ctr.value() == 6
        assert [s.name for s in tr.finished] == \
            ["s6", "s7", "s8", "s9"]

    def test_max_spans_env_configurable(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_TRACE_MAX_SPANS", "7")
        tr = Tracer(registry=MetricsRegistry())
        assert tr.max_spans == 7
        monkeypatch.setenv("DL4JTPU_TRACE_MAX_SPANS", "0")
        with pytest.raises(ValueError):
            Tracer(registry=MetricsRegistry())

    def test_flight_events_stamp_active_trace(self):
        from deeplearning4j_tpu.util import flightrecorder as flight
        tr = Tracer()
        e_outside = flight.record("trace_stamp_probe", n=1)
        assert "trace_id" not in e_outside
        with tr.span("round") as s:
            e = flight.record("trace_stamp_probe", n=2)
        assert e["trace_id"] == s.trace_id
        assert e["span_id"] == s.span_id
        # explicit fields always win over ambient context
        with tr.span("round2"):
            e2 = flight.record("trace_stamp_probe", trace_id="explicit")
        assert e2["trace_id"] == "explicit"

    def test_record_explicit_duration(self):
        tr = Tracer()
        with tr.span("parent") as p:
            s = tr.record("phase", 0.25, attributes={"round": 3})
        assert s.parent_id == p.span_id
        assert abs(s.duration_ms - 250.0) < 1e-6
        assert s.attributes == {"round": 3}


# ---------------------------------------------------------------------------
# resilience counters
# ---------------------------------------------------------------------------

class TestResilienceMetrics:
    def test_retry_attempts_and_give_ups_counted(self):
        from deeplearning4j_tpu.util.resilience import (ManualClock,
                                                        RetriesExhausted,
                                                        RetryPolicy)
        reg = MetricsRegistry()
        policy = RetryPolicy(max_attempts=3, clock=ManualClock(),
                             name="test-policy", registry=reg)
        with pytest.raises(RetriesExhausted):
            policy.call(lambda: (_ for _ in ()).throw(IOError("down")))
        attempts = reg.get("retry_attempts_total")
        give_ups = reg.get("retry_give_ups_total")
        assert attempts.value(policy="test-policy") == 3
        assert give_ups.value(policy="test-policy") == 1
        # a successful call adds attempts but no give-up
        assert policy.call(lambda: 42) == 42
        assert attempts.value(policy="test-policy") == 4
        assert give_ups.value(policy="test-policy") == 1

    def test_breaker_on_transition_hook_fires_every_change(self):
        from deeplearning4j_tpu.util.resilience import (CircuitBreaker,
                                                        ManualClock)
        clock = ManualClock()
        events = []
        br = CircuitBreaker(failure_threshold=2, reset_timeout_s=10.0,
                            clock=clock, name="hooked",
                            on_transition=lambda *a: events.append(a))
        br.record_failure()
        br.record_failure()                      # trips
        assert events == [("hooked", "closed", "open")]
        clock.advance(10.0)
        assert br.state == "half_open"
        br.record_success()
        assert events == [("hooked", "closed", "open"),
                          ("hooked", "open", "half_open"),
                          ("hooked", "half_open", "closed")]

    def test_raising_hook_never_breaks_the_breaker(self):
        """A broken telemetry hook is logged, not raised — it must not
        kill the serving batcher thread's failure path."""
        from deeplearning4j_tpu.util.resilience import (CircuitBreaker,
                                                        ManualClock)

        def bad_hook(*a):
            raise RuntimeError("telemetry exploded")

        br = CircuitBreaker(failure_threshold=1, reset_timeout_s=5.0,
                            clock=ManualClock(), name="fragile",
                            on_transition=bad_hook)
        br.record_failure()              # trips; hook raises internally
        assert br.state == "open"
        br.record_success()
        assert br.state == "closed"

    def test_metrics_transition_hook_records_per_breaker(self):
        from deeplearning4j_tpu.util.resilience import (
            CircuitBreaker, ManualClock, metrics_transition_hook)
        reg = MetricsRegistry()
        br = CircuitBreaker(failure_threshold=1, reset_timeout_s=5.0,
                            clock=ManualClock(), name="db",
                            on_transition=metrics_transition_hook(reg))
        br.record_failure()
        c = reg.get("breaker_transitions_total")
        assert c.value(breaker="db", from_state="closed",
                       to_state="open") == 1


# ---------------------------------------------------------------------------
# training bridge + UI endpoint (the tier-1 metrics smoke for real paths)
# ---------------------------------------------------------------------------

def _tiny_net(seed=1):
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(seed).updater("sgd")
            .learning_rate(0.1).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(5)).build())
    return MultiLayerNetwork(conf).init()


class TestMetricsListener:
    def test_training_counters_and_histogram(self, rng):
        from deeplearning4j_tpu.optimize import MetricsListener
        reg = MetricsRegistry()
        net = _tiny_net()
        net.set_listeners(MetricsListener(registry=reg, name="tiny"))
        x = rng.normal(size=(16, 5)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
        for _ in range(4):
            net.fit_batch(x, y)
        assert reg.get("training_iterations_total").value(model="tiny") == 4
        assert np.isfinite(reg.get("training_score").value(model="tiny"))
        # 3 inter-iteration gaps for 4 iterations
        assert reg.get("training_iteration_seconds").count(model="tiny") == 3
        assert_valid_prometheus(reg.expose())

    def test_step_skipped_counted(self):
        from deeplearning4j_tpu.optimize import MetricsListener
        reg = MetricsRegistry()
        l = MetricsListener(registry=reg, name="guarded")
        l.on_step_skipped(None, 3, "non-finite gradients")
        l.on_step_skipped(None, 4, "non-finite gradients",
                          info={"layer": "layer_1"})
        assert reg.get("training_steps_skipped_total").value(
            model="guarded", layer="") == 1
        assert reg.get("training_steps_skipped_total").value(
            model="guarded", layer="layer_1") == 1


class TestTrainingStatsMirror:
    def test_phase_events_land_in_histogram(self):
        from deeplearning4j_tpu.parallel.stats import TrainingStats
        reg = MetricsRegistry()
        ts = TrainingStats(registry=reg)
        ts.record("step", 0.0, 250.0)       # ms
        ts.record("step", 250.0, 750.0)
        ts.record("average", 1000.0, 100.0)
        h = reg.get("training_phase_seconds")
        assert h.count(phase="step") == 2
        assert h.sum(phase="step") == pytest.approx(1.0)
        assert h.count(phase="average") == 1
        # the in-memory summary is unchanged by mirroring
        assert ts.summary()["step"]["count"] == 2


class TestUIServerMetrics:
    def test_metrics_endpoint_exposes_registry(self):
        from deeplearning4j_tpu.storage import InMemoryStatsStorage
        from deeplearning4j_tpu.ui import UIServer
        reg = MetricsRegistry()
        reg.counter("training_iterations_total", "iters",
                    ("model",)).inc(5, model="m")
        server = UIServer(port=0, registry=reg).attach(InMemoryStatsStorage())
        base = f"http://127.0.0.1:{server.port}"
        try:
            resp = urllib.request.urlopen(base + "/metrics", timeout=5)
            assert resp.headers["Content-Type"] == EXPOSITION_CONTENT_TYPE
            fams = assert_valid_prometheus(resp.read().decode())
            (_, labels, value), = fams["training_iterations_total"]["samples"]
            assert labels == {"model": "m"} and value == 5
        finally:
            server.stop()


class TestStatsStorageMetricsListener:
    def test_records_counted_per_type(self):
        from deeplearning4j_tpu.storage import (InMemoryStatsStorage,
                                                Persistable,
                                                StatsStorageMetricsListener)
        reg = MetricsRegistry()
        st = InMemoryStatsStorage()
        st.register_listener(StatsStorageMetricsListener(registry=reg))
        st.put_static_info(Persistable("s", "StatsListener", "w", 1.0, {}))
        st.put_update(Persistable("s", "StatsListener", "w", 2.0, {}))
        st.put_update(Persistable("s", "TsneModule", "w", 3.0, {}))
        c = reg.get("stats_records_total")
        assert c.value(event="static", type_id="StatsListener") == 1
        assert c.value(event="update", type_id="StatsListener") == 1
        assert c.value(event="update", type_id="TsneModule") == 1


# ---------------------------------------------------------------------------
# StatsListener timing regression (satellite: iteration_ms under-reporting)
# ---------------------------------------------------------------------------

class _CaptureRouter:
    def __init__(self):
        self.static, self.updates = [], []

    def put_static_info(self, rec):
        self.static.append(rec)

    def put_update(self, rec):
        self.updates.append(rec)


class _FakeTime:
    """Stands in for the ``time`` module inside ui.stats."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now

    def time(self):
        return self.now


class TestStatsListenerTiming:
    def test_iteration_ms_with_frequency(self, monkeypatch):
        """frequency=5 must NOT divide the since-last-iteration gap by 5
        (the old code under-reported iteration_ms ~frequency×)."""
        from deeplearning4j_tpu.ui import stats as ui_stats
        fake = _FakeTime()
        monkeypatch.setattr(ui_stats, "time", fake)
        router = _CaptureRouter()
        listener = ui_stats.StatsListener(router, frequency=5,
                                          session_id="t")
        model = object()
        for i in range(1, 16):                 # 100 ms per iteration
            fake.now = i * 0.1
            listener.iteration_done(model, i, 0.5)
        collected = [u.data for u in router.updates]
        assert [d["iteration"] for d in collected] == [5, 10, 15]
        assert collected[0]["iteration_ms"] is None    # no prior sample
        assert collected[1]["iteration_ms"] == pytest.approx(100.0)
        assert collected[2]["iteration_ms"] == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# StatsStorage locking + FileStatsStorage lifecycle (satellite)
# ---------------------------------------------------------------------------

class TestStatsStorageConcurrency:
    def test_concurrent_readers_and_writers(self):
        from deeplearning4j_tpu.storage import (InMemoryStatsStorage,
                                                Persistable,
                                                StatsStorageListener)
        st = InMemoryStatsStorage()
        errors = []
        stop = threading.Event()

        def writer(wid):
            for i in range(300):
                st.put_update(Persistable("s", "T", f"w{wid}",
                                          float(i), {"i": i}))
                st.put_static_info(Persistable("s", "T", f"w{wid}",
                                               float(i), {"i": i}))

        def reader():
            while not stop.is_set():
                try:
                    st.get_latest_update("s", "T", "w0")
                    st.get_static_info("s", "T", "w1")
                    st.list_workers("s", "T")
                    st.register_listener(StatsStorageListener())
                except Exception as e:   # pragma: no cover - failure path
                    errors.append(e)
                    return

        writers = [threading.Thread(target=writer, args=(i,))
                   for i in range(3)]
        readers = [threading.Thread(target=reader) for _ in range(3)]
        for t in writers + readers:
            t.start()
        for t in writers:
            t.join(timeout=30)
        stop.set()
        for t in readers:
            t.join(timeout=30)
        assert not errors
        assert st.get_latest_update("s", "T", "w0").data == {"i": 299}

    def test_file_storage_context_manager(self, tmp_path):
        from deeplearning4j_tpu.storage import FileStatsStorage, Persistable
        p = str(tmp_path / "stats.jsonl")
        with FileStatsStorage(p) as st:
            st.put_update(Persistable("s", "T", "w", 1.0, {"x": 1}))
        assert st._f.closed
        with pytest.raises(ValueError):
            st.put_update(Persistable("s", "T", "w", 2.0, {"x": 2}))
        with FileStatsStorage(p) as st2:
            assert st2.get_latest_update("s", "T", "w").data == {"x": 1}


# ---------------------------------------------------------------------------
# serving integration: scrape a LIVE server under scripted faults
# ---------------------------------------------------------------------------

def _post(base, path, payload):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _scrape(base):
    resp = urllib.request.urlopen(base + "/metrics", timeout=5)
    assert resp.headers["Content-Type"] == EXPOSITION_CONTENT_TYPE
    return assert_valid_prometheus(resp.read().decode())


def _sample(fams, family, name=None, **labels):
    """The value of one sample, or 0.0 when absent."""
    for sname, slabels, value in fams.get(family, {}).get("samples", ()):
        if name is not None and sname != name:
            continue
        if all(slabels.get(k) == v for k, v in labels.items()):
            return value
    return 0.0


@pytest.mark.chaos
class TestServingMetrics:
    def test_scrape_roundtrip_and_counters_move_under_faults(self, rng):
        """Parser round-trip on a live /metrics; scripted FaultPlan moves
        the 500/shed counters; histograms stay monotonic throughout."""
        from deeplearning4j_tpu.serving import InferenceServer
        net = _tiny_net()
        server = InferenceServer(net, port=0, max_batch=4)
        base = f"http://127.0.0.1:{server.port}"
        x = rng.normal(size=(2, 5)).astype(np.float32)
        try:
            code, _ = _post(base, "/predict", {"inputs": x.tolist()})
            assert code == 200
            fams = _scrape(base)
            assert _sample(fams, "serving_responses_total",
                           code="200") >= 1
            assert _sample(fams, "serving_request_latency_seconds",
                           "serving_request_latency_seconds_count",
                           phase="queue_wait") == 1
            assert _sample(fams, "serving_request_latency_seconds",
                           "serving_request_latency_seconds_count",
                           phase="model_call") == 1
            assert _sample(fams, "serving_batch_size",
                           "serving_batch_size_count") == 1
            assert _sample(fams, "serving_examples_served_total") == 2
            assert _sample(fams, "serving_queue_depth") == 0
            assert _sample(fams, "serving_breaker_state") == 0  # closed

            # scripted fault: exactly one infer call fails → one 500
            plan = faults.FaultPlan().fail_at(
                "serving.infer", call=1, exc=RuntimeError("chip fell over"))
            with plan.active():
                code, body = _post(base, "/predict", {"inputs": x.tolist()})
                assert code == 500
            fams = _scrape(base)
            assert _sample(fams, "serving_responses_total", code="500") == 1

            # draining → shed with reason=draining
            assert server.drain(timeout=10)
            code, _ = _post(base, "/predict", {"inputs": x.tolist()})
            assert code == 503
            fams = _scrape(base)
            assert _sample(fams, "serving_shed_total",
                           reason="draining") >= 1
            assert server.shed >= 1
        finally:
            server.stop(drain=False)

    def test_deadline_expiry_counts_504(self):
        """A queued request whose deadline passes on the fake clock moves
        serving_deadline_expired_total (and answers 504)."""
        from deeplearning4j_tpu.serving import InferenceServer
        from deeplearning4j_tpu.util.resilience import ManualClock

        class _BlockingModel:
            def __init__(self):
                self.entered = threading.Event()
                self.release = threading.Event()

            def output(self, x):
                self.entered.set()
                assert self.release.wait(timeout=30)
                return np.zeros((x.shape[0], 3), np.float32)

        clock = ManualClock()
        model = _BlockingModel()
        server = InferenceServer(model, port=0, max_batch=1,
                                 batch_timeout_ms=1.0,
                                 request_timeout_s=5.0, clock=clock)
        base = f"http://127.0.0.1:{server.port}"
        results = {}

        def call(name):
            results[name] = _post(base, "/predict",
                                  {"inputs": [[0.0, 0.0, 0.0]]})

        try:
            ta = threading.Thread(target=call, args=("a",))
            ta.start()
            assert model.entered.wait(timeout=10)
            tb = threading.Thread(target=call, args=("b",))
            tb.start()
            for _ in range(200):
                if server._queue.qsize() >= 1:
                    break
                threading.Event().wait(0.01)
            clock.advance(10.0)               # b expires while queued
            model.release.set()
            ta.join(timeout=30)
            tb.join(timeout=30)
            assert results["b"][0] == 504
            fams = _scrape(base)
            assert _sample(fams, "serving_deadline_expired_total") == 1
            assert _sample(fams, "serving_responses_total", code="504") == 1
        finally:
            model.release.set()
            server.stop(drain=False)

    def test_breaker_transitions_counted_open_and_close(self, rng):
        """The acceptance scenario: breaker open/close transitions land in
        breaker_transitions_total, and the state gauge tracks them."""
        from deeplearning4j_tpu.serving import InferenceServer
        from deeplearning4j_tpu.util.resilience import (CircuitBreaker,
                                                        ManualClock)

        class _FailingModel:
            def output(self, x):
                raise RuntimeError("model exploded")

        clock = ManualClock()
        breaker = CircuitBreaker(failure_threshold=2, reset_timeout_s=60.0,
                                 clock=clock, name="serving-model")
        server = InferenceServer(_FailingModel(), port=0, max_batch=1,
                                 breaker=breaker, clock=clock)
        base = f"http://127.0.0.1:{server.port}"
        x = [[0.0] * 5]
        try:
            for _ in range(2):
                code, _ = _post(base, "/predict", {"inputs": x})
                assert code == 500
            fams = _scrape(base)
            assert _sample(fams, "breaker_transitions_total",
                           breaker="serving-model", from_state="closed",
                           to_state="open") == 1
            assert _sample(fams, "serving_breaker_state") == 2  # open
            # while open: shed with reason=breaker_open
            code, _ = _post(base, "/predict", {"inputs": x})
            assert code == 503
            fams = _scrape(base)
            assert _sample(fams, "serving_shed_total",
                           reason="breaker_open") == 1
            # recovery: cool-down elapses, probe succeeds, circuit closes
            server.set_model(_tiny_net())
            clock.advance(60.0)
            good = rng.normal(size=(1, 5)).astype(np.float32)
            code, _ = _post(base, "/predict", {"inputs": good.tolist()})
            assert code == 200
            fams = _scrape(base)
            assert _sample(fams, "breaker_transitions_total",
                           breaker="serving-model", from_state="open",
                           to_state="half_open") == 1
            assert _sample(fams, "breaker_transitions_total",
                           breaker="serving-model", from_state="half_open",
                           to_state="closed") == 1
            assert _sample(fams, "serving_breaker_state") == 0  # closed
        finally:
            server.stop(drain=False)

    def test_retry_give_ups_counted_for_remote_stats(self):
        """The remote stats router's exhausted retry loops land in
        retry_give_ups_total (acceptance: give-ups are counted)."""
        from deeplearning4j_tpu.storage import RemoteUIStatsStorageRouter
        from deeplearning4j_tpu.storage.stats_storage import Persistable
        from deeplearning4j_tpu.util.resilience import (ManualClock,
                                                        RetryPolicy)
        reg = MetricsRegistry()
        clock = ManualClock()

        def dead_transport(url, body, timeout):
            raise ConnectionError("ui unreachable")

        router = RemoteUIStatsStorageRouter(
            "http://localhost:1", clock=clock, transport=dead_transport,
            retry_policy=RetryPolicy(max_attempts=3, initial_backoff=0.1,
                                     clock=clock, name="remote-ui",
                                     registry=reg))
        try:
            router.put_update(Persistable("s", "T", "w", 1.0, {}))
            router.flush(timeout=10.0)
            assert reg.get("retry_give_ups_total").value(
                policy="remote-ui") == 1
            assert reg.get("retry_attempts_total").value(
                policy="remote-ui") == 3
        finally:
            router.close(timeout=5.0)

    def test_tracer_parents_predict_queue_batch_model(self, rng):
        """Acceptance: Tracer JSONL export shows parented spans for a
        predict request (queue → batch → model), the incoming
        ``traceparent`` header parents the whole tree on the caller's
        trace, and the response carries the predict span's context."""
        from deeplearning4j_tpu.serving import InferenceServer
        net = _tiny_net()
        tracer = Tracer()
        server = InferenceServer(net, port=0, max_batch=4, tracer=tracer)
        base = f"http://127.0.0.1:{server.port}"
        x = rng.normal(size=(2, 5)).astype(np.float32)
        client_trace, client_span = "ab" * 16, "cd" * 8
        header_in = f"00-{client_trace}-{client_span}-01"
        try:
            req = urllib.request.Request(
                base + "/predict",
                data=json.dumps({"inputs": x.tolist()}).encode(),
                method="POST",
                headers={"Content-Type": "application/json",
                         "traceparent": header_in})
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.status == 200
                header_out = r.headers.get("traceparent")
        finally:
            server.stop()
        spans = {s.name: s for s in tracer.finished}
        assert {"predict", "queue", "batch", "model"} <= set(spans)
        # the whole tree joined the CLIENT's trace (Dapper propagation)
        assert spans["predict"].parent_id == client_span
        assert spans["queue"].parent_id == spans["predict"].span_id
        assert spans["batch"].parent_id == spans["predict"].span_id
        assert spans["model"].parent_id == spans["batch"].span_id
        tids = {s.trace_id for s in spans.values()}
        assert tids == {client_trace}
        # header out names the server-side root of the request
        assert header_out == \
            f"00-{client_trace}-{spans['predict'].span_id}-01"
        assert spans["predict"].attributes["code"] == 200
        # the JSONL export carries the same structure
        lines = [json.loads(l) for l in tracer.to_jsonl().splitlines()]
        exported = {d["name"]: d for d in lines}
        assert exported["model"]["parent_id"] == exported["batch"]["span_id"]
        assert all(d["duration_ms"] is not None for d in lines)

    def test_fault_lands_in_model_span(self, rng):
        """serving.infer faults record the model-call span they hit."""
        from deeplearning4j_tpu.serving import InferenceServer
        net = _tiny_net()
        tracer = Tracer()
        server = InferenceServer(net, port=0, max_batch=1, tracer=tracer)
        base = f"http://127.0.0.1:{server.port}"
        x = rng.normal(size=(1, 5)).astype(np.float32)
        plan = faults.FaultPlan().fail_at("serving.infer", call=1,
                                         exc=RuntimeError("chip fell over"))
        try:
            with plan.active():
                code, _ = _post(base, "/predict", {"inputs": x.tolist()})
                assert code == 500
        finally:
            server.stop(drain=False)
        (ctx,) = plan.trigger_context
        assert ctx["span"]["name"] == "model"
        model_spans = [s for s in tracer.finished if s.name == "model"]
        assert ctx["span"]["span_id"] in {s.span_id for s in model_spans}


# ---------------------------------------------------------------------------
# metrics-convention lint (ISSUE 13 satellite): the exposition contract
# ---------------------------------------------------------------------------

_NAME_LINT = re.compile(r"^[a-z][a-z0-9_]*$")
_LABEL_LINT = re.compile(r"^[a-z][a-z0-9_]*$")

# histograms/gauges that count THINGS rather than measure a unit —
# additions need a reason (a unitless distribution like a batch size),
# not a forgotten _seconds suffix
_UNITLESS_HISTOGRAMS = {
    "serving_batch_size",           # examples per coalesced model call
    "decode_batch_occupancy",       # lanes active per decode step
    "kv_page_refcount",             # owners per shared KV page (a count
    #                                 distribution, observed per retain)
}
_UNIT_SUFFIXES = ("_seconds", "_bytes")
# reserved by the Prometheus exposition itself
_RESERVED_LABELS = {"le", "quantile"}
_MAX_SERIES_PER_METRIC = 128


def _lint_registry(reg, where: str):
    problems = []
    for name in reg.names():
        m = reg.get(name)
        if not _NAME_LINT.match(name):
            problems.append(f"{where}: {name}: not snake_case")
        if m.kind == "counter" and not name.endswith("_total"):
            problems.append(f"{where}: {name}: counter without _total")
        if m.kind != "counter" and name.endswith("_total"):
            problems.append(f"{where}: {name}: _total reserved for "
                            "counters")
        if m.kind == "histogram" and name not in _UNITLESS_HISTOGRAMS \
                and not name.endswith(_UNIT_SUFFIXES):
            problems.append(
                f"{where}: {name}: histogram without a unit suffix "
                f"({'/'.join(_UNIT_SUFFIXES)}) — if it is genuinely "
                "unitless, add it to _UNITLESS_HISTOGRAMS with a reason")
        for label in m.labelnames:
            if label in _RESERVED_LABELS:
                problems.append(f"{where}: {name}: label {label!r} is "
                                "reserved by the exposition format")
            if not _LABEL_LINT.match(label):
                problems.append(f"{where}: {name}: label {label!r} not "
                                "snake_case")
        snap = m.snapshot()
        n_series = len(snap.get("series", ()))
        if n_series > _MAX_SERIES_PER_METRIC:
            problems.append(
                f"{where}: {name}: {n_series} labelsets (> "
                f"{_MAX_SERIES_PER_METRIC}) — unbounded label "
                "cardinality?")
    return problems


class TestMetricsConventions:
    """Tier-1 lint of the exposition contract: every metric any layer
    registers must keep the naming/label invariants, so new
    instrumentation cannot silently break scrapers."""

    def test_default_registry_obeys_conventions(self):
        """Whatever this process registered into the process-default
        registry so far (the full tier-1 run exercises most layers)."""
        problems = _lint_registry(REGISTRY, "default")
        assert not problems, "\n".join(problems)

    def test_representative_families_obey_conventions(self):
        """Deterministic coverage independent of test order: register
        the elastic / tracing / xla / decode / serving / health metric
        families into a fresh registry and lint them."""
        from deeplearning4j_tpu.models import transformer_lm
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        from deeplearning4j_tpu.optimize import MetricsListener
        from deeplearning4j_tpu.parallel import elastic
        from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                                       PagedDecodeEngine)
        from deeplearning4j_tpu.util import health, tracing, xla

        reg = MetricsRegistry()
        elastic.rounds_counter(reg)
        elastic.round_seconds_histogram(reg)
        elastic.round_wait_seconds_histogram(reg)
        elastic.staleness_gauge(reg)
        elastic.transitions_counter(reg)
        tracing.dropped_spans_counter(reg)
        xla.compile_seconds_histogram(reg)
        xla.compiled_flops_gauge(reg)
        xla.compiled_bytes_gauge(reg)
        # training-health telemetry (ISSUE 15): the engine registers
        # training_health_state + the model_stats_* gauges, the listener
        # the layer-labeled skip counter. The per-layer `layer` label is
        # bounded by model DEPTH (layer keys / vertex names), so the
        # ≤128-series cardinality lint holds for any in-tree model.
        health.HealthEngine(model="lint", registry=reg)
        MetricsListener(registry=reg, name="lint")
        # a scheduler construction registers the whole decode plane
        # (goodput split included); no dispatch, so this is cheap
        net = ComputationGraph(transformer_lm(
            8, n_layers=1, d_model=8, n_heads=1, d_ff=16, seed=3,
            input_ids=True, max_cache_t=16)).init()
        # prefix_cache + int8 so the prefix-caching families (hit
        # outcomes, shared-page gauge, refcount histogram, CoW counter)
        # register and lint too (ISSUE 19)
        engine = PagedDecodeEngine(net, max_batch=2, page_size=4,
                                   pages_per_seq=4, registry=reg,
                                   prefix_cache=True, kv_dtype="int8")
        sched = DecodeScheduler(engine, registry=reg,
                                start_thread=False)
        # a hybrid net registers what OPT's engine has no use for: the
        # state arena's gauge and reset counter, the expert layers'
        # routing counts (ISSUE 33); `where` has two values
        from deeplearning4j_tpu.models import nemotron_h_lm
        hybrid = ComputationGraph(nemotron_h_lm(
            8, pattern="ME*", d_model=8, n_heads=2, n_kv_heads=1,
            mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=4,
            n_experts=4, top_k=2, d_latent=4, d_expert=4, d_shared=4,
            experts_held=2, max_cache_t=16)).init()
        hybrid_engine = PagedDecodeEngine(hybrid, max_batch=2, page_size=4,
                                          pages_per_seq=4, registry=reg)
        hybrid_engine.run(np.zeros((1, 4), np.int32),
                          np.arange(4, dtype=np.int32)[None],
                          np.zeros(1, np.int32),
                          np.full((1, 4), hybrid_engine.arena.sentinel,
                                  np.int32), np.full(1, 3, np.int32),
                          np.zeros(1, np.int32))
        # the serving-fleet tier (ISSUE 20): router/agent families plus
        # the drain-outcome counter on the replica side
        from deeplearning4j_tpu.serving import fleet as _fleet
        from deeplearning4j_tpu.serving import server as _server
        _fleet.requests_counter(reg)
        _fleet.failovers_counter(reg)
        _fleet.heartbeats_counter(reg)
        _fleet.router_latency_histogram(reg)
        _fleet.live_replicas_gauge(reg)
        _fleet.ready_replicas_gauge(reg)
        _fleet.shed_counter(reg)
        _server.drain_counter(reg)
        problems = _lint_registry(reg, "representative")
        assert not problems, "\n".join(problems)
        assert reg.get("decode_goodput_tokens_total") is not None
        for fam in ("kv_prefix_hits_total", "kv_prefix_hit_pages_total",
                    "kv_pages_shared", "kv_page_refcount",
                    "kv_pages_cow_total"):
            assert reg.get(fam) is not None, fam
        for fam in ("moe_routed_pairs_total", "moe_computed_pairs_total",
                    "moe_expert_load_peak_pairs_total",
                    "moe_expert_load_steps_total",
                    "decode_state_resets_total", "decode_state_bytes"):
            assert reg.get(fam) is not None, fam
        routed = reg.get("moe_routed_pairs_total").snapshot()["series"]
        assert {s["labels"]["where"] for s in routed} == {"held", "absent"}
        assert sum(s["value"] for s in routed) == 4 * 2    # tokens x top_k
        for fam in ("fleet_requests_total", "fleet_failovers_total",
                    "fleet_heartbeats_total",
                    "fleet_request_latency_seconds",
                    "fleet_live_replicas", "fleet_ready_replicas",
                    "serving_drain_total"):
            assert reg.get(fam) is not None, fam
        assert sched is not None  # keep the weak gauges alive till here
