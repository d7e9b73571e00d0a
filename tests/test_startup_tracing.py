"""ISSUE 37: set-up timed from inside. Start-up phases as regions of ONE
histogram, every compilation told apart at the jit seam (stage seconds,
cache requests and hits, the slowest sample), and ``xla_compile_seconds``
holding a ladder program's ahead-of-time compile rather than the look-up
its first call makes. On the CPU: counts, identities and parentage only."""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer_lm
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import InferenceServer
from deeplearning4j_tpu.serving.decode import PagedDecodeEngine
from deeplearning4j_tpu.util import flightrecorder as _flight
from deeplearning4j_tpu.util import metrics as _metrics
from deeplearning4j_tpu.util import tracing as _tracing
from deeplearning4j_tpu.util import xla as _xla
from deeplearning4j_tpu.util.metrics import MetricsRegistry
from deeplearning4j_tpu.util.tracing import Tracer

VOCAB = 24
ENGINE = dict(max_batch=2, page_size=4, pages_per_seq=8, block_len=4,
              prefill_chunk=8, start_thread=False)
ENGINE_PHASES = ("engine_build", "warmup", "warmup.plan", "warmup.compile",
                 "warmup.run")


def _graph():
    return ComputationGraph(transformer_lm(
        VOCAB, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=5,
        input_ids=True, max_cache_t=32))


def _mlp():
    return MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(3).learning_rate(0.1).list()
        .layer(DenseLayer(n_in=4, n_out=5, activation="tanh"))
        .layer(OutputLayer(n_in=5, n_out=3, activation="softmax",
                           loss="mcxent")).build())


def _phases(reg):
    """{phase: (count, sum)} of ``startup_phase_seconds`` in ``reg``."""
    h = reg.get("startup_phase_seconds")
    if h is None:
        return {}
    return {s["labels"]["phase"]: (s["count"], s["sum"])
            for s in h.snapshot()["series"]}


def _compile_samples(reg):
    h = reg.get("xla_compile_seconds")
    if h is None:
        return {}
    return {s["labels"]["fn"]: (s["count"], s["sum"])
            for s in h.snapshot()["series"]}


@pytest.fixture(scope="module")
def net():
    return _graph().init()


@pytest.fixture(scope="module")
def served(net):
    """One server started with a tracer: (server, tracer)."""
    tracer = Tracer()
    server = InferenceServer(net, tracer=tracer, decode=dict(ENGINE))
    yield server, tracer
    server.stop(drain=False)


# ---------------------------------------------------------------------------
# init(): one phase a call, pre_init once a process
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", [_mlp, _graph], ids=["multilayer", "graph"])
def test_init_observes_its_phase_once(make):
    before = _phases(_metrics.REGISTRY).get("init", (0, 0.0))
    made = make().init()
    count, total = _phases(_metrics.REGISTRY)["init"]
    assert count == before[0] + 1
    assert total > before[1]
    # the phase ended with the state on the device
    assert all(leaf.is_fully_addressable for leaf in
               jax.tree_util.tree_leaves(made.params))


def test_pre_init_is_stamped_once_a_process():
    _mlp().init()
    count, age = _phases(_metrics.REGISTRY)["pre_init"]
    _mlp().init()
    assert _phases(_metrics.REGISTRY)["pre_init"] == (count, age) == (1, age)
    # process start to the first init(): not after now
    assert 0.0 < age <= _xla.process_age_s()


def test_pre_init_is_left_out_where_proc_is_absent(monkeypatch):
    def no_proc(*a, **k):
        raise FileNotFoundError("/proc/self/stat")
    monkeypatch.setattr("builtins.open", no_proc)
    assert _xla.process_age_s() is None
    monkeypatch.undo()
    monkeypatch.setattr(_xla, "_done_once", {"listen"})
    monkeypatch.setattr(_xla, "process_age_s", lambda: None)
    before = _phases(_metrics.REGISTRY)
    _mlp().init()
    after = _phases(_metrics.REGISTRY)
    assert after.get("pre_init") == before.get("pre_init")
    assert after["init"][0] == before["init"][0] + 1


# ---------------------------------------------------------------------------
# InferenceServer(decode=...): the engine's phases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("phase", ENGINE_PHASES)
def test_server_observes_each_engine_phase_once(served, phase):
    server, _ = served
    count, total = _phases(server.registry)[phase]
    assert count == 1 and total > 0


def test_warmup_is_its_three_parts(served):
    server, _ = served
    p = _phases(server.registry)
    rungs = len(_compile_samples(server.registry))
    parts = sum(p[k][1] for k in ("warmup.plan", "warmup.compile",
                                  "warmup.run"))
    assert 0 <= p["warmup"][1] - parts < 1e-3 * rungs


def test_each_ladder_program_has_one_compile_sample_made_by_the_pool(served):
    """``xla_compile_seconds{fn}`` holds the pool task's ``lower().compile()``
    (the ``compile.program`` span under ``warmup.compile``), one a ladder
    key; the second pass's first call counted the trace and observed
    nothing."""
    server, tracer = served
    samples = _compile_samples(server.registry)
    retraces = server.registry.get("jit_retraces_total")
    ladder = {s["labels"]["fn"]: s["value"]
              for s in retraces.snapshot()["series"]}
    assert set(samples) == set(ladder) and len(ladder) == 6
    assert all(v == 1 for v in ladder.values())
    (pool,) = tracer.find("warmup.compile")
    spans = {s.attributes["fn"]: s for s in tracer.find("compile.program")}
    assert set(spans) == set(ladder)
    for fn, (count, total) in samples.items():
        assert count == 1
        assert spans[fn].parent_id == pool.span_id
        assert total == pytest.approx(spans[fn].duration_ms / 1000.0)
        # inside the pool's wall, not in the second pass
        assert spans[fn].start_mono >= pool.start_mono
        assert (spans[fn].start_mono + total
                <= pool.start_mono + pool.duration_ms / 1000.0 + 1e-6)


def test_compile_wall_reads_the_ladders_compiles(served):
    server, _ = served
    samples = _compile_samples(server.registry)
    assert server.decode.engine._compile_wall() == pytest.approx(
        sum(total for _, total in samples.values()))


def test_the_spans_of_one_startup_share_a_trace_under_one_root(served):
    _, tracer = served
    (root,) = tracer.find("startup")
    assert root.parent_id is None
    names = ("startup.engine_build", "startup.warmup", "warmup.plan",
             "warmup.compile", "warmup.run", "compile.program",
             "startup.cost_analysis")
    spans = [s for n in names for s in tracer.find(n)]
    assert {s.trace_id for s in spans} == {root.trace_id}
    (warm,) = tracer.find("startup.warmup")
    (build,) = tracer.find("startup.engine_build")
    assert warm.parent_id == build.parent_id == root.span_id
    for part in ("warmup.plan", "warmup.compile", "warmup.run"):
        (span,) = tracer.find(part)
        assert span.parent_id == warm.span_id
    (run,) = tracer.find("warmup.run")
    costs = tracer.find("startup.cost_analysis")
    assert len(costs) == 6 and all(s.parent_id == run.span_id for s in costs)


def test_cost_analysis_is_a_phase_observed_once_a_program(served):
    server, _ = served
    count, total = _phases(server.registry)["cost_analysis"]
    assert count == 6 and total > 0


def test_slowest_compile_is_the_largest_sample_so_far(served):
    server, _ = served
    gauge = _metrics.REGISTRY.get("xla_compile_slowest_seconds")
    largest = max(t for _, t in _compile_samples(server.registry).values())
    assert gauge.value() >= largest
    # a quicker compile afterwards does not lower it
    held = gauge.value()
    guarded = _xla.retrace_guard(jax.jit(lambda x: x - 7.0), "unit.quick",
                                 MetricsRegistry())
    guarded(jnp.ones(2))
    assert gauge.value() >= held


def test_background_warmup_observes_the_same_phases(net):
    tracer = Tracer()
    server = InferenceServer(net, tracer=tracer, decode=dict(ENGINE),
                             warmup_background=True)
    try:
        deadline = time.monotonic() + 120.0
        while server._warming and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not server._warming
        p = _phases(server.registry)
        assert all(p[phase][0] == 1 for phase in ENGINE_PHASES)
        (root,) = tracer.find("startup")
        (background,) = tracer.find("startup.background")
        (warm,) = tracer.find("startup.warmup")
        assert background.parent_id == root.span_id
        assert warm.parent_id == background.span_id
        assert warm.trace_id == root.trace_id
        assert len(tracer.find("compile.program")) == 6
    finally:
        server.stop(drain=False)


def test_startup_regions_are_host_spans_on_their_own_threads_lines(
        net, tmp_path):
    """Under a profiler session the phases are host spans of the trace
    itself: the warm-up's on the line of the thread that warmed up, each
    ``compile.program`` on the line of the pool thread that compiled."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _graph().init()
        PagedDecodeEngine(net, registry=MetricsRegistry(), max_batch=2,
                          page_size=4, pages_per_seq=8, block_len=4,
                          prefill_chunk=8).warmup()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            # a thread is a line; the lines share a name, so go by position
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    lines.setdefault(e.name, set()).add(i)
    for name in ("startup.init", "startup.engine_build", "startup.warmup",
                 "warmup.plan", "warmup.compile", "warmup.run",
                 "compile.program", "startup.cost_analysis"):
        assert name in lines, name
    main = lines["startup.warmup"]
    assert len(main) == 1
    for name in ("startup.init", "startup.engine_build", "warmup.plan",
                 "warmup.compile", "warmup.run", "startup.cost_analysis"):
        assert lines[name] == main
    assert lines["compile.program"] and not (lines["compile.program"] & main)


# ---------------------------------------------------------------------------
# the guard: a compiling first call, and a program compiled ahead of it
# ---------------------------------------------------------------------------

def test_a_train_steps_first_call_still_observes_one_sample():
    net = _mlp().init()
    x = np.ones((6, 4), np.float32)
    y = np.eye(3, dtype=np.float32)[[0, 1, 2, 0, 1, 2]]
    fn = "MultiLayerNetwork.train_step"
    before = _compile_samples(_metrics.REGISTRY).get(fn, (0, 0.0))
    cost_before = _phases(_metrics.REGISTRY).get("cost_analysis", (0, 0.0))
    net.fit_batch(x, y)
    count, total = _compile_samples(_metrics.REGISTRY)[fn]
    assert count == before[0] + 1 and total > before[1]
    assert (_phases(_metrics.REGISTRY)["cost_analysis"][0]
            == cost_before[0] + 1)
    net.fit_batch(x, y)             # the same shape again compiles nothing
    assert _compile_samples(_metrics.REGISTRY)[fn][0] == count


def test_a_precompiled_signature_observes_at_precompile_not_at_its_call():
    reg = MetricsRegistry()
    guarded = _xla.retrace_guard(jax.jit(lambda x: x * 2.0 + 1.0),
                                 "unit.ahead", reg)
    x = jnp.ones(4)
    guarded.precompile((x,))
    count, total = _compile_samples(reg)["unit.ahead"]
    assert count == 1 and total > 0
    seq = max((e["seq"] for e in _flight.events("compile")), default=0)
    assert np.allclose(guarded(x), 3.0)
    assert _compile_samples(reg)["unit.ahead"] == (count, total)
    assert reg.get("jit_retraces_total").value(fn="unit.ahead") == 1
    (event,) = [e for e in _flight.events("compile") if e["seq"] > seq
                and e["fn"] == "unit.ahead"]
    assert event["compile_seconds"] == pytest.approx(total, abs=1e-4)
    # a signature nobody compiled ahead compiles at its first call
    guarded(jnp.ones(5))
    assert _compile_samples(reg)["unit.ahead"][0] == 2
    assert _phases(reg)["cost_analysis"][0] == 2


def test_a_compile_on_a_worker_thread_names_its_cause():
    tracer, reg = Tracer(), MetricsRegistry()
    guarded = _xla.retrace_guard(jax.jit(lambda x: x + 0.5), "unit.worker",
                                 reg)
    with tracer.span("cause") as cause:
        worker = threading.Thread(
            target=guarded.precompile, args=((jnp.ones(3),), cause))
        worker.start()
        worker.join(60.0)
    assert not worker.is_alive()
    (span,) = tracer.find("compile.program")
    assert span.parent_id == cause.span_id
    assert span.trace_id == cause.trace_id
    assert span.attributes == {"fn": "unit.worker"}


def test_joining_finds_the_open_trace_or_nothing():
    assert _tracing.joining() == {}
    tracer = Tracer()
    with tracer.span("open") as span:
        assert _tracing.joining() == {"tracer": tracer, "parent": span}
        inner = _xla.startup_region("startup.unit", MetricsRegistry())
        with inner:
            pass
        assert inner.span.parent_id == span.span_id
    assert _tracing.joining(span) == {"tracer": tracer, "parent": span}
    assert _tracing.joining() == {}


# ---------------------------------------------------------------------------
# the jax.monitoring listeners
# ---------------------------------------------------------------------------

def _series():
    """Every series the listeners keep, flat."""
    out = {}
    for name in ("xla_compile_stage_seconds_total", "xla_compile_cache_total"):
        for s in _metrics.REGISTRY.get(name).snapshot()["series"]:
            out[(name, *s["labels"].values())] = s["value"]
    return out


@pytest.mark.parametrize("event, stage", [
    ("/jax/core/compile/jaxpr_trace_duration", "trace"),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", "lower"),
    ("/jax/core/compile/backend_compile_duration", "backend"),
    ("/jax/compilation_cache/cache_retrieval_time_sec", "cache_retrieval")])
def test_a_duration_event_moves_its_stage_and_nothing_else(event, stage):
    _xla.listen_to_compiles()
    before = _series()
    jax.monitoring.record_event_duration_secs(event, 1.25, fun_name="f")
    after = _series()
    key = ("xla_compile_stage_seconds_total", stage)
    assert after[key] == pytest.approx(before.get(key, 0.0) + 1.25)
    assert {k: v for k, v in after.items() if k != key} \
        == {k: v for k, v in before.items() if k != key}


@pytest.mark.parametrize("event, result", [
    ("/jax/compilation_cache/compile_requests_use_cache", "request"),
    ("/jax/compilation_cache/cache_hits", "hit")])
def test_a_cache_event_moves_its_count_and_nothing_else(event, result):
    _xla.listen_to_compiles()
    before = _series()
    jax.monitoring.record_event(event)
    after = _series()
    key = ("xla_compile_cache_total", result)
    assert after[key] == before.get(key, 0.0) + 1
    assert {k: v for k, v in after.items() if k != key} \
        == {k: v for k, v in before.items() if k != key}


@pytest.mark.parametrize("event", [
    "/jax/compilation_cache/cache_misses",
    "/jax/compilation_cache/compile_time_saved_sec",
    "/jax/core/compile/backend_compile_duration/",
    "/something/else"])
def test_any_other_event_is_ignored(event):
    _xla.listen_to_compiles()
    before = _series()
    jax.monitoring.record_event(event)
    jax.monitoring.record_event_duration_secs(event, 2.0)
    assert _series() == before


def test_registering_twice_adds_nothing():
    _xla.listen_to_compiles()
    from jax._src import monitoring
    listeners = (len(monitoring.get_event_listeners()),
                 len(monitoring.get_event_duration_listeners()))
    _xla.listen_to_compiles()
    _xla.retrace_guard(jax.jit(lambda x: x), "unit.twice", MetricsRegistry())
    _mlp().init()
    assert (len(monitoring.get_event_listeners()),
            len(monitoring.get_event_duration_listeners())) == listeners
    before = _series()
    jax.monitoring.record_event(
        "/jax/compilation_cache/compile_requests_use_cache")
    key = ("xla_compile_cache_total", "request")
    assert _series()[key] == before.get(key, 0.0) + 1


def test_one_jit_compile_moves_every_stage():
    _xla.listen_to_compiles()
    before = _series()
    jax.jit(lambda x: jnp.tanh(x) * 3.5 + jnp.sum(x))(jnp.ones(7))
    after = _series()
    for stage in ("trace", "lower", "backend"):
        key = ("xla_compile_stage_seconds_total", stage)
        assert after[key] > before.get(key, 0.0), stage


def test_the_same_program_asked_for_again_is_a_cache_hit():
    """Two functions of one name and one body are two traces and one
    program: the second asks the persistent cache (the suite keeps one,
    ``tests/conftest.py``) and is served, with the look-up's seconds."""
    _xla.listen_to_compiles()

    def make():
        def startup_tracing_twin(x):
            return jnp.cos(x) * 1.375 - x
        return jax.jit(startup_tracing_twin)

    x = jnp.ones(9)
    make()(x)                                   # writes the entry, or hits
    before = _series()
    make()(x)
    after = _series()
    for key in (("xla_compile_cache_total", "request"),
                ("xla_compile_cache_total", "hit")):
        assert after[key] == before.get(key, 0.0) + 1, key
    key = ("xla_compile_stage_seconds_total", "cache_retrieval")
    assert after[key] > before.get(key, 0.0)
