"""ISSUE 26: the ``region`` seam, the dispatch split with bytes counted,
the scheduler's named waits and admission reasons, per-delivery stamps,
``start_mono`` on spans, and the stable device-side names (named scopes
and XLA module names) — all on the CPU, counts and identities only."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer_lm
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine,
                                               _module_name)
from deeplearning4j_tpu import rng as _rng
from deeplearning4j_tpu.util.metrics import MetricsRegistry
from deeplearning4j_tpu.util.tracing import Tracer, region

VOCAB = 24


def _net(seed=5, window=32):
    return ComputationGraph(transformer_lm(
        VOCAB, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=seed,
        input_ids=True, max_cache_t=window)).init()


@pytest.fixture(scope="module")
def net():
    return _net()


def _hist(reg):
    return reg.histogram("phase_seconds", "", ("phase",))


# ---------------------------------------------------------------------------
# region: sinks, nesting, error status
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_hist", [False, True])
@pytest.mark.parametrize("with_tracer", [False, True])
@pytest.mark.parametrize("raises", [False, True])
def test_region_sinks(with_hist, with_tracer, raises):
    """Every combination of sinks: the histogram is observed only when
    the block did not raise, the span always ends (status error when it
    did), ``seconds`` is always set, and a region with no sink is just a
    pair of clock reads."""
    reg = MetricsRegistry()
    hist = _hist(reg) if with_hist else None
    tracer = Tracer() if with_tracer else None
    labels = {"phase": "p"} if with_hist else {}
    r = region("unit.phase", hist, tracer=tracer,
               attributes={"k": 1}, **labels)
    if raises:
        with pytest.raises(RuntimeError):
            with r:
                raise RuntimeError("x")
    else:
        with r as got:
            assert got is r
            time.sleep(0.001)
    assert r.seconds > 0
    if with_hist:
        assert hist.count(phase="p") == (0 if raises else 1)
        if not raises:
            assert hist.sum(phase="p") == pytest.approx(r.seconds)
    if with_tracer:
        (span,) = tracer.finished
        assert span is r.span and span.name == "unit.phase"
        assert span.status == ("error" if raises else "ok")
        assert span.attributes == {"k": 1}
        # the span and the histogram got the SAME pair of clock reads
        assert span.duration_ms == pytest.approx(r.seconds * 1000.0)
        assert tracer.current() is None
    else:
        assert r.span is None
        r.set_attribute("ignored", 1)      # no span: a no-op, no raise


def test_region_nesting_parents_and_restores_active_span():
    tracer = Tracer()
    root = tracer.start("root")
    with region("outer", tracer=tracer, parent=root) as outer:
        assert tracer.current() is outer.span
        with region("inner", tracer=tracer) as inner:
            assert tracer.current() is inner.span
            inner.set_attribute("n", 2)
        assert tracer.current() is outer.span
        # Tracer.span() is the same class and nests the same way
        with tracer.span("via_tracer") as s:
            assert s.parent_id == outer.span.span_id
    assert tracer.current() is None
    assert outer.span.parent_id == root.span_id
    assert inner.span.parent_id == outer.span.span_id
    assert inner.span.trace_id == root.trace_id
    assert inner.span.attributes == {"n": 2}
    assert outer.seconds >= inner.seconds


def test_tracer_span_is_one_class_not_one_per_call():
    tracer = Tracer()
    a, b = tracer.span("a"), tracer.span("b")
    assert type(a) is type(b) and isinstance(a, region)


def test_region_lands_on_the_profilers_host_plane(tmp_path):
    """Under a profiler session a region is a host span of the trace
    itself (so it is on the device trace's clock); ``fit.step`` style
    step annotations ride the same mechanism."""
    from jax.profiler import ProfileData
    import glob
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with region("unit.on_the_trace"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert "unit.on_the_trace" in names


def test_record_backdates_both_clocks_and_takes_a_status():
    tracer = Tracer()
    before_mono, before_unix = time.monotonic(), time.time()
    s = tracer.record("phase", 0.5, status="deadline")
    assert s.status == "deadline"
    assert s.start_mono <= before_mono - 0.5 + 0.05
    assert s.start_unix <= before_unix - 0.5 + 0.05
    assert s.to_dict()["start_mono"] == s.start_mono


# ---------------------------------------------------------------------------
# engine: dispatch split + bytes
# ---------------------------------------------------------------------------

def _engine(net, **kw):
    cfg = dict(max_batch=2, page_size=4, pages_per_seq=8, prefill_chunk=4,
               registry=MetricsRegistry())
    cfg.update(kw)
    return PagedDecodeEngine(net, **cfg)


def _phase_sum(reg, **labels):
    h = reg.get("decode_dispatch_phase_seconds")
    return sum(s["sum"] for s in h.snapshot()["series"]
               if all(s["labels"][k] == v for k, v in labels.items()))


def _run(sched, reqs, limit=400):
    for _ in range(limit):
        if all(r.done for r in reqs):
            return
        sched.step_once()
    raise AssertionError("requests did not finish")


@pytest.mark.parametrize("block_len", [1, 4])
def test_dispatch_phases_sum_to_the_dispatch_component(net, block_len):
    """enqueue + device_wait + fetch == component="dispatch", for every
    synced dispatch (the identity is per dispatch, so it holds on sums),
    and each kind has one observation per phase per dispatch."""
    eng = _engine(net, block_len=block_len)
    sched = DecodeScheduler(eng, start_thread=False)
    reqs = [sched.submit([1, 2, 3, 4, 5, 6], 6), sched.submit([7, 8], 5)]
    _run(sched, reqs)
    reg = eng.registry
    tick = reg.get("decode_host_tick_seconds")
    assert _phase_sum(reg) == pytest.approx(
        tick.sum(component="dispatch"), rel=1e-9)
    phases = reg.get("decode_dispatch_phase_seconds")
    dispatches = reg.get("decode_dispatches_total")
    for kind in ("paged", "fused") if block_len > 1 else ("paged",):
        n = dispatches.value(kind=kind)
        assert n > 0
        for phase in ("enqueue", "device_wait", "fetch"):
            assert phases.count(kind=kind, phase=phase) == n


def test_warmup_dispatches_stay_out_of_every_series(net):
    eng = _engine(net, block_len=4)
    eng.warmup()
    reg = eng.registry
    assert reg.get("decode_dispatch_phase_seconds").snapshot()["series"] == []
    assert reg.get("decode_d2h_bytes_total").total() == 0
    assert reg.get("decode_dispatches_total").total() == 0


def test_d2h_bytes_equal_nbytes_of_what_came_back(net):
    """One prefill chunk returns probs [B, V], the one row a lane that the
    caller named; one fused block returns tokens, valid, n_emitted and
    done: the counter moves by exactly the bytes of the arrays the host
    got."""
    eng = _engine(net, block_len=4)
    d2h = eng.registry.get("decode_d2h_bytes_total")
    lane = eng.acquire_lane(12, prompt=None)
    eng.ensure_pages(lane, 4)
    tables = eng._tables[lane][None, :]
    probs = eng.run(np.array([[1, 2, 3, 4]], np.int32),
                    np.arange(4, dtype=np.int32)[None, :],
                    np.zeros(1, np.int32), tables, np.full(1, 3, np.int32))
    assert probs.shape == (1, VOCAB)
    assert d2h.value(kind="paged") == probs.nbytes
    eng.advance(lane, 4)
    eng.ensure_pages(lane, 4)
    toks, valid, n_emitted = eng.run_fused(
        np.array([5], np.int32), eng._tables[lane][None, :],
        np.array([4], np.int32), np.ones(1, bool), np.array([4], np.int32),
        np.full(1, -1, np.int32), np.zeros(1, np.float32),
        np.zeros(1, np.int32), np.ones(1, np.float32),
        np.zeros((1, 4), np.float32))
    done_bytes = np.zeros(1, bool).nbytes
    assert d2h.value(kind="fused") == (toks.nbytes + valid.nbytes
                                       + n_emitted.nbytes + done_bytes)
    # a fused block hands back ids, not a distribution
    assert d2h.value(kind="fused") < d2h.value(kind="paged")


def test_failed_dispatch_is_not_a_sample(net, monkeypatch):
    eng = _engine(net)

    def boom(*a, **k):
        raise RuntimeError("device fell over")

    monkeypatch.setattr(jax, "block_until_ready", boom)
    lane = eng.acquire_lane(8, prompt=None)
    eng.ensure_pages(lane, 4)
    with pytest.raises(RuntimeError):
        eng.run(np.zeros((1, 4), np.int32), np.full((1, 4), -1, np.int32),
                np.zeros(1, np.int32), eng._tables[lane][None, :],
                np.zeros(1, np.int32))
    phases = eng.registry.get("decode_dispatch_phase_seconds")
    assert phases.count(kind="paged", phase="enqueue") == 1
    assert phases.count(kind="paged", phase="device_wait") == 0
    assert eng.registry.get("decode_host_tick_seconds").count(
        component="dispatch") == 0


# ---------------------------------------------------------------------------
# scheduler: TTFT components, blocked_by, deliveries, waits
# ---------------------------------------------------------------------------

def _component_sums(reg):
    out = {}
    for s in reg.get("decode_ttft_component_seconds").snapshot()["series"]:
        key = (s["labels"]["component"], s["labels"]["blocked_by"])
        out[key] = (s["sum"], s["count"])
    return out


@pytest.mark.parametrize("block_len", [1, 4])
def test_ttft_components_sum_to_ttft_and_deliveries_to_tokens(net,
                                                              block_len):
    eng = _engine(net, block_len=block_len, max_batch=2)
    sched = DecodeScheduler(eng, start_thread=False)
    reqs = [sched.submit([1, 2, 3, 4, 5], 7), sched.submit([6, 7], 3),
            sched.submit([8, 9, 10], 6)]       # the third queues on lanes
    _run(sched, reqs)
    reg = eng.registry
    parts = _component_sums(reg)
    ttft = reg.get("decode_ttft_seconds")
    assert sum(v[0] for v in parts.values()) == pytest.approx(
        ttft.sum(), rel=1e-9)
    assert all(b == "none" for (c, b) in parts if c != "queue_wait")
    assert sum(n for (c, _), (_, n) in parts.items()
               if c == "queue_wait") == len(reqs)
    gaps = reg.get("decode_delivery_gap_seconds")
    for r in reqs:
        assert sum(n for _, n in r.deliveries) == len(r.tokens)
        stamps = [t for t, _ in r.deliveries]
        assert stamps == sorted(stamps)
        assert stamps[0] == r.t_first_token and stamps[-1] <= r.t_done
        assert r.deliveries[0][1] == 1        # prefill hands the first one
        if block_len > 1:
            assert max(n for _, n in r.deliveries) > 1
    assert gaps.count() == sum(len(r.deliveries) - 1 for r in reqs)


def test_blocked_by_lanes_pages_and_none(net):
    """One request admitted at once, one refused for want of a lane, one
    refused for want of pages: each carries the reason of the last
    refused pass, and ``queue_wait`` is observed under it."""
    # 2 lanes, but pages for one long sequence only
    eng = _engine(net, max_batch=2, num_pages=8, pages_per_seq=8)
    sched = DecodeScheduler(eng, start_thread=False)
    first = sched.submit(list(range(1, 9)), 16)     # reserves 6 of 8 pages
    sched.step_once()
    assert first.blocked_by == "none" and first.t_admit is not None
    on_pages = sched.submit(list(range(1, 9)), 16)  # a lane is free; pages not
    sched.step_once()
    assert eng.refused_by == "pages"
    assert on_pages.blocked_by == "pages" and on_pages.t_admit is None
    _run(sched, [first, on_pages])
    assert on_pages.blocked_by == "pages"           # kept after admission

    eng2 = _engine(net, max_batch=1)
    sched2 = DecodeScheduler(eng2, start_thread=False)
    a = sched2.submit([1, 2, 3], 4)
    b = sched2.submit([4, 5, 6], 4)
    sched2.step_once()
    assert eng2.refused_by == "lanes"
    assert (a.blocked_by, b.blocked_by) == ("none", "lanes")
    _run(sched2, [a, b])
    parts = _component_sums(eng2.registry)
    assert parts[("queue_wait", "lanes")][1] == 1
    assert parts[("queue_wait", "none")][1] == 1
    assert ("queue_wait", "pages") in _component_sums(eng.registry)


def test_wait_idle_is_observed_when_the_queue_is_empty(net):
    eng = _engine(net)
    sched = DecodeScheduler(eng, start_thread=True)
    try:
        waits = eng.registry.get("decode_sched_wait_seconds")
        deadline = time.monotonic() + 10.0
        while waits.count(why="idle") < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert waits.count(why="idle") >= 2
        assert waits.count(why="blocked") == 0
        assert waits.sum(why="idle") > 0
    finally:
        sched.stop()


def test_span_export_lines_up_with_the_requests_clock(net, tmp_path):
    """``start_mono`` in the JSONL export is on the clock of
    ``DecodeRequest.t_*``: the queue span starts at ``t_submit`` and
    lasts the ``queue_wait`` the histogram observed, and every
    prefill_chunk / decode_block span lies inside the request's life."""
    tracer = Tracer()
    eng = _engine(net, max_batch=1, block_len=4)
    sched = DecodeScheduler(eng, start_thread=False, tracer=tracer)
    reqs = [sched.submit([1, 2, 3, 4, 5, 6], 6), sched.submit([7, 8], 5)]
    _run(sched, reqs)
    path = tmp_path / "spans.jsonl"
    tracer.export_jsonl(str(path))
    spans = [json.loads(line) for line in open(path)]
    assert all("start_mono" in s and "start_unix" in s for s in spans)
    for r in reqs:
        mine = [s for s in spans if s["trace_id"] == r.span.trace_id]
        root = next(s for s in mine if s["name"] == "decode.request")
        assert root["start_mono"] == pytest.approx(r.t_submit, abs=0.05)
        queue = next(s for s in mine if s["name"] == "queue")
        assert queue["duration_ms"] == pytest.approx(
            r.ttft_breakdown["queue_wait"] * 1000.0, abs=1e-6)
        assert queue["start_mono"] == pytest.approx(r.t_submit, abs=0.05)
        assert queue["attributes"]["blocked_by"] == r.blocked_by
        work = [s for s in mine
                if s["name"] in ("prefill_chunk", "decode_block")]
        assert work
        for s in work:
            assert s["start_mono"] >= r.t_submit - 0.05
            assert (s["start_mono"] + s["duration_ms"] / 1000.0
                    <= r.t_done + 0.05)
    assert reqs[1].blocked_by == "lanes"


# ---------------------------------------------------------------------------
# device side: scope names and module names in the lowered programs
# ---------------------------------------------------------------------------

TRAIN_SCOPES = ("embed", "ln", "attn.qkv", "attn.flash", "attn.out", "ffn",
                "head", "loss")
PAGED_SCOPES = ("embed", "ln", "attn.qkv", "attn.paged_write",
                "attn.paged_gather", "attn.paged_softmax", "attn.out",
                "ffn", "head")


@pytest.fixture(scope="module")
def lowered(net):
    """Lowered text (with locations) of the train step, one prefill
    program and one fused decode program, and their module names."""
    import os
    from deeplearning4j_tpu.util.xla import interpret_kernels
    texts = {}
    # the train step at a flash-sized head (the kernel wants d >= 64)
    old = os.environ.get("DL4JTPU_FLASH_ATTENTION")
    os.environ["DL4JTPU_FLASH_ATTENTION"] = "1"
    try:
        with interpret_kernels():
            tnet = ComputationGraph(transformer_lm(
                VOCAB, n_layers=1, d_model=128, n_heads=2, d_ff=32,
                seed=3, input_ids=True)).init()
            step = tnet._make_train_step(None)
            x = jnp.zeros((1, 128, 1), jnp.int32)
            y = jnp.zeros((1, 128), jnp.int32)
            low = step.lower(tnet.params, tnet.updater_state,
                             tnet._states_map(None), [x], [y], None,
                             _rng.key(0), jnp.int32(0))
            texts["train"] = low.as_text(debug_info=True)
    finally:
        if old is None:
            os.environ.pop("DL4JTPU_FLASH_ATTENTION", None)
        else:
            os.environ["DL4JTPU_FLASH_ATTENTION"] = old
    eng = _engine(net, block_len=4)
    eng.warmup()
    for key, fn in eng._jit_cache.items():
        name = key.split("|")[-1]
        if name in ("paged_decode[S1xT4xP8]", "fused_decode[S1xN4xP8]"):
            texts[name] = fn.__wrapped__
    k, v = eng.arena.k_pools, eng.arena.v_pools
    tables = np.full((1, 8), eng.arena.sentinel, np.int32)
    zi, zf = np.zeros(1, np.int32), np.zeros(1, np.float32)
    texts["prefill"] = texts.pop("paged_decode[S1xT4xP8]").lower(
        net.params, k, v, np.zeros((1, 4), np.int32), tables,
        np.full((1, 4), -1, np.int32), zi, zi).as_text(debug_info=True)
    texts["fused"] = texts.pop("fused_decode[S1xN4xP8]").lower(
        net.params, k, v, zi, tables, zi, np.zeros(1, bool), zi,
        np.full(1, -1, np.int32), zf, zi, np.ones(1, np.float32),
        np.zeros((1, 4), np.float32)).as_text(debug_info=True)
    return texts


def _module(text):
    import re
    return re.search(r"module @(\S+)", text).group(1)


@pytest.mark.parametrize("scope", TRAIN_SCOPES)
def test_train_step_carries_scope(lowered, scope):
    assert f"{scope}/" in lowered["train"] or f"{scope})" in lowered["train"]


@pytest.mark.parametrize("scope", PAGED_SCOPES)
def test_prefill_program_carries_scope(lowered, scope):
    assert f"{scope}/" in lowered["prefill"]


@pytest.mark.parametrize("scope", PAGED_SCOPES + ("sample",))
def test_fused_decode_program_carries_scope(lowered, scope):
    assert f"{scope}/" in lowered["fused"]


def test_the_kernel_calls_sit_under_one_scope_only(lowered):
    """XLA names a Mosaic call after the innermost scope around it, and
    the benchmark finds the flash kernels as ``jvp…`` / ``transpose…``:
    so ``attn.flash`` is the only scope between the transform and the
    call (PERF.md section 7)."""
    import re
    calls = re.findall(r'"([^"]*pallas_call[^"]*)"', lowered["train"])
    assert calls
    for loc in calls:
        assert re.search(r"(jvp|transpose)\(.*attn\.flash\)+/pallas_call",
                         loc), loc


def test_module_names_tell_the_programs_apart(lowered):
    assert _module(lowered["train"]) == "jit_ComputationGraph_train_step"
    assert _module(lowered["prefill"]) == "jit_paged_decode_S1_T4_P8"
    assert _module(lowered["fused"]) == "jit_fused_decode_S1_N4_P8"
    assert _module_name("spec_verify[S8xK4xP128]") == "spec_verify_S8_K4_P128"
