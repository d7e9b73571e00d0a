"""Continuous batching + paged KV-cache decode (ISSUE 9).

Three contracts pinned here:

1. **Bit-exact parity**: greedy decode through the paged arena — ragged
   prompts, admission mid-flight, retirement every step — produces
   EXACTLY the tokens of the single-sequence full-cache oracle
   (``models.transformer.generate`` over the dense streaming cache). The
   paged gather reassembles the same window the dense cache holds, and
   both paths share the sampling helper, so equality is exact, not
   approximate.
2. **Scheduler policy**, driven deterministically (ManualClock, no
   threads, ``step_once()``): shed-by-reason, per-sequence SLO deadlines,
   page-reservation admission, page-table reuse after free, decode-aware
   drain, chaos via the ``serving.decode_step`` fault seam, and the
   steady-state retrace pin (1 compile per bucket across admissions and
   retirements).
3. **Sliding-window eviction** in the dense streaming path (satellite:
   the old clamp-and-warn became real eviction with global positions,
   plus a strict mode that refuses the overflow host-side).

An open-loop Poisson load test (real threads) is marked ``slow``;
``bench.py::bench_decode`` carries the full A/B vs the wave oracle.
"""

import json
import threading
import urllib.error
import urllib.request
import warnings as _warnings

import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer_lm
from deeplearning4j_tpu.models.transformer import generate
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine,
                                               SchedulerDraining,
                                               SchedulerSaturated)
from deeplearning4j_tpu.serving.kv_cache import PageAllocator
from deeplearning4j_tpu.util.metrics import MetricsRegistry
from deeplearning4j_tpu.util.resilience import ManualClock

VOCAB = 11


def _net(max_cache_t=32, seed=5, n_layers=2):
    conf = transformer_lm(VOCAB, n_layers=n_layers, d_model=16, n_heads=2,
                          d_ff=32, seed=seed, input_ids=True,
                          max_cache_t=max_cache_t)
    return ComputationGraph(conf).init()


def _scheduler(net, *, max_batch=4, page_size=8, pages_per_seq=4,
               prefill_chunk=4, registry=None, clock=None, **kw):
    registry = registry or MetricsRegistry()
    engine = PagedDecodeEngine(net, max_batch=max_batch,
                               page_size=page_size,
                               pages_per_seq=pages_per_seq,
                               prefill_chunk=prefill_chunk,
                               registry=registry)
    return DecodeScheduler(engine, clock=clock or ManualClock(),
                           registry=registry, start_thread=False, **kw)


def _run(sched, reqs, limit=500):
    steps = 0
    while not all(r.done for r in reqs) and steps < limit:
        sched.step_once()
        steps += 1
    assert all(r.done for r in reqs), [r.finish_reason for r in reqs]
    return steps


# module-scoped: one oracle net (its rnn_time_step traces accumulate
# across tests) and one default-config scheduler (its bucket traces
# compile once) — every test that uses them leaves the scheduler fully
# drained, which each asserts via _run()
@pytest.fixture(scope="module")
def oracle_net():
    return _net()


@pytest.fixture(scope="module")
def sched(oracle_net):
    return _scheduler(oracle_net)


class TestPagedParity:
    """Greedy continuous-batched decode == single-sequence full-cache
    decode, token for token (acceptance criterion: bit-exact)."""

    def test_ragged_batch_bitexact_vs_oracle(self, oracle_net, sched):
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
                   for n in (3, 5, 7, 2)]
        n_new = [4, 6, 2, 8]
        oracle = [generate(oracle_net, p, n).tolist()
                  for p, n in zip(prompts, n_new)]
        reqs = [sched.submit(p, n) for p, n in zip(prompts, n_new)]
        _run(sched, reqs)
        for o, r in zip(oracle, reqs):
            assert r.tokens == o          # EXACT, not allclose
        assert all(r.finish_reason == "max_tokens" for r in reqs)

    def test_admission_mid_flight_stays_bitexact(self, oracle_net, sched):
        """Sequences admitted while others are mid-decode do not perturb
        anyone: every lane still reproduces its solo oracle exactly."""
        rng = np.random.default_rng(1)
        p0 = rng.integers(0, VOCAB, 4)
        first = sched.submit(p0, 10)
        for _ in range(3):
            sched.step_once()
        assert not first.done             # genuinely mid-flight
        p1, p2 = rng.integers(0, VOCAB, 6), rng.integers(0, VOCAB, 2)
        later = [sched.submit(p1, 5), sched.submit(p2, 7)]
        _run(sched, [first] + later)
        assert first.tokens == generate(oracle_net, p0, 10).tolist()
        assert later[0].tokens == generate(oracle_net, p1, 5).tolist()
        assert later[1].tokens == generate(oracle_net, p2, 7).tolist()

    def test_multi_chunk_prefill_bitexact(self, oracle_net, sched):
        """A prompt longer than prefill_chunk (4 here) prefills over
        several interleaved chunks and still matches the oracle
        exactly."""
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, VOCAB, 19)     # 5 chunks of 4
        req = sched.submit(prompt, 6)
        _run(sched, [req])
        assert req.tokens == generate(oracle_net, prompt, 6).tolist()

    def test_eos_retires_like_oracle(self, oracle_net, sched):
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, VOCAB, 4)
        free_run = generate(oracle_net, prompt, 8)
        eos = int(free_run[2])                  # a token it actually emits
        oracle = generate(oracle_net, prompt, 8, eos_id=eos)
        req = sched.submit(prompt, 8, eos_id=eos)
        _run(sched, [req])
        assert req.tokens == oracle.tolist()
        assert req.finish_reason == "eos"
        assert len(req.tokens) < 8

    def test_page_table_reuse_after_free(self, oracle_net):
        """Retired sequences return pages to the free list; later
        sequences decode correctly on the recycled (stale-content) pages
        and the arena ends empty."""
        rng = np.random.default_rng(4)
        # a 4-page arena and 2-page reservations per sequence: wave 2
        # MUST reuse wave 1's physical pages
        engine = PagedDecodeEngine(oracle_net, max_batch=2, page_size=8,
                                   pages_per_seq=4, num_pages=4,
                                   prefill_chunk=8,
                                   registry=MetricsRegistry())
        sched = DecodeScheduler(engine, clock=ManualClock(),
                                registry=engine.registry,
                                start_thread=False)
        alloc = sched.engine.arena.allocator
        assert alloc.num_pages == 4
        prompts = [rng.integers(0, VOCAB, n) for n in (5, 3, 6, 4)]
        reqs = [sched.submit(p, 5) for p in prompts]
        _run(sched, reqs)
        for p, r in zip(prompts, reqs):
            assert r.tokens == generate(oracle_net, p, 5).tolist()
        assert alloc.pages_in_use == 0
        assert alloc.reserved == 0
        assert sched.engine.lanes_free() == 2

    def test_long_generation_evicts_pages(self):
        """Generation far past the window slides by page eviction and
        still produces max_new_tokens (positions stay global). No oracle
        comparison here ON PURPOSE: past the window the arena evicts a
        page at a time while the dense oracle slides per token, so the
        two are only window-equivalent, not bit-equal (the scoped
        parity contract in serving/decode.py's docstring)."""
        reg = MetricsRegistry()
        net = _net(max_cache_t=16, n_layers=1)
        sched = _scheduler(net, max_batch=2, page_size=8, pages_per_seq=2,
                           prefill_chunk=8, registry=reg)
        req = sched.submit(np.arange(5) % VOCAB, 40)
        _run(sched, [req])
        assert len(req.tokens) == 40
        assert req.finish_reason == "max_tokens"
        assert reg.get("kv_pages_evicted_total").value() > 0
        assert sched.engine.arena.allocator.pages_in_use == 0

    def test_generate_handles_prompt_longer_than_window(self):
        """The full-cache oracle feeds over-long prompts in window-sized
        chunks (the cache slides) instead of tripping the chunk guard."""
        net = _net(max_cache_t=8, n_layers=1)
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")        # overflow warning
            out = generate(net, np.arange(20) % VOCAB, 4)
        assert len(out) == 4
        assert all(0 <= t < VOCAB for t in out)

    def test_temperature_sampling_reproducible(self, sched):
        """temperature>0 samples through the shared helper with the
        request's seeded rng — same seed, same tokens."""
        prompt = [1, 2, 3]
        outs = []
        for _ in range(2):
            req = sched.submit(prompt, 6, temperature=0.8, seed=42)
            _run(sched, [req])
            outs.append(req.tokens)
        assert outs[0] == outs[1]
        assert all(0 <= t < VOCAB for t in outs[0])


class TestSchedulerPolicy:
    """Deterministic-clock scheduler unit tests — no threads, no sleeps."""

    def test_queue_full_sheds_with_reason(self, oracle_net):
        reg = MetricsRegistry()
        sched = _scheduler(oracle_net, registry=reg, max_queue=1)
        sched.submit([1, 2], 4)
        with pytest.raises(SchedulerSaturated):
            sched.submit([3, 4], 4)
        assert reg.get("serving_shed_total").value(
            reason="decode_queue_full") == 1

    def test_deadline_expiry_mid_decode_returns_partial(self, oracle_net):
        clock = ManualClock()
        sched = _scheduler(oracle_net, clock=clock)
        req = sched.submit([1, 2, 3], 50, timeout_s=5.0)
        for _ in range(4):
            sched.step_once()
        got = len(req.tokens)
        assert 0 < got < 50
        clock.advance(10.0)                     # SLO blown mid-flight
        sched.step_once()
        assert req.done and req.finish_reason == "deadline"
        assert len(req.tokens) == got           # partial output preserved
        assert sched.engine.arena.allocator.pages_in_use == 0

    def test_deadline_expiry_in_queue(self, oracle_net):
        """A request whose deadline passes while QUEUED is answered
        without ever costing a lane or a dispatch."""
        clock = ManualClock()
        reg = MetricsRegistry()
        # 1 lane: the second request must wait in queue
        sched = _scheduler(oracle_net, max_batch=1, pages_per_seq=4,
                           registry=reg, clock=clock)
        hog = sched.submit([1], 60, timeout_s=120.0)
        sched.step_once()                       # hog admitted + decoding
        waiter = sched.submit([2], 4, timeout_s=5.0)
        clock.advance(10.0)
        sched.step_once()
        assert waiter.done and waiter.finish_reason == "deadline"
        assert waiter.tokens == []
        assert not hog.done                     # hog unaffected
        assert reg.get("decode_retired_total").value(reason="deadline") == 1

    def test_admission_waits_on_page_pressure(self, oracle_net):
        """When the arena cannot reserve a new sequence's worst case, the
        request stays queued (not shed) and admits after a retirement."""
        net = oracle_net
        # 2 lanes but an arena of only 4 pages: a's 3-page reservation +
        # b's 2 exceed it → one sequence at a time
        reg = MetricsRegistry()
        engine = PagedDecodeEngine(net, max_batch=2, page_size=8,
                                   pages_per_seq=4, num_pages=4,
                                   prefill_chunk=8, registry=reg)
        sched = DecodeScheduler(engine, clock=ManualClock(), registry=reg,
                                start_thread=False)
        a = sched.submit([1, 2, 3], 18)         # 21 tokens → 3 pages
        b = sched.submit([4, 5, 6], 8)          # 11 tokens → 2 pages
        sched.step_once()
        assert sched.active_count() == 1 and sched.queue_depth() == 1
        _run(sched, [a, b])
        assert a.tokens == generate(net, [1, 2, 3], 18).tolist()
        assert b.tokens == generate(net, [4, 5, 6], 8).tolist()

    def test_drain_finishes_in_flight_then_refuses(self, oracle_net):
        sched = _scheduler(oracle_net)
        req = sched.submit([1, 2], 6)
        sched.step_once()
        assert sched.drain(timeout=30.0)        # steps inline (no thread)
        assert req.done and req.finish_reason == "max_tokens"
        with pytest.raises(SchedulerDraining):
            sched.submit([1], 2)

    def test_stop_fails_remaining_work(self, oracle_net):
        sched = _scheduler(oracle_net)
        running = sched.submit([1, 2], 50)
        sched.step_once()
        queued = None
        # fill every lane so this one stays queued
        for _ in range(5):
            queued = sched.submit([3], 50)
        sched.stop()
        assert running.finish_reason == "shutdown"
        assert queued.finish_reason == "shutdown"
        assert sched.engine.arena.allocator.pages_in_use == 0

    @pytest.mark.chaos
    def test_faultplan_decode_step_outage(self, oracle_net):
        """A scripted fault at the serving.decode_step seam fails the
        in-flight batch with finish_reason="error", frees its pages, and
        the scheduler keeps serving the next request cleanly."""
        from deeplearning4j_tpu.util import faults
        net = oracle_net
        sched = _scheduler(net)
        victim = sched.submit([1, 2, 3], 6)
        plan = faults.FaultPlan().fail_at(
            "serving.decode_step", call=2,
            exc=RuntimeError("chip fell over"))
        with plan.active():
            _run(sched, [victim])
            assert victim.finish_reason == "error"
            assert "chip fell over" in victim.error
            assert sched.engine.arena.allocator.pages_in_use == 0
            # same scheduler, next request: clean, and still bit-exact
            retry = sched.submit([1, 2, 3], 6)
            _run(sched, [retry])
        assert retry.finish_reason == "max_tokens"
        assert retry.tokens == generate(net, [1, 2, 3], 6).tolist()
        assert plan.triggered == [("serving.decode_step", 2)]

    @pytest.mark.chaos
    def test_dispatch_failure_resets_donated_pools(self, oracle_net,
                                                   monkeypatch):
        """The pools are DONATED into every dispatch — after a failed one
        the arena is rebuilt (zeros, same shapes) and the next request
        decodes bit-exact on it."""
        import deeplearning4j_tpu.models.transformer as T
        sched = _scheduler(oracle_net)
        eng = sched.engine
        shapes = [tuple(p.shape) for p in eng.arena.k_pools]

        def boom(*a, **k):
            raise RuntimeError("device fell over mid-dispatch")
        monkeypatch.setattr(T, "paged_decode_forward", boom)
        with pytest.raises(RuntimeError, match="mid-dispatch"):
            eng.run(np.zeros((1, 1), np.int32),
                    np.full((1, 1), -1, np.int32),
                    np.zeros(1, np.int32),
                    np.full((1, eng.pages_per_seq), eng.arena.sentinel,
                            np.int32), np.zeros(1, np.int32))
        assert [tuple(p.shape) for p in eng.arena.k_pools] == shapes
        monkeypatch.undo()
        req = sched.submit([1, 2], 3)
        _run(sched, [req])
        assert req.tokens == generate(oracle_net, [1, 2], 3).tolist()

    def test_retrace_pin_one_compile_per_bucket(self):
        """Steady-state acceptance: admissions and retirements across
        many ticks compile exactly ONE program per (lane-bucket, chunk)
        — jit_retraces_total pinned at 1 per bucket, and the bucket set
        is the fixed power-of-two ladder, never per-occupancy shapes."""
        reg = MetricsRegistry()
        sched = _scheduler(_net(), registry=reg)
        rng = np.random.default_rng(9)
        reqs = []
        for wave in range(3):                   # churn: 3 waves of 3
            reqs += [sched.submit(rng.integers(0, VOCAB, 1 + wave + i), 3 + i)
                     for i in range(3)]
            for _ in range(4):
                sched.step_once()
        _run(sched, reqs)
        counter = reg.get("jit_retraces_total")
        series = counter.snapshot()["series"]
        assert all(s["value"] == 1 for s in series), series
        names = {s["labels"]["fn"] for s in series}
        assert any("T1x" in n for n in names)       # decode buckets
        assert any("T4x" in n for n in names)       # prefill buckets
        # power-of-two lane buckets only (1/2/4), bounded by max_batch=4
        assert names <= {f"paged_decode[S{b}xT{t}xP4]"
                         for b in (1, 2, 4) for t in (1, 4)}, names

    def test_decode_metrics_populated(self, oracle_net):
        reg = MetricsRegistry()
        sched = _scheduler(oracle_net, registry=reg)
        req = sched.submit([1, 2, 3], 6)
        _run(sched, [req])
        assert reg.get("decode_admitted_total").value() == 1
        assert reg.get("decode_retired_total").value(
            reason="max_tokens") == 1
        assert reg.get("decode_steps_total").value() > 0
        assert reg.get("decode_tokens_total").value(phase="decode") == 5
        assert reg.get("decode_tokens_total").value(phase="prefill") == 3
        assert reg.get("decode_batch_occupancy").count() > 0
        assert reg.get("decode_ttft_seconds").count() == 1
        # exposition carries the whole decode pane
        text = reg.expose()
        for name in ("decode_batch_occupancy", "kv_pages_in_use",
                     "decode_retired_total", "decode_ttft_seconds"):
            assert name in text


class TestPageAllocator:
    def test_reserve_draw_free_invariants(self):
        reg = MetricsRegistry()
        a = PageAllocator(4, registry=reg)
        assert a.available() == 4
        assert a.reserve(3)
        assert not a.reserve(2)                 # only 1 unreserved left
        p1, p2 = a.draw(), a.draw()
        assert {p1, p2} <= {0, 1, 2, 3} and p1 != p2
        assert a.pages_in_use == 2
        a.unreserve(1)
        with pytest.raises(RuntimeError):
            a.draw()                            # reservation exhausted
        a.free([p1, p2])
        assert a.pages_in_use == 0
        assert a.available() == 4
        with pytest.raises(ValueError):
            a.unreserve(1)

    def test_reuse_is_fifo(self):
        a = PageAllocator(2)
        assert a.reserve(2)
        first = a.draw()
        a.free([first])
        assert a.reserve(1)
        second = a.draw()
        third = a.draw()
        assert third == first                   # recycled after the fresh page
        assert second != first


class TestStreamingEviction:
    """Satellite: dense streaming overflow is sliding-window eviction
    (positions stay global), with a strict mode that raises host-side."""

    def _mln(self, max_cache_t, overflow="evict"):
        from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
        from deeplearning4j_tpu.nn.conf.builders import \
            NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import (LayerNormalization,
                                                       RnnOutputLayer)
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(
            (NeuralNetConfiguration.builder().seed(3).updater("sgd")
             .learning_rate(0.1).list()
             .layer(LayerNormalization())
             .layer(SelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                       causal=True,
                                       max_cache_t=max_cache_t,
                                       cache_overflow=overflow))
             .layer(RnnOutputLayer(n_out=5, activation="softmax",
                                   loss="mcxent"))
             .set_input_type(InputType.recurrent(8)).build())).init()

    def test_window_decode_matches_truncated_full_forward(self, rng):
        """Token-by-token decode past the window equals the full forward
        over exactly the last W tokens — REAL eviction semantics, not
        the old tail-overwrite clamp (which desynced positions)."""
        W, T = 4, 10
        net = self._mln(W)
        x = rng.normal(size=(2, T, 8)).astype(np.float32)
        steps = []
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            for t in range(T):
                steps.append(np.asarray(net.rnn_time_step(x[:, t])))
        for t in range(T):
            lo = max(0, t - W + 1)
            ref = np.asarray(net.output(x[:, lo:t + 1]))[:, -1]
            np.testing.assert_allclose(steps[t], ref, rtol=1e-4,
                                       atol=1e-5)

    def test_chunked_overflow_evicts_whole_chunks(self, rng):
        """Multi-step chunks evict in one shift: after overflow, the
        last chunk's final output equals the truncated full forward."""
        net = self._mln(4)
        x = rng.normal(size=(2, 6, 8)).astype(np.float32)
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            net.rnn_time_step(x[:, 0:2])
            net.rnn_time_step(x[:, 2:4])
            out = np.asarray(net.rnn_time_step(x[:, 4:6]))
        ref = np.asarray(net.output(x[:, 2:6]))[:, -1]
        np.testing.assert_allclose(out[:, -1], ref, rtol=1e-4, atol=1e-5)

    def test_overflow_still_warns_once(self, rng):
        """The host-side overflow warning survives the semantics change
        (it now announces the sliding window)."""
        net = self._mln(4)
        x = rng.normal(size=(2, 3, 8)).astype(np.float32)
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            net.rnn_time_step(x)
        with pytest.warns(RuntimeWarning, match="max_cache_t"):
            net.rnn_time_step(x)
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")      # once, not per call
            net.rnn_time_step(x[:, :1])

    def test_strict_mode_raises_before_dispatch(self, rng):
        from deeplearning4j_tpu.util.netutil import StreamingCacheOverflow
        net = self._mln(4, overflow="strict")
        x = rng.normal(size=(2, 3, 8)).astype(np.float32)
        net.rnn_time_step(x)                    # 3 of 4: fine
        with pytest.raises(StreamingCacheOverflow, match="max_cache_t=4"):
            net.rnn_time_step(x)
        # the cache was left untouched: the tally did not move and a
        # fitting chunk still decodes
        assert net._rnn_steps_fed == 3
        net.rnn_time_step(x[:, :1])
        assert net._rnn_steps_fed == 4
        net.rnn_clear_previous_state()
        net.rnn_time_step(x)                    # fresh window

    def test_strict_mode_on_graph_runtime(self):
        from deeplearning4j_tpu.util.netutil import StreamingCacheOverflow
        conf = transformer_lm(7, n_layers=1, d_model=16, n_heads=2,
                              d_ff=32, seed=4, input_ids=True,
                              max_cache_t=4)
        for v in conf.vertices.values():
            layer = getattr(v, "layer", None)
            if layer is not None and hasattr(layer, "cache_overflow"):
                layer.cache_overflow = "strict"
        net = ComputationGraph(conf).init()
        ids = np.zeros((1, 3, 1), np.int32)
        net.rnn_time_step(ids)
        with pytest.raises(StreamingCacheOverflow):
            net.rnn_time_step(ids)

    def test_bad_overflow_value_rejected(self):
        from deeplearning4j_tpu import dtypes as _dtypes
        from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
        layer = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2, causal=True,
                                   max_cache_t=8, cache_overflow="wat")
        with pytest.raises(ValueError, match="cache_overflow"):
            layer._zero_state(2, _dtypes.default_policy())


class TestPartialTableEviction:
    """Eviction while the page table still has sentinel holes (reachable
    whenever prefill_chunk > page_size): the live prefix must stay
    contiguous — rotating the full row used to smear a hole into the
    middle and silently drop the chunk's K/V writes."""

    def _engine(self):
        # max_cache_t=None: the engine window (4×3=12) is the only
        # window in play
        net = ComputationGraph(transformer_lm(
            VOCAB, n_layers=1, d_model=16, n_heads=2, d_ff=32, seed=6,
            input_ids=True)).init()
        return PagedDecodeEngine(net, max_batch=1, page_size=4,
                                 pages_per_seq=3, prefill_chunk=8,
                                 registry=MetricsRegistry())

    def test_live_table_prefix_stays_contiguous(self):
        eng = self._engine()
        lane, t = eng.acquire_lane(16), eng.lane_tables
        t.ensure(lane, 8)                       # fills pages 0,1 of 3
        t.advance(lane, 8)
        t.ensure(lane, 8)                       # evicts with a hole left
        held = t.held[lane]
        live = t.tables[lane][:len(held)]
        assert (live != eng.arena.sentinel).all(), t.tables[lane]
        assert sorted(live.tolist()) == sorted(held)
        # every slot the pending chunk writes maps to a REAL page
        rel = t.rel_pos(lane)
        for s in range(rel, rel + 8):
            assert t.tables[lane][s // 4] != eng.arena.sentinel, s

    def test_long_prompt_through_scheduler_stays_deterministic(self):
        eng = self._engine()
        sched = DecodeScheduler(eng, clock=ManualClock(),
                                registry=eng.registry, start_thread=False)
        prompt = (np.arange(16) * 3) % VOCAB
        req = sched.submit(prompt, 4)
        _run(sched, [req])
        assert req.finish_reason == "max_tokens"
        assert len(req.tokens) == 4
        assert eng.registry.get("kv_pages_evicted_total").value() > 0
        # a second identical request over recycled pages reproduces it
        eng2 = self._engine()
        sched2 = DecodeScheduler(eng2, clock=ManualClock(),
                                 registry=eng2.registry,
                                 start_thread=False)
        rerun = sched2.submit(prompt, 4)
        _run(sched2, [rerun])
        assert rerun.tokens == req.tokens


class TestEngineValidation:
    def test_rejects_one_hot_input_net(self):
        net = ComputationGraph(transformer_lm(
            7, n_layers=1, d_model=16, n_heads=2, d_ff=32,
            max_cache_t=8)).init()              # input_ids=False
        with pytest.raises(ValueError, match="input_ids"):
            PagedDecodeEngine(net, registry=MetricsRegistry())

    def test_rejects_strict_overflow_and_window_mismatch(self):
        strict = transformer_lm(VOCAB, n_layers=1, d_model=16, n_heads=2,
                                d_ff=32, input_ids=True, max_cache_t=32)
        for v in strict.vertices.values():
            layer = getattr(v, "layer", None)
            if layer is not None and hasattr(layer, "cache_overflow"):
                layer.cache_overflow = "strict"
        with pytest.raises(ValueError, match="strict"):
            PagedDecodeEngine(ComputationGraph(strict).init(),
                              page_size=8, pages_per_seq=4,
                              registry=MetricsRegistry())
        mismatched = _net(max_cache_t=32)
        with pytest.raises(ValueError, match="window"):
            # window 8×8=64 != the net's declared 32-token cache
            PagedDecodeEngine(mismatched, page_size=8, pages_per_seq=8,
                              registry=MetricsRegistry())

    def test_rejects_recurrent_state_net(self):
        from deeplearning4j_tpu.models.char_rnn import char_rnn_lstm
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        net = MultiLayerNetwork(char_rnn_lstm(7, hidden=8, layers=1,
                                              tbptt_length=4)).init()
        with pytest.raises(ValueError, match="ComputationGraph"):
            PagedDecodeEngine(net, registry=MetricsRegistry())

    def test_swap_net_checks_topology_and_fence(self):
        net = _net(seed=5)
        other_shape = ComputationGraph(transformer_lm(
            VOCAB, n_layers=1, d_model=16, n_heads=2, d_ff=32, seed=5,
            input_ids=True, max_cache_t=32)).init()
        sched = _scheduler(net)
        with pytest.raises(ValueError, match="topology"):
            sched.engine.swap_net(other_shape)
        # compatible swap at an idle fence changes the served weights
        swapped = _net(seed=99)
        with sched.fence() as active:
            assert active == 0
            sched.engine.swap_net(swapped)
        req = sched.submit([1, 2, 3], 4)
        _run(sched, [req])
        assert req.tokens == generate(swapped, [1, 2, 3], 4).tolist()


class TestServingGenerateHTTP:
    """The /generate endpoint end to end: continuous-batched responses
    bit-exact vs the oracle, decode-aware drain, fenced model swap."""

    @staticmethod
    def _make_server(net, **decode_kw):
        from deeplearning4j_tpu.serving import InferenceServer
        from deeplearning4j_tpu.util.tracing import Tracer
        cfg = {"max_batch": 4, "page_size": 8, "pages_per_seq": 4,
               "prefill_chunk": 4}
        cfg.update(decode_kw)
        return InferenceServer(net, port=0, decode=cfg, tracer=Tracer())

    @pytest.fixture(scope="class")
    def server(self, oracle_net):
        server = self._make_server(oracle_net)
        yield server
        server.stop(drain=False)

    @staticmethod
    def _post(base, path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def test_generate_endpoint_matches_oracle(self, oracle_net, server):
        base = f"http://127.0.0.1:{server.port}"
        out = self._post(base, "/generate",
                         {"prompt_ids": [1, 2, 3, 4],
                          "max_new_tokens": 6})
        assert out["tokens"] == generate(oracle_net,
                                         [1, 2, 3, 4], 6).tolist()
        assert out["finish_reason"] == "max_tokens"
        assert out["n_generated"] == 6
        assert out["ttft_ms"] >= 0
        health = json.loads(urllib.request.urlopen(
            base + "/healthz", timeout=5).read())
        assert health["decode"]["active"] == 0
        assert health["decode"]["queued"] == 0
        # served engines default prefix_cache=True → the healthz pane
        # carries the prefix-cache observability block (ISSUE 19)
        pane = health["decode"]["prefix_cache"]
        assert pane["misses"] >= 1              # the 4-token prompt above
        assert pane["cached_pages"] == 0        # sub-page prompt: nothing
        #                                         full-page to publish
        metrics = urllib.request.urlopen(
            base + "/metrics", timeout=5).read().decode()
        assert "decode_batch_occupancy" in metrics
        assert "kv_pages_in_use" in metrics
        assert "decode_goodput_tokens_total" in metrics

    def test_traceparent_propagates_and_timeline_served(self, server):
        """ISSUE 13 tentpole (HTTP leg): an incoming traceparent parents
        the request's decode spans, the response carries the request
        root's context, and /debug/timeline renders the request's
        nested span tree."""
        base = f"http://127.0.0.1:{server.port}"
        client_trace, client_span = "ab" * 16, "cd" * 8
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt_ids": [1, 2, 3],
                             "max_new_tokens": 4}).encode(),
            method="POST",
            headers={"Content-Type": "application/json",
                     "traceparent":
                         f"00-{client_trace}-{client_span}-01"})
        with urllib.request.urlopen(req, timeout=60) as r:
            body = json.loads(r.read())
            header_out = r.headers.get("traceparent")
        assert body["trace_id"] == client_trace
        assert header_out is not None
        assert header_out.split("-")[1] == client_trace
        tl = json.loads(urllib.request.urlopen(
            base + f"/debug/timeline?trace_id={client_trace}",
            timeout=10).read())
        assert len(tl["requests"]) == 1
        root = tl["requests"][0]["spans"]
        assert root["name"] == "decode.request"
        assert root["parent_id"] == client_span
        child_names = {c["name"] for c in root["children"]}
        assert {"queue", "prefill_chunk", "decode_block"} <= child_names
        attrs = tl["requests"][0]["attributes"]
        assert attrs["finish_reason"] == "max_tokens"
        assert attrs["tokens"] == 4
        assert set(attrs["ttft_breakdown_ms"]) == \
            {"queue_wait", "prefill", "compile", "dispatch"}

    def test_concurrent_generates_continuously_batched(self, oracle_net,
                                                       server):
        base = f"http://127.0.0.1:{server.port}"
        prompts = [[i + 1, i + 2] for i in range(4)]
        results = [None] * 4

        def call(i):
            results[i] = self._post(base, "/generate",
                                    {"prompt_ids": prompts[i],
                                     "max_new_tokens": 3 + i})
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i in range(4):
            ref = generate(oracle_net, prompts[i], 3 + i).tolist()
            assert results[i]["tokens"] == ref, i

    @pytest.mark.chaos
    def test_drain_waits_for_in_flight_decode_and_swap_is_fenced(
            self, oracle_net, tmp_path):
        """Satellite: drain() reports clean only after in-flight
        generative sequences finish; a mid-decode model swap is refused
        (409 over HTTP — a retriable conflict, not a bad request), and
        allowed at the post-drain step boundary."""
        import time
        from deeplearning4j_tpu.util.serialization import save_model
        server = self._make_server(oracle_net)
        base = f"http://127.0.0.1:{server.port}"
        swap_zip = str(tmp_path / "swap.zip")
        # built BEFORE the long call starts: building a net takes longer
        # than 600 tiny decode steps, so doing it mid-decode loses the race
        swap_net = _net(seed=99)
        save_model(swap_net, swap_zip)
        done = {}

        def long_call():
            # long enough that the HTTP /model round-trip below lands
            # while this is still decoding
            done["r"] = self._post(base, "/generate",
                                   {"prompt_ids": [1],
                                    "max_new_tokens": 600,
                                    "timeout_s": 120})
        t = threading.Thread(target=long_call)
        t.start()
        try:
            for _ in range(400):
                if server.decode.active_count() > 0:
                    break
                time.sleep(0.005)
            assert server.decode.active_count() == 1
            with pytest.raises(RuntimeError, match="in flight"):
                server.set_model(swap_net)
            # over HTTP the refusal is a retriable 409, not a 400
            try:
                self._post(base, "/model", {"path": swap_zip})
                assert False, "mid-decode POST /model was not refused"
            except urllib.error.HTTPError as e:
                assert e.code == 409
                assert "Retry-After" in dict(e.headers)
            assert server.drain(timeout=120)
            t.join(timeout=60)
            assert done["r"]["finish_reason"] == "max_tokens"
            assert len(done["r"]["tokens"]) == 600
            assert server.decode.active_count() == 0
            # step boundary reached: the swap now goes through
            server.set_model(swap_net)
            # and a draining server sheds new generates with 503
            try:
                self._post(base, "/generate", {"prompt_ids": [1]})
                assert False, "draining server accepted a generate"
            except urllib.error.HTTPError as e:
                assert e.code == 503
        finally:
            t.join(timeout=10)
            server.stop(drain=False)


class TestRequestTimelines:
    """ISSUE 13: per-request decode timelines — root span per request,
    TTFT decomposition that sums to the measured TTFT, goodput split,
    and the TTFT-from-submit audit (satellite: the histogram must
    include queue wait, not start at admission)."""

    @pytest.fixture(scope="class")
    def traced(self, oracle_net):
        """Real-clock scheduler with a tracer: the decomposition mixes
        the scheduler clock with dispatch walls, so a clock that
        actually advances is part of what is under test."""
        from deeplearning4j_tpu.util.tracing import Tracer
        registry = MetricsRegistry()
        tracer = Tracer()
        engine = PagedDecodeEngine(_net(), max_batch=4, page_size=8,
                                   pages_per_seq=4, prefill_chunk=4,
                                   registry=registry)
        sched = DecodeScheduler(engine, registry=registry,
                                start_thread=False, tracer=tracer)
        rng = np.random.default_rng(21)
        reqs = [sched.submit(rng.integers(0, VOCAB, 5), 6,
                             trace_ctx="00-" + "ab" * 16 + "-"
                                       + "cd" * 8 + "-01"),
                sched.submit(rng.integers(0, VOCAB, 3), 4)]
        _run(sched, reqs)
        return sched, tracer, reqs

    def test_breakdown_sums_to_measured_ttft(self, traced):
        """Acceptance: queue_wait + prefill + compile + dispatch == the
        measured TTFT within 5% (exact by construction here)."""
        _sched, _tracer, reqs = traced
        for r in reqs:
            ttft = r.t_first_token - r.t_submit
            bd = r.ttft_breakdown
            assert set(bd) == {"queue_wait", "prefill", "compile",
                               "dispatch"}
            assert all(v >= 0 for v in bd.values())
            assert abs(sum(bd.values()) - ttft) <= 0.05 * ttft
            assert bd["prefill"] > 0
        # no warmup() was called, so the first request's prefill tick
        # paid the bucket compile — the decomposition must attribute it
        assert reqs[0].ttft_breakdown["compile"] > 0

    def test_span_tree_and_remote_parenting(self, traced):
        from deeplearning4j_tpu.util import timeline
        _sched, tracer, reqs = traced
        timelines = timeline.request_timelines(tracer)
        assert len(timelines) == 2
        by_trace = {t["trace_id"]: t for t in timelines}
        # the trace_ctx request joined the caller's trace, parented on
        # the caller's span
        remote = by_trace["ab" * 16]
        assert remote["spans"]["parent_id"] == "cd" * 8
        for t in timelines:
            root = t["spans"]
            assert root["name"] == "decode.request"
            kids = root["children"]
            assert [k["name"] for k in kids][0] == "queue"
            blocks = [k for k in kids if k["name"] == "decode_block"]
            assert blocks, "no per-block child spans"
            for b in blocks:
                a = b["attributes"]
                assert a["kind"] == "ticked"
                assert 0 <= a["lane"] < 4
                assert a["bucket"] in (1, 2, 4)
                assert a["tokens"] == 1
            # the FIRST token falls out of the last prefill chunk (TTFT
            # lands there); decode blocks account for all the rest
            total = sum(b["attributes"]["tokens"] for b in blocks)
            assert total == t["attributes"]["tokens"] - 1
        # spans carry process provenance for cross-process merges
        assert all(s.host and s.pid for s in tracer.finished)

    def test_ttft_measured_from_submit_includes_queue_wait(
            self, oracle_net, sched):
        """Satellite audit: TTFT (histogram AND decomposition) starts at
        submit(), not at admission — a queued request's wait shows up in
        both, and the queue_wait component pins the histogram's view."""
        clock = sched.clock
        rng = np.random.default_rng(31)
        hist = sched.registry.get("decode_ttft_seconds")
        n0, s0 = hist.count(), hist.sum()
        # saturate all 4 lanes so the 5th request must queue
        occupants = [sched.submit(rng.integers(0, VOCAB, 3), 8)
                     for _ in range(4)]
        sched.step_once()                   # admits the 4 occupants
        queued = sched.submit(rng.integers(0, VOCAB, 3), 3)
        sched.step_once()
        assert queued.t_admit is None       # provably still queued
        clock.advance(0.5)                  # queue wait under a clock
        _run(sched, occupants + [queued])
        ttft = queued.t_first_token - queued.t_submit
        assert ttft >= 0.5, "TTFT missed the queue wait"
        bd = queued.ttft_breakdown
        assert bd["queue_wait"] >= 0.5
        assert abs(sum(bd.values()) - ttft) < 1e-6
        # the histogram observed the same submit-anchored values
        assert hist.count() == n0 + 5
        assert hist.sum() - s0 >= 0.5

    def test_goodput_splits_met_vs_missed(self, oracle_net, sched):
        """decode_goodput_tokens_total{slo}: a request that finishes
        within its deadline contributes met tokens; one retired at its
        deadline contributes its served tokens as missed."""
        clock = sched.clock
        rng = np.random.default_rng(41)
        ctr = sched.registry.get("decode_goodput_tokens_total")
        met0 = ctr.value(slo="met")
        missed0 = ctr.value(slo="missed")
        ok = sched.submit(rng.integers(0, VOCAB, 3), 5)
        _run(sched, [ok])
        assert ctr.value(slo="met") == met0 + 5
        slow = sched.submit(rng.integers(0, VOCAB, 3), 50, timeout_s=1.0)
        for _ in range(4):                  # prefill + a few tokens
            sched.step_once()
        served = len(slow.tokens)
        assert 0 < served < 50
        clock.advance(2.0)                  # blow the SLO deadline
        sched.step_once()
        assert slow.finish_reason == "deadline"
        assert ctr.value(slo="missed") == missed0 + served
        assert ctr.value(slo="met") == met0 + 5


@pytest.mark.slow
class TestPoissonOpenLoopLoad:
    """Open-loop Poisson arrivals against the threaded scheduler: every
    request completes, lanes never leak, outputs stay bit-exact. The
    throughput A/B vs the wave-batched oracle lives in
    bench.py::bench_decode."""

    def test_poisson_arrivals_complete_and_match_oracle(self):
        import time
        net = _net()
        reg = MetricsRegistry()
        engine = PagedDecodeEngine(net, max_batch=4, page_size=8,
                                   pages_per_seq=4, prefill_chunk=8,
                                   registry=reg)
        sched = DecodeScheduler(engine, registry=reg, start_thread=True,
                                request_timeout_s=120.0)
        rng = np.random.default_rng(11)
        n = 16
        prompts = [rng.integers(0, VOCAB, int(rng.integers(2, 8)))
                   for _ in range(n)]
        n_new = [int(rng.choice([2, 4, 8, 16])) for _ in range(n)]
        gaps = rng.exponential(0.004, n)
        reqs = []
        try:
            for i in range(n):
                time.sleep(float(gaps[i]))
                reqs.append(sched.submit(prompts[i], n_new[i]))
            deadline = time.monotonic() + 300
            for r in reqs:
                assert r.wait(timeout=max(1.0, deadline - time.monotonic()))
            for p, k, r in zip(prompts, n_new, reqs):
                assert r.finish_reason == "max_tokens"
                assert r.tokens == generate(net, p, k).tolist()
            assert engine.arena.allocator.pages_in_use == 0
            occ = reg.get("decode_batch_occupancy")
            assert occ.count() > 0
        finally:
            sched.stop()
