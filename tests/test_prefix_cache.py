"""Cross-request KV reuse: prefix caching + int8 KV-page quantization
(ISSUE 19).

Contracts pinned here:

1. **Hit parity**: admissions served from the prefix cache — full hits
   (whole prompt resident, prefill skipped entirely), partial hits
   (shared full-page prefix + private tail), unaligned tails — produce
   EXACTLY the tokens of the single-sequence full-cache oracle
   (``models.transformer.generate``), greedy bit-exact; the fused
   ``lax.while_loop`` block path serves hit lanes with zero new traces
   (retrace pin) and the same fused dispatch count as a miss.
2. **Refcount/CoW invariants**: no page freed while referenced, no
   refcount leak after retire/evict/CoW churn, shared-page eviction
   refused (reclaim only at refcount 0, LRU over unpinned chains),
   admission atomic (retain+reserve or neither), reservations account
   only uncovered pages — except window-overflow sequences, whose
   shared pages may each detach copy-on-write.
3. **Staleness**: a failed dispatch rebuilds the pools AND flushes the
   index (zeroed pools must not serve hits); a model swap flushes too
   (cached K/V belongs to the old params).
4. **int8 quality**: the quantized arena's distributions stay within a
   measured log-prob bound of the dense float oracle, greedy decode
   matches the fp arena token-for-token on the test model (including
   window-sliding evictions, which exercise the scale reset of recycled
   pages), and the quantized pools compose with prefix hits.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer_lm
from deeplearning4j_tpu.models.transformer import (attention_vertices,
                                                   generate,
                                                   oracle_stream_probs,
                                                   paged_decode_forward)
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine)
from deeplearning4j_tpu.serving.kv_cache import (PageAllocator,
                                                 PagedKVArena, PrefixIndex)
from deeplearning4j_tpu.util.metrics import MetricsRegistry
from deeplearning4j_tpu.util.resilience import ManualClock

VOCAB = 11
PS = 8                      # page_size: window = 8 * 4 = 32


def _net(max_cache_t=32, seed=5):
    conf = transformer_lm(VOCAB, n_layers=2, d_model=16, n_heads=2,
                          d_ff=32, seed=seed, input_ids=True,
                          max_cache_t=max_cache_t)
    return ComputationGraph(conf).init()


def _scheduler(net, *, registry=None, **engine_kw):
    registry = registry or MetricsRegistry()
    engine_kw.setdefault("max_batch", 4)
    engine = PagedDecodeEngine(net, page_size=PS, pages_per_seq=4,
                               prefill_chunk=4, registry=registry,
                               **engine_kw)
    return DecodeScheduler(engine, clock=ManualClock(), registry=registry,
                           start_thread=False)


def _run(sched, reqs, limit=500):
    steps = 0
    while not all(r.done for r in reqs) and steps < limit:
        sched.step_once()
        steps += 1
    assert all(r.done for r in reqs), [r.finish_reason for r in reqs]
    return steps


@pytest.fixture(scope="module")
def oracle_net():
    return _net()


@pytest.fixture(scope="module")
def psched(oracle_net):
    """Prefix-cache-enabled scheduler; every test leaves it drained."""
    return _scheduler(oracle_net, prefix_cache=True)


# one shared 2-full-page prompt reused across the parity tests (each
# uses the module scheduler, so later tests hit the cache the earlier
# ones seeded — that is the point)
_RNG = np.random.default_rng(21)
PROMPT16 = _RNG.integers(0, VOCAB, 16).astype(np.int32)


class TestPrefixHitParity:
    def test_miss_then_full_hit_bitexact(self, oracle_net, psched):
        oracle = generate(oracle_net, PROMPT16, 6).tolist()
        first = psched.submit(PROMPT16, 6)
        _run(psched, [first])
        assert first.tokens == oracle
        assert first.prefix_covered_tokens == 0
        idx = psched.engine.arena.prefix_index
        assert idx.cached_pages == 2            # both full pages published
        hit = psched.submit(PROMPT16, 6)
        _run(psched, [hit])
        assert hit.tokens == oracle             # EXACT, not allclose
        assert hit.prefix_covered_tokens == 16  # whole prompt resident
        hits = psched.registry.get("kv_prefix_hits_total")
        assert hits.value(result="full") >= 1
        assert hits.value(result="miss") >= 1
        assert psched.registry.get(
            "kv_prefix_hit_pages_total").value() >= 2

    def test_partial_hit_bitexact(self, oracle_net, psched):
        tail = np.asarray([7, 3, 9, 1, 5], np.int32)
        prompt = np.concatenate([PROMPT16[:8], tail])      # 1 shared page
        oracle = generate(oracle_net, prompt, 5).tolist()
        req = psched.submit(prompt, 5)
        _run(psched, [req])
        assert req.tokens == oracle
        assert req.prefix_covered_tokens == 8   # the aligned shared page
        assert psched.registry.get(
            "kv_prefix_hits_total").value(result="partial") >= 1

    def test_unaligned_tail_reprefills_from_page_boundary(self, oracle_net,
                                                          psched):
        prompt = PROMPT16[:11]                  # 1 full page + 3 tail
        oracle = generate(oracle_net, prompt, 4).tolist()
        r1 = psched.submit(prompt, 4)
        _run(psched, [r1])
        r2 = psched.submit(prompt, 4)
        _run(psched, [r2])
        assert r1.tokens == r2.tokens == oracle
        # sharing is full-page only: the 3-token tail is never cached
        assert r2.prefix_covered_tokens == 8

    def test_short_prompt_never_registers(self, oracle_net, psched):
        idx = psched.engine.arena.prefix_index
        before = idx.cached_pages
        prompt = np.asarray([2, 4, 6], np.int32)           # < page_size
        oracle = generate(oracle_net, prompt, 4).tolist()
        for _ in range(2):
            r = psched.submit(prompt, 4)
            _run(psched, [r])
            assert r.tokens == oracle
            assert r.prefix_covered_tokens == 0
        assert idx.cached_pages == before

    def test_concurrent_hit_lanes_share_pages(self, oracle_net, psched):
        """Two hit admissions decoding at once both reference the cached
        chain (refcount 3: index + 2 lanes) and both stay bit-exact."""
        oracle = generate(oracle_net, PROMPT16, 8).tolist()
        reqs = [psched.submit(PROMPT16, 8) for _ in range(2)]
        for _ in range(3):
            psched.step_once()
        alloc = psched.engine.arena.allocator
        assert not any(r.done for r in reqs)    # genuinely concurrent
        assert alloc.shared_pages >= 2
        assert all(alloc.refcount(p) == 3 for p in
                   psched.engine.arena.prefix_index.lookup(PROMPT16, 4))
        _run(psched, reqs)
        assert all(r.tokens == oracle for r in reqs)
        assert alloc.shared_pages == 0          # only the index holds refs

    def test_long_prompt_that_slides_never_registers(self, oracle_net,
                                                     psched):
        """A prompt longer than the window slides during prefill — its
        leading pages no longer hold the prompt's start, so publishing
        them would poison the index."""
        idx = psched.engine.arena.prefix_index
        before = idx.cached_pages
        prompt = _RNG.integers(0, VOCAB, 40).astype(np.int32)   # > window
        req = psched.submit(prompt, 3)
        _run(psched, [req])
        assert req.finish_reason == "max_tokens"
        assert idx.cached_pages == before


class TestFusedHitPath:
    def test_fused_hit_no_retrace_same_dispatch_count(self, oracle_net):
        """Acceptance: the fused while_loop block path serves hit lanes
        unchanged — zero new traces after warmup, the same number of
        fused dispatches as the miss that seeded the cache, bit-exact
        tokens."""
        reg = MetricsRegistry()
        sched = _scheduler(oracle_net, prefix_cache=True, block_len=4,
                           registry=reg)
        sched.engine.warmup()
        retraces = reg.get("jit_retraces_total")
        series0 = retraces.snapshot()["series"]
        disp = reg.get("decode_dispatches_total")
        oracle = generate(oracle_net, PROMPT16, 8).tolist()

        f0 = disp.value(kind="fused")
        miss = sched.submit(PROMPT16, 8)
        _run(sched, [miss])
        fused_miss = disp.value(kind="fused") - f0

        f0 = disp.value(kind="fused")
        hit = sched.submit(PROMPT16, 8)
        _run(sched, [hit])
        fused_hit = disp.value(kind="fused") - f0

        assert miss.tokens == hit.tokens == oracle
        assert hit.prefix_covered_tokens == 16
        # warmup compiled every shape the hit path needs ([b,1] re-feed
        # included): the whole run added NO traces
        assert retraces.snapshot()["series"] == series0
        assert fused_hit == fused_miss > 0


class TestAdmissionAccounting:
    @pytest.fixture()
    def warm(self, oracle_net):
        """A prefix scheduler with PROMPT16's 2-page chain cached and
        every lane idle."""
        sched = _scheduler(oracle_net, prefix_cache=True)
        req = sched.submit(PROMPT16, 2)
        _run(sched, [req])
        return sched

    def test_hit_reserves_only_uncovered_pages(self, warm):
        eng = warm.engine
        alloc = eng.arena.allocator
        assert alloc.reserved == 0
        # 16 prompt + 6 new = 22 tokens → worst 3 pages, 2 covered
        lane = eng.acquire_lane(22, prompt=PROMPT16)
        assert lane is not None
        assert int(eng._covered[lane]) == 16
        assert alloc.reserved == 1              # only the uncovered page
        assert alloc.shared_pages == 2          # chain pinned by the lane
        # full cover: the feed cursor re-feeds the LAST prompt token
        assert int(eng._pos[lane]) == 15
        eng.release_lane(lane)
        assert alloc.reserved == 0
        assert alloc.shared_pages == 0

    def test_window_overflow_hit_reserves_full_quota(self, warm):
        eng = warm.engine
        alloc = eng.arena.allocator
        # 16 + 40 = 56 tokens → worst 7 > pages_per_seq: every shared
        # page may detach copy-on-write, so the reservation must cover
        # the full quota even though 2 pages are mapped from the cache
        lane = eng.acquire_lane(56, prompt=PROMPT16)
        assert lane is not None
        assert int(eng._covered[lane]) == 16
        assert alloc.reserved == eng.pages_per_seq
        eng.release_lane(lane)
        assert alloc.reserved == 0

    def test_admit_is_atomic(self):
        a = PageAllocator(2, registry=MetricsRegistry())
        assert a.reserve(2)
        p0, p1 = a.draw(), a.draw()
        # need exceeds capacity → the retain must be rolled back
        assert not a.admit(1, [p0])
        assert a.refcount(p0) == 1
        # an unknown page anywhere in the chain rolls back prior retains
        assert not a.admit(0, [p0, 999])
        assert a.refcount(p0) == 1
        assert a.admit(0, [p0])
        assert a.refcount(p0) == 2
        a.free([p0, p0, p1])
        assert a.pages_in_use == 0

    def test_full_cover_admit_fails_when_pin_breaks_invariant(self):
        """need == 0 is not automatically admissible: pinning a cached
        chain removes it from the reclaimable pool, and an outstanding
        reservation may be counting on reclaiming exactly those
        pages."""
        a = PageAllocator(2, registry=MetricsRegistry())
        idx = PrefixIndex(a, page_size=2)
        assert a.reserve(2)
        pages = [a.draw(), a.draw()]
        idx.register([1, 2, 3, 4], pages)
        a.free(pages)                   # only the index holds them now
        assert idx.reclaimable == 2
        assert a.reserve(2)             # covered by reclaiming the chain
        assert not a.admit(0, pages)    # pin would strand the reservation
        assert all(a.refcount(p) == 1 for p in pages)   # rolled back
        assert idx.reclaimable == 2


class TestEvictionOrdering:
    def test_shared_page_eviction_refused_until_last_ref_drops(self):
        a = PageAllocator(2, registry=MetricsRegistry())
        idx = PrefixIndex(a, page_size=2)
        assert a.reserve(1)
        p0 = a.draw()
        idx.register([5, 6], [p0])      # lane + index → refcount 2
        assert a.refcount(p0) == 2
        assert a.reserve(1)
        p1 = a.draw()
        # pool exhausted and the chain is PINNED (the lane still reads
        # it): nothing is reclaimable, admission must refuse
        assert not a.reserve(1)
        a.free([p0])                    # lane retires → unpinned
        assert a.reserve(1)             # now covered by reclaim
        p2 = a.draw()
        assert p2 == p0                 # the chain was evicted for it
        assert idx.cached_pages == 0
        a.free([p1, p2])

    def test_reclaim_is_lru_over_chains(self):
        a = PageAllocator(4, registry=MetricsRegistry())
        idx = PrefixIndex(a, page_size=2)
        assert a.reserve(4)
        pa = [a.draw(), a.draw()]
        pb = [a.draw(), a.draw()]
        idx.register([1, 2, 3, 4], pa)
        idx.register([5, 6, 7, 8], pb)
        a.free(pa + pb)
        idx.lookup([1, 2, 3, 4], 4)     # touch A: B becomes LRU
        assert a.reserve(2)
        drawn = {a.draw(), a.draw()}
        assert drawn == set(pb)         # B evicted leaf-first, A intact
        assert idx.cached_pages == 2
        assert idx.lookup([1, 2, 3, 4], 4) == pa
        a.free(list(drawn))


class TestRefcountChurn:
    @pytest.mark.chaos
    def test_churn_no_leak_no_premature_free(self, oracle_net):
        """Random admit/retire/evict/CoW churn, invariants checked
        mid-flight and at quiescence: reserved <= free + reclaimable
        throughout; afterwards no reservation outstanding, nothing
        shared, and every resident page is exactly an index entry at
        refcount 1."""
        reg = MetricsRegistry()
        sched = _scheduler(oracle_net, prefix_cache=True, registry=reg)
        eng = sched.engine
        alloc, idx = eng.arena.allocator, eng.arena.prefix_index
        rng = np.random.default_rng(3)
        bases = [rng.integers(0, VOCAB, 16).astype(np.int32)
                 for _ in range(3)]
        reqs = []
        for wave in range(6):
            for _ in range(3):
                kind = rng.integers(0, 3)
                if kind == 0:           # exact repeat → full hits
                    prompt = bases[rng.integers(0, 3)]
                elif kind == 1:         # shared prefix + private tail
                    prompt = np.concatenate(
                        [bases[rng.integers(0, 3)][:8],
                         rng.integers(0, VOCAB, 5).astype(np.int32)])
                else:                   # fresh prompt
                    prompt = rng.integers(0, VOCAB, 1 + int(
                        rng.integers(0, 16))).astype(np.int32)
                # some overflow the window → CoW detaches on shared pages
                n_new = int(rng.choice([2, 5, 24]))
                reqs.append(sched.submit(prompt, n_new))
            for _ in range(4):
                sched.step_once()
                with alloc._lock:
                    assert alloc._reserved <= (len(alloc._free)
                                               + idx.reclaimable)
        _run(sched, reqs)
        assert all(r.finish_reason == "max_tokens" for r in reqs)
        assert alloc.reserved == 0
        assert alloc.shared_pages == 0
        # resident pages == cached pages, each held exactly once (the
        # index's own reference), and the page<->entry maps agree
        assert alloc.pages_in_use == idx.cached_pages
        with alloc._lock:
            for e in idx._entries.values():
                assert alloc._refcount[e.page] == 1
                assert idx._bypage[e.page] == e.key
        assert reg.get("kv_pages_cow_total").value() >= 1
        # flushing the index releases the last references
        idx.flush()
        assert alloc.pages_in_use == 0
        assert alloc.available() == alloc.num_pages

    def test_cow_overflow_matches_uncached_engine(self, oracle_net):
        """A hit lane that outgrows the window detaches its shared pages
        instead of recycling them in place — token stream identical to
        the same request on a prefix-off engine, and the cached chain
        survives untouched."""
        reg = MetricsRegistry()
        sched = _scheduler(oracle_net, prefix_cache=True, registry=reg)
        plain = _scheduler(oracle_net)
        seed = sched.submit(PROMPT16, 2)
        _run(sched, [seed])
        cow0 = reg.get("kv_pages_cow_total").value()
        hit = sched.submit(PROMPT16, 24)        # 16 + 24 = 40 > window
        _run(sched, [hit])
        ref = plain.submit(PROMPT16, 24)
        _run(plain, [ref])
        assert hit.prefix_covered_tokens == 16
        assert hit.tokens == ref.tokens
        assert reg.get("kv_pages_cow_total").value() > cow0
        idx = sched.engine.arena.prefix_index
        assert idx.cached_pages == 2            # chain survived the slide
        rehit = sched.submit(PROMPT16, 2)
        _run(sched, [rehit])
        assert rehit.prefix_covered_tokens == 16
        assert rehit.tokens == ref.tokens[:2]

    def test_reclaim_under_page_pressure_end_to_end(self, oracle_net):
        """With the arena sized so cached chains must be reclaimed to
        admit new work, admissions proceed (reserved <= free +
        reclaimable), the LRU chains are sacrificed, and outputs stay
        bit-exact."""
        sched = _scheduler(oracle_net, prefix_cache=True, num_pages=8)
        idx = sched.engine.arena.prefix_index
        seeds = [_RNG.integers(0, VOCAB, 16).astype(np.int32)
                 for _ in range(2)]
        for p in seeds:
            r = sched.submit(p, 2)
            _run(sched, [r])
        assert idx.cached_pages == 4            # the whole arena's half
        prompts = [_RNG.integers(0, VOCAB, 8).astype(np.int32)
                   for _ in range(2)]
        oracle = [generate(oracle_net, p, 24).tolist() for p in prompts]
        reqs = [sched.submit(p, 24) for p in prompts]   # worst 4 pages each
        _run(sched, reqs)
        for o, r in zip(oracle, reqs):
            assert r.tokens == o
        assert idx.cached_pages < 4             # chains were reclaimed


class TestStaleness:
    def test_dispatch_failure_flushes_index(self, oracle_net, monkeypatch):
        """After a failed (donated) dispatch the pools are rebuilt as
        zeros — serving a prefix hit from them would be silent garbage,
        so the index must flush with the rebuild."""
        import deeplearning4j_tpu.models.transformer as T
        sched = _scheduler(oracle_net, prefix_cache=True)
        eng = sched.engine
        seed = sched.submit(PROMPT16, 2)
        _run(sched, [seed])
        assert eng.arena.prefix_index.cached_pages == 2

        def boom(*a, **k):
            raise RuntimeError("device fell over mid-dispatch")
        monkeypatch.setattr(T, "paged_decode_forward", boom)
        # a bucket the seed run did NOT compile, so the dispatch actually
        # reaches the (faulted) traced forward instead of the jit cache
        with pytest.raises(RuntimeError, match="mid-dispatch"):
            eng.run(np.zeros((2, 1), np.int32),
                    np.full((2, 1), -1, np.int32),
                    np.zeros(2, np.int32),
                    np.full((2, eng.pages_per_seq), eng.arena.sentinel,
                            np.int32), np.zeros(2, np.int32))
        monkeypatch.undo()
        assert eng.arena.prefix_index.cached_pages == 0
        assert eng.arena.allocator.pages_in_use == 0
        retry = sched.submit(PROMPT16, 2)
        _run(sched, [retry])
        assert retry.prefix_covered_tokens == 0          # a clean miss
        assert retry.tokens == generate(oracle_net, PROMPT16, 2).tolist()

    def test_swap_net_flushes_index(self, oracle_net):
        sched = _scheduler(oracle_net, prefix_cache=True)
        seed = sched.submit(PROMPT16, 2)
        _run(sched, [seed])
        assert sched.engine.arena.prefix_index.cached_pages == 2
        net2 = _net(seed=7)
        sched.engine.swap_net(net2)
        assert sched.engine.arena.prefix_index.cached_pages == 0
        req = sched.submit(PROMPT16, 4)
        _run(sched, [req])
        assert req.prefix_covered_tokens == 0
        assert req.tokens == generate(net2, PROMPT16, 4).tolist()


class TestInt8Quality:
    @pytest.fixture(scope="class")
    def fp_sched(self, oracle_net):
        return _scheduler(oracle_net)

    @pytest.fixture(scope="class")
    def q8_sched(self, oracle_net):
        return _scheduler(oracle_net, kv_dtype="int8")

    def test_log_prob_bound_vs_dense_oracle(self, oracle_net):
        """The measured quality gate: int8 paged forward vs the dense
        float oracle over a full window, max |Δ log p| within the bound
        PERF.md records, greedy argmax identical."""
        dims = {}
        for name in attention_vertices(oracle_net):
            layer = oracle_net.conf.vertices[name].layer
            dims[name] = (layer.n_heads, layer.n_in // layer.n_heads)
        seq = np.random.default_rng(11).integers(
            0, VOCAB, 32).astype(np.int32)
        oracle = oracle_stream_probs(oracle_net, seq)
        q8 = PagedKVArena(dims, num_pages=4, page_size=PS,
                          kv_dtype="int8", with_allocator=False)
        probs, _, _ = paged_decode_forward(
            oracle_net, oracle_net.params, q8.k_pools, q8.v_pools,
            seq[None], np.arange(4, dtype=np.int32)[None],
            np.arange(32, dtype=np.int32)[None], np.zeros(1, np.int32))
        probs = np.asarray(probs, np.float64)[0]
        err = np.max(np.abs(np.log(np.maximum(probs, 1e-12))
                            - np.log(np.maximum(oracle, 1e-12))))
        assert err < 0.25, f"int8 log-prob err {err} exceeds the gate"
        assert (np.argmax(probs, axis=-1)
                == np.argmax(oracle, axis=-1)).all()

    def test_greedy_matches_fp_arena(self, fp_sched, q8_sched):
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
                   for n in (3, 9, 16)]
        fp = [fp_sched.submit(p, 6) for p in prompts]
        _run(fp_sched, fp)
        q8 = [q8_sched.submit(p, 6) for p in prompts]
        _run(q8_sched, q8)
        for a, b in zip(fp, q8):
            assert a.tokens == b.tokens

    def test_window_slide_resets_recycled_scales(self, fp_sched, q8_sched):
        """Past-window decode recycles pages; a recycled page's stale
        scale would corrupt the fresh rows' quantization if it were not
        reset — fp and int8 arenas must stay token-identical through the
        slide."""
        prompt = np.random.default_rng(17).integers(
            0, VOCAB, 8).astype(np.int32)
        fp = fp_sched.submit(prompt, 40)        # 48 tokens > window 32
        _run(fp_sched, [fp])
        q8 = q8_sched.submit(prompt, 40)
        _run(q8_sched, [q8])
        assert fp.tokens == q8.tokens

    def test_int8_composes_with_prefix_hits(self, oracle_net, q8_sched):
        sched = _scheduler(oracle_net, prefix_cache=True, kv_dtype="int8")
        ref = q8_sched.submit(PROMPT16, 6)
        _run(q8_sched, [ref])
        miss = sched.submit(PROMPT16, 6)
        _run(sched, [miss])
        hit = sched.submit(PROMPT16, 6)
        _run(sched, [hit])
        assert hit.prefix_covered_tokens == 16
        assert miss.tokens == hit.tokens == ref.tokens
