"""The paged read (``ops/paged_attention.paged_read_attention``): the
chunked, bounded read against the full-window gather + masked softmax it
replaced, the trip count on host and device, the inner ``while`` in the
lowered fused block, and the two counters that say how much of the window
a dispatch visits (``paged_read_window_share`` of the benchmark), and
the rounding of the gathered chunk where the products would round K/V
(``paged_read_chunk_rounded_share``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import nemotron_h_lm
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.ops.paged_attention import (
    READ_CHUNK_TOKENS, paged_gather, paged_read_attention, paged_write,
    read_chunk_pages, read_rounds_chunk, read_trip_count)
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine)

H, D = 2, 8


def full_window_read(q, k_pool, v_pool, page_table, rel_pos, scale):
    """What ``apply_paged`` did before the bounded read: gather every
    lane's whole table, then mask (kept here as the test's reference)."""
    k_view = paged_gather(k_pool, page_table, H)        # [S, h, d, window]
    v_view = paged_gather(v_pool, page_table, H)
    t_new, w = q.shape[1], k_view.shape[-1]
    logits = jnp.einsum("bqhd,bhdk->bhqk", q, k_view) * scale
    q_idx = rel_pos[:, None] + jnp.arange(t_new)[None, :]
    allow = jnp.arange(w)[None, None, :] <= q_idx[:, :, None]
    logits = jnp.where(allow[:, None], logits.astype(jnp.float32), -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(jnp.isneginf(logits), 0.0, jnp.exp(logits - m_safe))
    weights = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bhdk->bqhd", weights.astype(q.dtype), v_view)


def _arena(rng, lanes, page_size, pages_per_seq, live, *, int8=False):
    """Random pools and a table whose lane ``i`` holds ``live[i]`` tokens
    (the pages beyond are sentinels); a stale tail is left in each last
    page, as a recycled page would have."""
    num_pages = lanes * pages_per_seq + 3
    k = rng.standard_normal((num_pages, page_size, H, D)).astype(np.float32)
    v = rng.standard_normal((num_pages, page_size, H, D)).astype(np.float32)
    table = np.full((lanes, pages_per_seq), num_pages, np.int32)
    perm = rng.permutation(num_pages)
    at = 0
    for i, n in enumerate(live):
        need = -(-int(n) // page_size)
        table[i, :need] = perm[at:at + need]
        at += need
    return (as_pool(k, int8=int8), as_pool(v, int8=int8),
            jnp.asarray(table))


def quantized(x):
    """``[pages, page_size, h, d]`` floats as int8 codes of that shape
    and their ``[pages, h]`` scales."""
    scales = np.abs(x).max(axis=(1, 3)) / 127.0
    return np.round(x / scales[:, None, :, None]).astype(np.int8), scales


def as_pool(x, *, int8=False):
    """A hand-built ``[pages, page_size, h, d]`` array as the arena stores
    it: rows of ``h*d`` (int8: codes in that shape and ``[pages, h]``
    scales). The one place the tests know the stored shape."""
    x, scales = quantized(np.asarray(x)) if int8 else (np.asarray(x), None)
    rows = jnp.asarray(x.reshape(x.shape[:2] + (-1,)))
    return (rows, jnp.asarray(scales)) if int8 else rows


# (page_size, pages_per_seq): two chunks of 8 pages; 16 chunks as in the
# benchmark's window; a table that is no whole number of chunks
GEOMETRIES = {"2chunks": (16, 16), "window2048": (16, 128),
              "ragged_tail": (16, 20), "page_over_chunk": (256, 3)}


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("t_new", [1, 5, READ_CHUNK_TOKENS])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_bounded_read_equals_full_window_read(geometry, t_new, int8):
    """Ragged lanes: one at position 0, one whose last query sits in the
    window's last slot, two in between."""
    page_size, pages_per_seq = GEOMETRIES[geometry]
    window = page_size * pages_per_seq
    rng = np.random.default_rng(7 * t_new + pages_per_seq + int(int8))
    rel = np.array([0, window - t_new, window // 3, 17], np.int32)
    k, v, table = _arena(rng, 4, page_size, pages_per_seq, rel + t_new,
                         int8=int8)
    q = jnp.asarray(rng.standard_normal((4, t_new, H, D)), jnp.float32)
    scale = jnp.float32(1.0 / np.sqrt(D))
    want = full_window_read(q, k, v, table, jnp.asarray(rel), scale)
    got = jax.jit(paged_read_attention)(q, k, v, table, jnp.asarray(rel),
                                        scale)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("t_new", [1, 5])
def test_window_shorter_than_one_chunk_is_one_trip(t_new):
    page_size, pages_per_seq = 4, 8                     # window 32 < 128
    assert read_chunk_pages(page_size, pages_per_seq) == pages_per_seq
    rng = np.random.default_rng(t_new)
    rel = np.array([0, 32 - t_new, 9], np.int32)
    k, v, table = _arena(rng, 3, page_size, pages_per_seq, rel + t_new)
    q = jnp.asarray(rng.standard_normal((3, t_new, H, D)), jnp.float32)
    want = full_window_read(q, k, v, table, jnp.asarray(rel), 0.5)
    got = paged_read_attention(q, k, v, table, jnp.asarray(rel), 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    assert int(read_trip_count(jnp.asarray(rel), t_new, page_size,
                               pages_per_seq)) == 1


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_all_padded_dispatch_reads_finite_zeros(int8):
    """Warm-up and retired lanes: every table entry the sentinel."""
    rng = np.random.default_rng(3)
    k, v, table = _arena(rng, 2, 16, 16, [0, 0], int8=int8)
    q = jnp.asarray(rng.standard_normal((2, 5, H, D)), jnp.float32)
    out = paged_read_attention(q, k, v, table, jnp.zeros(2, jnp.int32), 0.3)
    assert np.array_equal(np.asarray(out), np.zeros((2, 5, H, D)))


def test_stale_keys_past_the_live_position_are_not_read():
    """NaN in every page of the chunks beyond the furthest live lane: the
    bounded read never gathers them, so the output is what it was (a
    full-window read would turn it to NaN through 0 * NaN)."""
    page_size, pages_per_seq = 16, 32                   # 4 chunks
    rng = np.random.default_rng(11)
    rel = np.array([100, 3], np.int32)                  # bound: chunk 0
    k, v, table = _arena(rng, 2, page_size, pages_per_seq, [512, 512])
    clean = paged_read_attention(
        jnp.ones((2, 1, H, D)), k, v, table, jnp.asarray(rel), 0.2)
    far = np.asarray(table)[:, 8:].reshape(-1)          # chunks 1..3
    k_bad, v_bad = k.at[far].set(jnp.nan), v.at[far].set(jnp.nan)
    out = paged_read_attention(
        jnp.ones((2, 1, H, D)), k_bad, v_bad, table, jnp.asarray(rel), 0.2)
    assert np.array_equal(np.asarray(out), np.asarray(clean))


def test_write_then_read_round_trip_matches_dense_attention():
    """Scatter 40 tokens through a shuffled table, read them back."""
    page_size, pages_per_seq, n = 16, 16, 40
    rng = np.random.default_rng(5)
    k, v, table = _arena(rng, 1, page_size, pages_per_seq, [n])
    new_k = jnp.asarray(rng.standard_normal((1, n, H, D)), jnp.float32)
    new_v = jnp.asarray(rng.standard_normal((1, n, H, D)), jnp.float32)
    slots = jnp.arange(n, dtype=jnp.int32)[None]
    k = paged_write(k, new_k, table, slots)
    v = paged_write(v, new_v, table, slots)
    q = jnp.asarray(rng.standard_normal((1, n, H, D)), jnp.float32)
    got = paged_read_attention(q, k, v, table, jnp.zeros(1, jnp.int32), 0.35)
    logits = np.einsum("bqhd,bkhd->bhqk", q, new_k) * 0.35
    logits = np.where(np.tril(np.ones((n, n), bool))[None, None], logits,
                      -np.inf)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", w, new_v)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


TRIP_CASES = [
    # rel_pos, t_new, page_size, pages_per_seq, trips
    ([0], 1, 16, 128, 1),
    ([127], 1, 16, 128, 1),
    ([128], 1, 16, 128, 2),
    ([0, 500, 37], 1, 16, 128, 4),
    ([0], 128, 16, 128, 1),
    ([1], 128, 16, 128, 2),
    ([2047], 1, 16, 128, 16),
    ([4000], 1, 16, 128, 16),          # clamped to the table
    ([300], 5, 16, 20, 3),             # 20 pages: chunks of 8, 8, 4
    ([31], 1, 4, 8, 1),                # window under one chunk
    ([600], 1, 256, 3, 3),             # a page larger than the chunk
]


@pytest.mark.parametrize("rel,t_new,page_size,pages_per_seq,trips",
                         TRIP_CASES)
def test_trip_count_same_on_host_and_device(rel, t_new, page_size,
                                            pages_per_seq, trips):
    rel = np.asarray(rel, np.int32)
    host = read_trip_count(rel, t_new, page_size, pages_per_seq, xp=np)
    dev = jax.jit(lambda r: read_trip_count(r, t_new, page_size,
                                            pages_per_seq))(rel)
    assert int(host) == int(dev) == trips


# ---------------------------------------------------------------------------
# the chunk rounded where the products would round it (PR 38)
# ---------------------------------------------------------------------------

# form -> (query heads a K/V head, value width of a latent row or None)
ROUNDED_FORMS = {"plain": (1, None), "grouped": (4, None),
                 "latent": (4, 8)}


def _rounding_case(form, rng, *, positions=5, q_dtype=jnp.bfloat16,
                   int8=False):
    """Arguments of a read of ``positions`` new tokens a lane in one of
    the three forms its callers use, over float32 (or int8) pools."""
    group, v_width = ROUNDED_FORMS[form]
    page_size, pages_per_seq = 16, 20
    rel = np.array([0, 150, page_size * pages_per_seq - positions], np.int32)
    k, v, table = _arena(rng, 3, page_size, pages_per_seq, rel + positions,
                         int8=int8)
    if v_width is not None:     # one key head as wide as the row, no V pool
        q_shape, v = (3, positions * group, 1, H * D), None
    else:
        q_shape = (3, positions * group, H, D)
    q = jnp.asarray(rng.standard_normal(q_shape), q_dtype)
    return (q, k, v, table, jnp.asarray(rel), jnp.asarray(0.3, q_dtype)), {
        "group": group, "v_width": v_width}


def primitives(jaxpr):
    """The name of every primitive of a jaxpr and of the jaxprs inside."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield from primitives(x)


@pytest.mark.parametrize("form", sorted(ROUNDED_FORMS))
def test_rounded_read_equals_the_read_over_a_rounded_pool(form):
    """A bfloat16 query, float32 pools, more than one row a K/V head:
    the read gives, bit for bit, what it gives over the pools rounded to
    bfloat16 as a whole (what the TPU compiler made of the pools before
    PR 38), and something else than the unrounded products the CPU
    would form."""
    args, kw = _rounding_case(form, np.random.default_rng(len(form)))
    q, k, v, *rest = args

    def rounded(pool):
        return None if pool is None else \
            pool.astype(jnp.bfloat16).astype(jnp.float32)

    assert read_rounds_chunk(q.dtype, k.dtype, q.shape[1])
    got = paged_read_attention(*args, **kw)
    want = paged_read_attention(q, rounded(k), rounded(v), *rest, **kw)
    assert got.dtype == want.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert "reduce_precision" in set(primitives(jax.make_jaxpr(
        lambda *a: paged_read_attention(*a, **kw))(*args).jaxpr))
    # the pools themselves are not bfloat16 numbers: rounding did something
    assert not np.array_equal(np.asarray(k), np.asarray(rounded(k)))


@pytest.mark.parametrize("case", ["one_row", "f32_query", "int8_pools"])
def test_the_read_does_not_round_where_the_products_do_not(case):
    """One row a K/V head (the vector unit's float32 products use K/V
    unrounded), a float32 query, int8 pools (dequantized in the gather):
    the read's jaxpr is what it was, no ``reduce_precision`` in it."""
    rng = np.random.default_rng(len(case))
    args, kw = _rounding_case(
        "plain", rng, positions=1 if case == "one_row" else 5,
        q_dtype=jnp.float32 if case == "f32_query" else jnp.bfloat16,
        int8=case == "int8_pools")
    q, k = args[0], args[1]
    assert not read_rounds_chunk(
        q.dtype, None if isinstance(k, tuple) else k.dtype, q.shape[1])
    assert "reduce_precision" not in set(primitives(jax.make_jaxpr(
        lambda *a: paged_read_attention(*a, **kw))(*args).jaxpr))


@pytest.mark.parametrize("q_dtype,pool_dtype,rows,rounds", [
    ("bfloat16", "float32", 2, True),        # spec_verify at K = 1
    ("bfloat16", "float32", 128, True),      # a prefill chunk; the latent read
    ("bfloat16", "float32", 16, True),       # the hybrid's grouped read
    ("float16", "float32", 4, True),
    ("bfloat16", "float32", 1, False),       # OPT's fused block, ticked step
    ("float32", "float32", 128, False),
    ("bfloat16", "bfloat16", 128, False),    # nothing narrower to round to
    ("bfloat16", None, 128, False),          # int8 pools
])
def test_read_rounds_chunk(q_dtype, pool_dtype, rows, rounds):
    assert read_rounds_chunk(q_dtype, pool_dtype, rows) is rounds


# ---------------------------------------------------------------------------
# the engine: the loop in the lowered programs, and the counters
# ---------------------------------------------------------------------------

VOCAB = 48


@pytest.fixture(scope="module")
def net():
    return ComputationGraph(transformer_lm(
        VOCAB, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=3,
        input_ids=True)).init()


def _engine(net, **kw):
    kw = {"max_batch": 2, "page_size": 16, "pages_per_seq": 16,
          "prefill_chunk": 8, "block_len": 4, **kw}
    return PagedDecodeEngine(net, **kw)


@pytest.fixture(scope="module")
def fused_text(net):
    eng = _engine(net)
    eng.warmup()
    fn = next(f for key, f in eng._jit_cache.items()
              if key.endswith("fused_decode[S1xN4xP16]"))
    tables = np.full((1, 16), eng.arena.sentinel, np.int32)
    zi, zf = np.zeros(1, np.int32), np.zeros(1, np.float32)
    return fn.__wrapped__.lower(
        net.params, eng.arena.k_pools, eng.arena.v_pools, zi, tables, zi,
        np.zeros(1, bool), zi, np.full(1, -1, np.int32), zf, zi,
        np.ones(1, np.float32),
        np.zeros((1, 4), np.float32)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["attn.paged_gather", "attn.paged_softmax"])
def test_fused_block_reads_inside_an_inner_while(fused_text, scope):
    """The read is one function of the program (traced once, called by
    every layer from inside the block's ``while``); both scopes sit in
    the body of its own ``while``."""
    import re
    assert f"while/body/{scope}/" in fused_text
    assert len(re.findall(r"func\.func private @paged_read_attention\(",
                          fused_text)) == 1
    assert len(re.findall(r"call @paged_read_attention\(", fused_text)) == 2
    assert fused_text.count("stablehlo.while") == 2      # block + read


def test_fused_block_gathers_a_chunk_never_the_window(fused_text):
    """No gather of the program yields a whole 256-token window of K/V
    (the chunk is 8 pages of 16 rows of h*d): ``tensor<1x16x16x16`` would
    be it."""
    assert "stablehlo.gather" in fused_text
    assert "tensor<1x16x16x16x" not in fused_text
    assert "tensor<1x8x16x16x" in fused_text


def _serve(net, prompts, new_tokens, **kw):
    eng = _engine(net, **kw)
    sched = DecodeScheduler(eng, start_thread=False)
    reqs = [sched.submit(p, n) for p, n in zip(prompts, new_tokens)]
    for _ in range(400):
        if all(r.done for r in reqs):
            break
        sched.step_once()
    assert all(r.done for r in reqs)
    return eng


def _kv_counters(eng):
    read = eng.registry.get("decode_kv_read_tokens_total")
    window = eng.registry.get("decode_kv_window_tokens_total")
    assert read is not None and window is not None
    kinds = {s["labels"]["kind"] for s in read.snapshot()["series"]}
    return ({k: read.value(kind=k) for k in kinds},
            {k: window.value(kind=k) for k in kinds})


@pytest.mark.parametrize("block_len,kinds", [(4, {"paged", "fused"}),
                                             (1, {"paged"})])
def test_short_sequences_read_a_share_of_the_window(net, block_len, kinds):
    eng = _serve(net, [[1, 2, 3, 4, 5], [6, 7, 8]], [6, 5],
                 block_len=block_len)
    read, window = _kv_counters(eng)
    assert set(read) == kinds
    for kind in kinds:
        # lanes at 3-11 tokens of a 256-token window: one chunk of two
        assert 0 < read[kind] < window[kind]
        assert read[kind] / window[kind] == pytest.approx(0.5)
    assert eng.registry.get("decode_dispatches_total").value(
        kind="paged") > 0


def test_a_lane_at_the_windows_end_reads_all_of_it(net):
    """249 prompt tokens + 7 generated fill the 256-token window: the
    last dispatches visit both chunks."""
    eng = _engine(net, max_batch=1, block_len=1)
    read = eng.registry.get("decode_kv_read_tokens_total")
    window = eng.registry.get("decode_kv_window_tokens_total")
    tables = np.full((1, 16), eng.arena.sentinel, np.int32)
    eng.run(np.zeros((1, 1), np.int32), np.full((1, 1), -1, np.int32),
            np.array([255], np.int32), tables, np.zeros(1, np.int32))
    assert read.value(kind="paged") == window.value(kind="paged") == 256
    eng.run(np.zeros((1, 1), np.int32), np.full((1, 1), -1, np.int32),
            np.array([5], np.int32), tables, np.zeros(1, np.int32))
    assert read.value(kind="paged") == 256 + 128
    assert window.value(kind="paged") == 512


def test_warmup_counts_nothing(net):
    eng = _engine(net)
    eng.warmup()
    read, window = _kv_counters(eng)
    assert not any(read.values()) and not any(window.values())


def test_speculative_kinds_are_counted(net):
    from deeplearning4j_tpu.models.transformer import draft_transformer_lm
    draft = ComputationGraph(draft_transformer_lm(
        VOCAB, d_model=16, n_heads=2, d_ff=32, seed=5)).init()
    eng = _serve(net, [[1, 2, 3, 4, 5]], [6], block_len=1, draft_net=draft,
                 draft_k=2)
    read, window = _kv_counters(eng)
    assert {"paged", "draft_prefill", "draft", "verify"} <= set(read)
    for kind in ("draft", "verify"):
        assert 0 < read[kind] < window[kind]
    # the draft block runs K+1 steps of the read, the verify one
    assert window["draft"] == 3 * window["verify"]


def test_benchmark_metric_names_counters_the_registry_has(net):
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "metrics", "paged_read_window_share.json")
    with open(path) as f:
        metric = json.load(f)
    assert metric["name"] == "paged_read_window_share"
    assert metric["layer"] == "paged read"
    assert metric["moves"] == "serve_tokens_per_s"
    assert metric["reader"]["kind"] == "counter_ratio"
    eng = _serve(net, [[1, 2, 3]], [4])
    values = {}
    for side in ("num", "den"):
        spec = metric["reader"][side]
        family = eng.registry.get(spec["metric"])
        assert family is not None, spec["metric"]
        want = spec.get("labels", {})
        values[side] = sum(
            s["value"] for s in family.snapshot()["series"]
            if all(s["labels"].get(k) == x for k, x in want.items()))
    share = metric["reader"]["scale"] * values["num"] / values["den"]
    assert 0 < share < 100
    with open(os.path.join(os.path.dirname(path), "..", "..",
                           "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == metric["name"]]
    assert len(entry) == 1
    assert entry[0]["workloads"] == ["serve-opt-chat", "serve-opt-docqa",
                                     "serve-pangu-longdoc"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[0][key] == metric[key]


@pytest.fixture(scope="module")
def bf16_net():
    return ComputationGraph(transformer_lm(
        VOCAB, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=3,
        input_ids=True, dtype="mixed_bf16")).init()


def _rounded(eng, kind):
    read = eng.registry.get("decode_kv_read_tokens_total").value(kind=kind)
    return read, eng.registry.get(
        "decode_kv_chunk_rounded_tokens_total").value(kind=kind)


@pytest.mark.parametrize("case", ["bf16_prefill", "bf16_one_row_block",
                                  "f32_prefill", "bf16_int8_prefill",
                                  "bf16_grouped_one_token"])
def test_rounded_counter_counts_the_dispatches_whose_read_rounds(
        net, bf16_net, case):
    """``decode_kv_chunk_rounded_tokens_total`` is all of
    ``decode_kv_read_tokens_total`` for a ``mixed_bf16`` prefill dispatch
    and for a grouped-query net's one-token step (``group`` rows a K/V
    head), and stays 0 for a one-row block, a float32 policy and int8
    pools."""
    if case == "bf16_grouped_one_token":
        served = ComputationGraph(nemotron_h_lm(     # one attention layer
            VOCAB, pattern="*", d_model=16, n_heads=4, n_kv_heads=2,
            mamba_heads=1, mamba_head_dim=8, mamba_groups=1, state_size=8,
            n_experts=1, top_k=1, d_latent=8, d_expert=8, d_shared=8,
            dtype="mixed_bf16")).init()
    else:
        served = net if case.startswith("f32") else bf16_net
    eng = _engine(served, max_batch=1,
                  kv_dtype="int8" if "int8" in case else None)
    tables = np.full((1, 16), eng.arena.sentinel, np.int32)
    zi = np.zeros(1, np.int32)
    if case.endswith("prefill"):
        eng.run(np.zeros((1, 8), np.int32), np.full((1, 8), -1, np.int32),
                np.array([130], np.int32), tables, zi)
        read, rounded = _rounded(eng, "paged")
        assert read == 256
    elif case == "bf16_grouped_one_token":
        eng.run(np.zeros((1, 1), np.int32), np.full((1, 1), -1, np.int32),
                np.array([5], np.int32), tables, zi)
        read, rounded = _rounded(eng, "paged")
        assert read == 128
    else:
        eng.run_fused(zi, tables, np.array([5], np.int32), np.ones(1, bool),
                      np.full(1, 4, np.int32), np.full(1, -1, np.int32),
                      np.zeros(1, np.float32), zi, np.ones(1, np.float32),
                      np.zeros((1, 4), np.float32))
        read, rounded = _rounded(eng, "fused")
        assert read == 4 * 128
    assert rounded == (read if case in ("bf16_prefill",
                                        "bf16_grouped_one_token") else 0)
