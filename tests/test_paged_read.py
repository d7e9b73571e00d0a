"""The paged read (``ops/paged_attention.paged_read_attention``): the
chunked, bounded read against the full-window gather + masked softmax it
replaced, the trip count on host and device, the inner ``while`` in the
lowered fused block, and the two counters that say how much of the window
a dispatch visits (``paged_read_window_share`` of the benchmark), and
the rounding of the gathered chunk where the products would round K/V
(``paged_read_chunk_rounded_share``); the trip that follows the value
width (``read_trip_tokens``: a latent read's 512 keys, the guard's
halving) and the counter of the keys read in such trips
(``paged_read_long_trip_share``); the read over one K/V head, which folds
each chunk as gathered, against the turned read it replaced, and its
counter (``paged_read_unturned_share``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import nemotron_h_lm
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.ops import paged_attention as _paged
from deeplearning4j_tpu.ops.paged_attention import (
    READ_CHUNK_TOKENS, paged_gather, paged_read_attention, paged_write,
    read_chunk_pages, read_rounds_chunk, read_trip_count, read_trip_tokens)
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine)

H, D = 2, 8


def full_window_read(q, k_pool, v_pool, page_table, rel_pos, scale,
                     group=1, v_width=None):
    """What ``apply_paged`` did before the bounded read: gather every
    lane's whole table, then mask (kept here as the test's reference).
    ``group`` and ``v_width`` as for ``paged_read_attention``."""
    heads = q.shape[2]
    k_view = paged_gather(k_pool, page_table, heads)    # [S, h, d, window]
    v_view = (paged_gather(v_pool, page_table, heads) if v_width is None
              else k_view[:, :, :v_width])
    t_new, w = q.shape[1], k_view.shape[-1]
    logits = jnp.einsum("bqhd,bhdk->bhqk", q, k_view) * scale
    q_idx = rel_pos[:, None] + (jnp.arange(t_new) // group)[None, :]
    allow = jnp.arange(w)[None, None, :] <= q_idx[:, :, None]
    logits = jnp.where(allow[:, None], logits.astype(jnp.float32), -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(jnp.isneginf(logits), 0.0, jnp.exp(logits - m_safe))
    weights = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bhdk->bqhd", weights.astype(q.dtype), v_view)


def _arena(rng, lanes, page_size, pages_per_seq, live, *, int8=False,
           h=H, d=D):
    """Random pools and a table whose lane ``i`` holds ``live[i]`` tokens
    (the pages beyond are sentinels); a stale tail is left in each last
    page, as a recycled page would have."""
    num_pages = lanes * pages_per_seq + 3
    k = rng.standard_normal((num_pages, page_size, h, d)).astype(np.float32)
    v = rng.standard_normal((num_pages, page_size, h, d)).astype(np.float32)
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
# the same, with the read's value width and float32 logit rows (lanes x
# K/V heads x query rows a head): the latent cell's window of 512 pages
LONG_TRIP_CASES = [
    # rel_pos, t_new, page_size, pages_per_seq, trips, d_v, logit_rows
    ([600], 1, 16, 512, 2, 512, 32 * 128),           # decode: 512 keys
    ([6000], 128, 16, 512, 12, 512, 16 * 16384),     # prefill, 16 lanes
    ([6000], 128, 16, 512, 24, 512, 32 * 16384),     # 32 lanes: 256 keys
    ([1279], 1, 16, 80, 3, 512, 4),                  # 80 pages: 32, 32, 16
    ([4000], 1, 16, 80, 3, 512, 4),                  # clamped to the table
    ([600], 1, 16, 512, 5, 128, 32 * 32),            # a value of 128
]


@pytest.mark.parametrize(
    "rel,t_new,page_size,pages_per_seq,trips,d_v,logit_rows",
    [case + (READ_CHUNK_TOKENS, 1) for case in TRIP_CASES] + LONG_TRIP_CASES)
def test_trip_count_same_on_host_and_device(rel, t_new, page_size,
                                            pages_per_seq, trips, d_v,
                                            logit_rows):
    rel = np.asarray(rel, np.int32)
    host = read_trip_count(rel, t_new, page_size, pages_per_seq, np, d_v,
                           logit_rows)
    dev = jax.jit(lambda r: read_trip_count(r, t_new, page_size,
                                            pages_per_seq, d_v=d_v,
                                            logit_rows=logit_rows))(rel)
    assert int(host) == int(dev) == trips


# the trip of each serving cell's read (`benchmarks/configs`): value width,
# float32 logit rows (lanes x K/V heads x query rows a head), keys a trip
READ_TRIPS = {
    "opt_prefill": (64, 8 * 32 * 128, 128),
    "opt_fused_step": (64, 8 * 32, 128),
    "hybrid_prefill": (128, 32 * 2 * 16 * 128, 128),
    "eva_prefill": (128, 8 * 32 * 128, 128),
    "latent_fused_step": (512, 32 * 128, 512),
    "latent_prefill_16_lanes": (512, 16 * 128 * 128, 512),
    # 32 lanes x 128 tokens x 128 heads: 1 GiB of logits at 512 keys
    "latent_prefill_32_lanes": (512, 32 * 128 * 128, 256),
    "value_640": (640, 1, 512),
    "value_1024": (1024, 1, 1024),
    "value_200": (200, 1, 128),
}


@pytest.mark.parametrize("case", sorted(READ_TRIPS))
def test_the_trip_follows_the_value_width(case):
    """The largest power-of-two multiple of 128 keys not above the value
    width, halved while the trip's float32 logits pass 512 MiB."""
    d_v, rows, keys = READ_TRIPS[case]
    assert read_trip_tokens(d_v, rows) == keys
    assert rows * keys * 4 <= _paged.READ_TRIP_LOGIT_BYTES or keys == 128
    assert read_chunk_pages(16, 512, d_v, rows) == keys // 16


@pytest.fixture
def short_trips(monkeypatch):
    """The read held to 128-key trips: no logits fit the guard."""
    paged_read_attention.clear_cache()
    monkeypatch.setattr(_paged, "READ_TRIP_LOGIT_BYTES", 0)
    yield
    paged_read_attention.clear_cache()


@pytest.mark.parametrize("t_new", [1, 8])
def test_a_latent_read_in_512_key_trips(t_new, request):
    """The latent read's shape (4 query heads on one K/V head of 640
    columns, the value its first 512) over 80 pages: trips of 32 pages
    and a last of 16, lanes at the start, at the end and inside. Against
    the whole window read at once, and against the same read made in
    128-key trips."""
    group, d, v_width, page_size, pages_per_seq = 4, 640, 512, 16, 80
    window = page_size * pages_per_seq
    rng = np.random.default_rng(11 + t_new)
    rel = np.array([0, window - t_new, 700], np.int32)
    k, _, table = _arena(rng, 3, page_size, pages_per_seq, rel + t_new,
                         h=1, d=d)
    q = jnp.asarray(rng.standard_normal((3, t_new * group, 1, d)),
                    jnp.float32)
    scale = jnp.float32(1.0 / np.sqrt(d))
    assert read_chunk_pages(page_size, pages_per_seq, v_width,
                            3 * t_new * group) == 32

    def read():
        return np.asarray(paged_read_attention(
            q, k, None, table, jnp.asarray(rel), scale, group=group,
            v_width=v_width))
    got = read()
    want = full_window_read(q, k, None, table, jnp.asarray(rel), scale,
                            group=group, v_width=v_width)
    assert got.shape == (3, t_new * group, 1, v_width)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-6)
    request.getfixturevalue("short_trips")
    assert read_chunk_pages(page_size, pages_per_seq, v_width, 1) == 8
    np.testing.assert_allclose(got, read(), rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# a read over one K/V head folds its chunks as gathered
# ---------------------------------------------------------------------------

def turned_read(q, k_pool, v_pool, page_table, rel_pos, scale, group=1,
                v_width=None):
    """The read over one K/V head as every read was made before it kept
    the gathered layout (kept here as the reference): the same trips,
    rounding and softmax, each chunk turned to ``[S, 1, row, keys]``
    (``paged_gather``), the accumulator ``[S, rows, 1, d_v]``."""
    codes = k_pool[0] if isinstance(k_pool, tuple) else k_pool
    num_pages, page_size = codes.shape[0], codes.shape[1]
    kv_dtype = jnp.float32 if isinstance(k_pool, tuple) else codes.dtype
    out_dtype = jnp.result_type(q.dtype, kv_dtype)
    s, rows, h, d = q.shape
    round_to = q.dtype if read_rounds_chunk(
        q.dtype, None if isinstance(k_pool, tuple) else codes.dtype,
        rows) else None
    d_v = d if v_width is None else v_width
    pages_per_seq = page_table.shape[1]
    cp = read_chunk_pages(page_size, pages_per_seq, d_v, s * h * rows)
    page_table = jnp.pad(page_table, ((0, 0), (0, -pages_per_seq % cp)),
                         constant_values=num_pages)
    trips = read_trip_count(rel_pos, rows // group, page_size, pages_per_seq,
                            d_v=d_v, logit_rows=s * h * rows)
    q_idx = rel_pos[:, None] + (jnp.arange(rows) // group)[None, :]

    def fold(c, carry):
        m, l, acc = carry
        table_c = jax.lax.dynamic_slice_in_dim(page_table, c * cp, cp, axis=1)
        k_c = paged_gather(k_pool, table_c, h, round_to)
        v_c = (paged_gather(v_pool, table_c, h, round_to)
               if v_width is None else k_c[:, :, :v_width])
        logits = jnp.einsum("bqhd,bhdk->bhqk", q, k_c) * scale
        key_idx = c * k_c.shape[-1] + jnp.arange(k_c.shape[-1])
        allow = key_idx[None, None, :] <= q_idx[:, :, None]
        logits = jnp.where(allow[:, None], logits.astype(jnp.float32),
                           -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(jnp.isneginf(logits), 0.0, jnp.exp(logits - m_safe))
        alpha = jnp.exp(m - m_safe)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bhdk->bqhd", p.astype(q.dtype), v_c)
        acc = jnp.swapaxes(alpha, 1, 2) * acc + pv.astype(acc.dtype)
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(0, trips, fold, (
        jnp.full((s, h, rows, 1), -jnp.inf, jnp.float32),
        jnp.zeros((s, h, rows, 1), jnp.float32),
        jnp.zeros((s, rows, h, d_v), jnp.promote_types(out_dtype,
                                                       jnp.float32))))
    out = acc / jnp.swapaxes(jnp.maximum(l, 1e-30), 1, 2)
    return out.astype(out_dtype)


# rows a head -> (new positions a lane, query heads on the one K/V head)
ONE_HEAD_ROWS = {"1": (1, 1), "4": (1, 4), "128": (8, 16)}


@pytest.mark.parametrize("q_dtype,pools", [
    ("float32", "latent"), ("bfloat16", "latent"), ("float32", "k_and_v"),
    ("bfloat16", "k_and_v"), ("float32", "int8")])
@pytest.mark.parametrize("rows", sorted(ONE_HEAD_ROWS))
def test_a_one_head_read_equals_the_turned_read(rows, q_dtype, pools):
    """One K/V head, over a table of two trips (the second partial): a
    lane at position 0, one whose last query sits in the window's last
    slot, one with a hole (a sentinel page) inside its live range; the
    latent form (one pool, the value its first columns), a K and a V pool,
    int8 pools; a float32 and a bfloat16 query over float32 pools (the
    chunk rounded where more than one row reads a key). Equal, to 1e-5,
    to the same read made with each chunk turned."""
    t_new, group = ONE_HEAD_ROWS[rows]
    d, v_width = (24, 16) if pools == "latent" else (16, None)
    page_size, pages_per_seq = 4, 40             # trips of 32 pages, then 8
    window = page_size * pages_per_seq
    rng = np.random.default_rng(100 * int(rows) + len(q_dtype) + len(pools))
    rel = np.array([0, window - t_new, 70], np.int32)
    k, v, table = _arena(rng, 3, page_size, pages_per_seq, rel + t_new,
                         int8=pools == "int8", h=1, d=d)
    table = table.at[2, 5].set(k[0].shape[0] if pools == "int8"
                               else k.shape[0])
    q = jnp.asarray(rng.standard_normal((3, t_new * group, 1, d)),
                    jnp.dtype(q_dtype))
    args = (q, k, None if pools == "latent" else v, table, jnp.asarray(rel),
            jnp.asarray(1.0 / np.sqrt(d), q.dtype))
    kw = {"group": group, "v_width": v_width}
    got = paged_read_attention(*args, **kw)
    want = jax.jit(turned_read, static_argnames=("group", "v_width"))(
        *args, **kw)
    assert got.shape == want.shape == (3, t_new * group, 1, v_width or d)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    prims = set(primitives(jax.make_jaxpr(
        lambda *a: paged_read_attention(*a, **kw))(*args).jaxpr))
    assert "transpose" not in prims


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


METRIC_CELLS = {
    "paged_read_window_share": ["serve-opt-chat", "serve-opt-docqa",
                                "serve-pangu-longdoc",
                                "serve-solar2-longprompt"],
    "paged_read_long_trip_share": ["serve-opt-chat", "serve-opt-docqa",
                                   "serve-nemotron3-reason",
                                   "serve-pangu-longdoc",
                                   "serve-solar2-longprompt"],
    "paged_read_unturned_share": ["serve-opt-chat", "serve-opt-docqa",
                                  "serve-nemotron3-reason",
                                  "serve-pangu-longdoc",
                                  "serve-solar2-longprompt"],
    "moe_grid_walked_share": ["serve-nemotron3-reason",
                              "serve-pangu-longdoc",
                              "serve-solar2-longprompt"],
}


def _metric(name):
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "metrics", f"{name}.json")
    with open(path) as f:
        return json.load(f)


def _metric_share(metric, eng):
    """The metric's ``counter_ratio`` over the engine's registry."""
    values = {}
    for side in ("num", "den"):
        spec = metric["reader"][side]
        family = eng.registry.get(spec["metric"])
        assert family is not None, spec["metric"]
        want = spec.get("labels", {})
        values[side] = sum(
            s["value"] for s in family.snapshot()["series"]
            if all(s["labels"].get(k) == x for k, x in want.items()))
    return metric["reader"]["scale"] * values["num"] / values["den"]


@pytest.mark.parametrize("name", sorted(METRIC_CELLS))
def test_benchmark_metric_names_counters_the_registry_has(net, latent_net,
                                                          name):
    metric = _metric(name)
    assert metric["name"] == name
    experts = name.startswith("moe_")
    assert metric["layer"] == ("expert layer" if experts else "paged read")
    assert metric["moves"] == "serve_tokens_per_s"
    assert metric["reader"]["kind"] == "counter_ratio"
    eng = (_serve(latent_net, [[1, 2, 3]], [4], pages_per_seq=64)
           if experts else _serve(net, [[1, 2, 3]], [4]))
    share = _metric_share(metric, eng)
    if name == "paged_read_long_trip_share":
        assert share == 0               # OPT-like heads of 8: 128-key trips
    elif name == "paged_read_unturned_share":
        assert share == 0               # two K/V heads: every chunk turned
    else:
        assert 0 < share < 100
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == metric["name"]]
    assert len(entry) == 1
    assert entry[0]["workloads"] == METRIC_CELLS[name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[0][key] == metric[key]


@pytest.fixture(scope="module")
def latent_net():
    """The small latent-attention net of ``test_pangu.py`` with a value
    512 wide, as the latent cell's (``kv_lora_rank``)."""
    from test_pangu import SMALL, build_net, common
    cfg = dict(SMALL, kv_lora_rank=512)
    return build_net(common.load_family(cfg), cfg, 3, max_cache_t=1024)


def test_a_latent_engine_counts_its_512_key_trips(latent_net, net):
    """``_note_kv_read`` counts with the vertex's own trip: lanes x 512 x
    trips, all of it in long trips; an OPT-like engine's long-trip counter
    stays 0."""
    eng = PagedDecodeEngine(latent_net, max_batch=2, page_size=16,
                            pages_per_seq=64, prefill_chunk=16,
                            block_len=1, prefix_cache=False)
    read = eng.registry.get("decode_kv_read_tokens_total")
    long_trip = eng.registry.get("decode_kv_long_trip_tokens_total")
    tables = np.full((2, 64), eng.arena.sentinel, np.int32)
    zi = np.zeros(2, np.int32)
    eng.run(np.zeros((2, 1), np.int32), np.full((2, 1), -1, np.int32),
            np.array([600, 10], np.int32), tables, zi)
    assert read.value(kind="paged") == 2 * 512 * 2           # 601 keys
    eng.run(np.zeros((2, 16), np.int32), np.full((2, 16), -1, np.int32),
            np.array([0, 100], np.int32), tables, zi)
    assert read.value(kind="paged") == 2 * 512 * 2 + 2 * 512 * 1
    assert long_trip.value(kind="paged") == read.value(kind="paged")
    assert _metric_share(_metric("paged_read_long_trip_share"), eng) == 100
    opt = _serve(net, [[1, 2, 3, 4, 5]], [6])
    assert opt.registry.get("decode_kv_read_tokens_total").value(
        kind="paged") > 0
    assert opt.registry.get("decode_kv_long_trip_tokens_total").value(
        kind="paged") == 0


def test_a_one_head_read_is_counted_unturned(latent_net, net):
    """``decode_kv_unturned_tokens_total`` is all of
    ``decode_kv_read_tokens_total`` for a latent engine (one K/V head a
    vertex, whatever the dispatch) and 0 for an OPT-like engine (two)."""
    eng = PagedDecodeEngine(latent_net, max_batch=2, page_size=16,
                            pages_per_seq=64, prefill_chunk=16,
                            block_len=4, prefix_cache=False)
    read = eng.registry.get("decode_kv_read_tokens_total")
    unturned = eng.registry.get("decode_kv_unturned_tokens_total")
    tables = np.full((2, 64), eng.arena.sentinel, np.int32)
    zi = np.zeros(2, np.int32)
    eng.run(np.zeros((2, 16), np.int32), np.full((2, 16), -1, np.int32),
            np.array([0, 700], np.int32), tables, zi)
    eng.run_fused(zi, tables, np.array([5, 900], np.int32),
                  np.ones(2, bool), np.full(2, 4, np.int32),
                  np.full(2, -1, np.int32), np.zeros(2, np.float32), zi,
                  np.ones(2, np.float32), np.zeros((2, 4), np.float32))
    for kind in ("paged", "fused"):
        assert read.value(kind=kind) > 0
        assert unturned.value(kind=kind) == read.value(kind=kind)
    assert _metric_share(_metric("paged_read_unturned_share"), eng) == 100
    opt = _serve(net, [[1, 2, 3, 4, 5]], [6])
    assert opt.registry.get("decode_kv_read_tokens_total").value(
        kind="paged") > 0
    assert opt.registry.get("decode_kv_unturned_tokens_total").value(
        kind="paged") == 0


def _recording_routing(monkeypatch):
    """``(counts, steps, tokens)`` of every dispatch the engines of a
    test account their routing for."""
    seen = []
    real = PagedDecodeEngine._note_routing

    def recording(self, counts, steps, tokens):
        if counts and not self._warming:
            seen.append(([int(v) for v in counts[0]], steps, tokens))
        return real(self, counts, steps, tokens)

    monkeypatch.setattr(PagedDecodeEngine, "_note_routing", recording)
    return seen


def _sized(seen, layers, k, e_held, n_published):
    """Tiles computed over the tiles' rows, and the tiles the kernel's
    arrays are sized for, over the recorded dispatches."""
    from deeplearning4j_tpu.nn.conf.moe import tile_rows
    computed = sized = 0
    for counts, steps, tokens in seen:
        rows = tile_rows(tokens * k, n_published)
        assert counts[2] % rows == 0
        computed += counts[2] // rows
        sized += layers * steps * (e_held + tokens * k // rows)
    return computed, sized


def _grid_tiles(eng):
    series = eng.registry.get("moe_grid_tiles_total").snapshot()["series"]
    return {(s["labels"]["kernel"], s["labels"]["part"]): s["value"]
            for s in series}


def test_a_gated_engine_counts_the_live_tiles_its_kernel_walks(
        latent_net, monkeypatch):
    """Two expert layers holding 4 of 16 experts, 3 a token: the gated
    kernel walks the tiles the routing filled (rows computed over a
    tile's rows, per dispatch) and skips the rest of the tiles its arrays
    are sized for (``e_held + T·k // rows`` a call), in prefill chunks
    and fused blocks alike; counted from what the dispatch brought back."""
    seen = _recording_routing(monkeypatch)
    eng = _serve(latent_net, [list(range(1, 12)), [5, 6, 7]], [7, 6],
                 pages_per_seq=64)
    assert {tokens for _, _, tokens in seen} >= {2 * 8, 2}
    walked, sized = _sized(seen, 2, 3, 4, 16)
    assert _grid_tiles(eng) == {("moe_gated_ffn", "walked"): walked,
                                ("moe_gated_ffn", "skipped"): sized - walked}
    assert 0 < walked < sized
    share = _metric_share(_metric("moe_grid_walked_share"), eng)
    assert share == pytest.approx(100.0 * walked / sized)


def test_a_squared_relu_engine_walks_every_tile(monkeypatch):
    """The grouped kernel's grid is static: every tile it is sized for
    is walked, none skipped, and the share reads 100."""
    hybrid = ComputationGraph(nemotron_h_lm(
        VOCAB, pattern="*E", d_model=16, n_heads=4, n_kv_heads=2,
        mamba_heads=1, mamba_head_dim=8, mamba_groups=1, state_size=8,
        n_experts=16, top_k=3, d_latent=8, d_expert=8, d_shared=8,
        experts_held=4, expert_offset=4, max_cache_t=256)).init()
    seen = _recording_routing(monkeypatch)
    eng = _serve(hybrid, [list(range(1, 12)), [5, 6, 7]], [7, 6],
                 prefix_cache=False)
    _, sized = _sized(seen, 1, 3, 4, 16)
    assert sized > 0
    assert _grid_tiles(eng) == {("moe_grouped_ffn", "walked"): sized,
                                ("moe_grouped_ffn", "skipped"): 0}
    assert _metric_share(_metric("moe_grid_walked_share"), eng) == 100


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
