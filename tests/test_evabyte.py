"""The ``evabyte`` decoder (``models/evabyte.py``, EVA attention in
``nn/conf/eva.py``) through the program's normal paths, at a small size in
float32 (hidden 64, 4 heads of 16, window 64, chunks of 16, 2 layers, 320
ids), against its plain reference (``benchmarks/configs/evabyte.reference.py``,
which imports nothing of the program and computes a window block at a
time): the whole-sequence forward; chunked prefill and then decoding
through the paged engine across window edges, one of them inside a fused
block; the summary rows the pools hold; the pages a lane holds and gives
back; the read's reach; what the engine refuses; and the programs of the
other attention kinds, which this layer leaves as they were.

Weights. The benchmark's draw (``lib/weights.py``, N(0, 0.02^2)) makes the
attention nearly uniform at hidden 64, so the tests scale the attention's
matrices by 10 and the pooling vectors by 50 (program and reference get
the same numbers): a softmax then has a clear maximum and a chunk's pooling
weights differ, so a wrong row, a missing summary or a rounded pooling
softmax moves the logits.

Tolerances. Both sides compute in float32 (x64 is on in this suite, every
array made float32) and differ by the order of their sums (a running
softmax over chunks of pages against one softmax over a block, summaries
pooled from a page against a reshaped sequence): log-probabilities agree to
some 1e-6, and the limit is ``TOL`` = 2e-5. The same comparisons with the
summaries left out read 1e-2 and more, and with the pooling softmax
rounded to bfloat16 read 1e-4 and more (the two tests that plant them)."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer as _transformer
from deeplearning4j_tpu.models.transformer import (draft_transformer_lm,
                                                   transformer_lm)
from deeplearning4j_tpu.nn.conf.eva import EvaAttentionLayer
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.ops import paged_attention as _paged
from deeplearning4j_tpu.serving.decode import PagedDecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from lib import common, weights  # noqa: E402
from lib.train_cell import build_net  # noqa: E402

DATA = os.path.join(ROOT, "benchmarks", "tests", "data", "configs")
with open(os.path.join(DATA, "evabyte-small.json")) as _f:
    SMALL = json.load(_f)
SEED = 2147483659
TOL = 2e-5
W, C = SMALL["window_size"], SMALL["chunk_size"]       # 64, 16
CONTEXT = 256
ENGINE = dict(max_batch=2, page_size=C, pages_per_seq=CONTEXT // C,
              prefill_chunk=32)
SCALE = {"W_q": 10.0, "W_k": 10.0, "W_v": 10.0, "W_o": 10.0, "phi": 50.0,
         "mu": 50.0}


@pytest.fixture(scope="module")
def family():
    return common.load_family(SMALL)


@pytest.fixture(scope="module")
def reference():
    return common.load_reference(SMALL)


@pytest.fixture(scope="module")
def flat(family):
    made = weights.make_weights(family, SMALL, SEED)
    return {k: v * SCALE.get(k.split(".")[-1], 1.0) for k, v in made.items()}


@pytest.fixture(scope="module")
def net(family, flat):
    net = build_net(family, SMALL, SEED, max_cache_t=CONTEXT)
    like = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), net.params)
    net.params = weights.to_program(family, SMALL, flat, like)
    return net


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, 256, 176, dtype=np.int32)


def log_softmax(z):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(z, jnp.float32), -1))


@pytest.fixture(scope="module")
def want(reference, flat, ids):
    """The reference's log-probabilities at every position of ``ids``."""
    return log_softmax(reference.logits_at(flat, ids, np.arange(len(ids)),
                                           cfg=SMALL, mode="f32"))


def engine_logprobs(eng, ids, n_prefill, lane=None, before=None):
    """Teacher-forced through ``eng.run``: the first ``n_prefill`` ids in
    prefill chunks, the rest as one-token steps; the log-probabilities the
    engine returns at each position it was asked for (a chunk's last)."""
    lane = eng.acquire_lane(len(ids) + 1) if lane is None else lane
    lt = eng.lane_tables
    got, pos = {}, int(lt.pos[lane])
    while pos < len(ids):
        c = eng.prefill_chunk
        n = min(c, n_prefill - pos) if pos < n_prefill else 1
        t = c if pos < n_prefill else 1
        lt.ensure(lane, n)
        if before is not None:
            before(eng, lane)
        fed = np.zeros((1, t), np.int32)
        fed[0, :n] = ids[pos:pos + n]
        slots = np.full((1, t), -1, np.int32)
        slots[0, :n] = lt.rel_pos(lane) + np.arange(n)
        row = eng.run(fed, slots, np.array([lt.rel_pos(lane)], np.int32),
                      lt.tables[lane][None], np.array([n - 1], np.int32),
                      np.array([lane], np.int32))
        got[pos + n - 1] = np.log(np.asarray(row[0], np.float64))
        lt.advance(lane, n)
        pos += n
    return got, lane


def assert_close(got, want, tol=TOL):
    err = max(np.abs(row - want[p]).max() for p, row in got.items())
    assert err < tol, err
    return err


# ---------------------------------------------------------------------------
# (a) the whole sequence
# ---------------------------------------------------------------------------


def test_full_forward_is_the_reference(net, ids, want):
    got = np.log(np.asarray(net.output(ids[None, :, None])[0], np.float64))
    assert np.abs(got - want).max() < TOL


# ---------------------------------------------------------------------------
# (b) prefill in chunks, then decode through the pages, across window edges
# ---------------------------------------------------------------------------


def test_prefill_then_decode_across_two_edges_is_the_reference(net, ids,
                                                                want):
    """Chunks of 32 up to 96 (the first edge, 64, between two of them),
    then one-token steps across the second edge, 128, as far as 176: every
    position the engine answers agrees with the reference, and the lane
    gave back the two windows it left."""
    eng = PagedDecodeEngine(net, **ENGINE)
    got, lane = engine_logprobs(eng, ids, n_prefill=96)
    assert_close(got, want)
    assert eng.lane_tables.base[lane] == 128
    retired = eng.registry.get("eva_window_pages_retired_total")
    assert retired.value() == 2 * W // C


def test_a_fused_block_across_an_edge_is_one_block_and_correct(net, ids,
                                                               reference,
                                                               flat):
    """Prefill 60 ids, then fused blocks of 8 from 60: the first block's
    steps 4-7 lie past the edge at 64, the block's first step completes the
    old window's last chunk, and the steps after the edge see the new
    window and every summary. The block's tokens are the reference's
    argmax; a one-token step after the blocks, fed through the pools the
    blocks wrote, gives the reference's log-probabilities; and the blocks
    were dispatched as blocks (one a tick)."""
    eng = PagedDecodeEngine(net, **dict(ENGINE, block_len=8))
    prompt = ids[:60]
    got, lane = engine_logprobs(eng, prompt, n_prefill=60,
                                lane=eng.acquire_lane(100))
    first = int(np.argmax(got[59]))
    seq, last = list(prompt) + [first], first
    lt = eng.lane_tables
    for _ in range(2):                                   # 61 .. 76
        lt.ensure(lane, 8)
        rel = np.array([lt.rel_pos(lane)], np.int32)
        toks, valid, n = eng.run_fused(
            np.array([last], np.int32), lt.tables[lane][None], rel,
            np.ones(1, bool), np.array([8], np.int32),
            np.array([-1], np.int32), np.zeros(1, np.float32),
            np.zeros(1, np.int32), np.ones(1, np.float32),
            np.zeros((1, 8), np.float32), np.array([lane], np.int32))
        assert int(n[0]) == 8 and valid.all()
        lt.advance(lane, 8)
        seq += [int(t) for t in toks[0]]
        last = int(toks[0, -1])
    assert eng.registry.get("decode_dispatches_total").value(kind="fused") \
        == 2
    seq = np.asarray(seq, np.int32)
    z = log_softmax(reference.logits_at(flat, seq, np.arange(len(seq)),
                                        cfg=SMALL, mode="f32"))
    top2 = np.sort(z, -1)[:, -2:]
    clear = [p for p in range(59, len(seq) - 1)     # a near tie proves
             if top2[p, 1] - top2[p, 0] > 1e-3]     # nothing either way
    assert len(clear) >= len(seq) - 62
    assert all(seq[p + 1] == np.argmax(z[p]) for p in clear)
    after, _ = engine_logprobs(eng, seq, n_prefill=0, lane=lane)
    assert_close(after, z)


def test_the_scheduler_serves_lanes_at_different_windows(net, ids, reference,
                                                       flat):
    """The normal path: three requests through ``DecodeScheduler`` with
    fused blocks of 8, packed in a bucket of four (a padded slot), their
    lanes in different windows, prompts of 50, 100 and 130 prefilled in
    chunks of 32 and 30 tokens each decoded across the edges at 64 and 128:
    every served token is the reference's best where the reference's two
    best lie apart, and every page is back when they are done."""
    from deeplearning4j_tpu.serving.decode import DecodeScheduler
    eng = PagedDecodeEngine(net, **dict(ENGINE, max_batch=4, block_len=8))
    sched = DecodeScheduler(eng, start_thread=False)
    prompts = [ids[:50], ids[20:120], ids[40:170]]
    reqs = [sched.submit(p, 30) for p in prompts]
    for _ in range(2_000):
        if all(r.done for r in reqs):
            break
        sched.step_once()
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    assert eng.arena.allocator.pages_in_use == 0
    assert eng.arena.allocator.reserved == 0
    for p, r in zip(prompts, reqs):
        seq = np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)])
        z = np.asarray(reference.logits_at(flat, seq, np.arange(len(seq)),
                                           cfg=SMALL, mode="f32"))
        rows = z[len(p) - 1:]
        top2 = np.sort(rows, -1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 1e-3
        assert clear.sum() >= len(rows) - 2
        assert (np.argmax(rows, -1) == np.asarray(r.tokens))[clear].all()


# ---------------------------------------------------------------------------
# (c) the summary rows the pools hold
# ---------------------------------------------------------------------------


def test_the_summary_rows_are_the_references(net, ids, reference, flat):
    """After 176 ids, the K and V pools' summary rows of each layer's 11
    whole chunks are the reference's k~ and v~ computed from that layer's
    input in the reference's own stream (float32 on both sides: rounding
    alone)."""
    eng = PagedDecodeEngine(net, **ENGINE)
    _, lane = engine_logprobs(eng, ids, n_prefill=96)
    rows = np.asarray(eng.lane_tables.summaries[lane])
    n, h = len(ids) // C, SMALL["num_attention_heads"]
    d = SMALL["hidden_size"] // h
    for i in range(SMALL["num_hidden_layers"]):
        own = {k[len(f"l{i}."):]: v for k, v in flat.items()
               if k.startswith(f"l{i}.")}
        x = reference.hidden(flat, ids, cfg=dict(SMALL, num_hidden_layers=i))
        u = reference.rms_norm(x, own["g1"], SMALL["rms_norm_eps"])
        k = reference.rotary(reference.mm(u, own["W_k"], "f32")
                             .reshape(-1, h, d), SMALL["rope_theta"])
        v = reference.mm(u, own["W_v"], "f32").reshape(-1, h, d)
        pooled = reference.summaries(own, k[:n * C], v[:n * C], chunk=C,
                                     mode="f32")
        for pool, want_rows in zip((eng.arena.k_pools[i],
                                    eng.arena.v_pools[i]), pooled):
            got = np.asarray(pool)[rows].reshape(-1, h, d)[:n]
            np.testing.assert_allclose(got, want_rows, rtol=0,
                                       atol=1e-5 * np.abs(want_rows).max())


def _pooled_in_bfloat16(self, params, k, v):
    """``EvaAttentionLayer.summarize`` with its softmax rounded to
    bfloat16 (the planted fault)."""
    k = k.astype(jnp.float32)

    def pool(vec, rows):
        s = jnp.einsum("...chd,hd->...ch", k, vec.astype(jnp.float32))
        w = jax.nn.softmax((s * self._scale).astype(jnp.bfloat16), axis=-2)
        return jnp.einsum("...ch,...chd->...hd", w.astype(jnp.float32),
                          rows.astype(jnp.float32))
    return pool(params["phi"], k), pool(params["mu"], v)


def _no_summary_trips(*args, **kw):
    """``eva_read_trips`` with the summary part's trips at 0: the read
    leaves the summaries out (the planted fault)."""
    first, end, _ = REAL_TRIPS(*args, **kw)
    return first, end, 0


REAL_TRIPS = _paged.eva_read_trips


@pytest.mark.parametrize("fault,least", [("bf16_pooling", 5 * TOL),
                                         ("no_summaries", 1e-2)])
def test_a_planted_fault_fails_the_comparison(net, ids, want, fault, least):
    """The comparison of (b) with the pooling softmax rounded to bfloat16,
    or with the summaries left out of the read, lies beyond its limit."""
    with pytest.MonkeyPatch.context() as mp:
        if fault == "bf16_pooling":
            mp.setattr(EvaAttentionLayer, "summarize", _pooled_in_bfloat16)
        else:
            mp.setattr(_paged, "eva_read_trips", _no_summary_trips)
        _paged.eva_read_attention.clear_cache()
        try:
            got, _ = engine_logprobs(PagedDecodeEngine(net, **ENGINE), ids,
                                     n_prefill=96)
        finally:
            _paged.eva_read_attention.clear_cache()
    err = max(np.abs(row - want[p]).max() for p, row in got.items())
    assert err > least, err


# ---------------------------------------------------------------------------
# (d) the pages a lane holds, and gives back
# ---------------------------------------------------------------------------


def test_a_lane_holds_one_window_and_its_summaries(net):
    """Through 250 positions in chunks of 32 and fused-block-sized steps: a
    lane never holds more than ``W / C + 1`` window pages and the summary
    pages of its whole chunks, its pages in use are exactly those, the
    reservation covers the worst case, and retiring the lane gives every
    page and reservation back."""
    eng = PagedDecodeEngine(net, **dict(ENGINE, block_len=8))
    alloc = eng.arena.allocator
    total = 250
    lane, lt = eng.acquire_lane(total), eng.lane_tables
    assert alloc.reserved == W // C + 1 + -(-(total // C) // C)
    pos = 0
    while pos < total - 8:
        n = 32 if pos < 96 else 8
        lt.ensure(lane, n)
        held, sums = len(lt.held[lane]), len(lt.summaries[lane])
        assert held <= W // C + 1
        assert sums == -(-((pos + n) // C) // C)
        assert alloc.pages_in_use == held + sums
        assert alloc.pages_in_use + alloc.reserved == \
            W // C + 1 + -(-(total // C) // C)
        lt.advance(lane, n)
        pos += n
    eng.release_lane(lane)
    assert alloc.pages_in_use == 0 and alloc.reserved == 0


def test_no_page_the_lane_gave_back_is_read(net, ids, want):
    """Every page the lane held and gave back at an edge is filled with NaN
    before each dispatch (they wait at the end of the free list, so nothing
    draws them again here): a read that touched one would turn the logits
    to NaN."""
    eng = PagedDecodeEngine(net, **ENGINE)
    ever = set()

    def poison(eng, lane):
        held = eng.lane_tables.held[lane]
        ever.update(held)
        gone = np.asarray(sorted(ever - set(held)), np.int32)
        eng.arena.k_pools = [p.at[gone].set(jnp.nan)
                             for p in eng.arena.k_pools]
        eng.arena.v_pools = [p.at[gone].set(jnp.nan)
                             for p in eng.arena.v_pools]

    got, _ = engine_logprobs(eng, ids, n_prefill=96, before=poison)
    assert len(ever) > W // C + 1           # windows were given back
    assert all(np.isfinite(row).all() for row in got.values())
    assert_close(got, want)


def test_a_step_past_the_edge_reads_no_page_of_the_last_window():
    """The read alone: a lane whose window table still holds the last
    window's pages (as inside a fused block that crossed the edge), NaN in
    all of them, and a query one past the edge: the output is finite and is
    the read over the new window's one row and the summaries."""
    ps, h, d, pages = C, 2, 8, 32
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(pages, ps, h * d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(pages, ps, h * d)), jnp.float32)
    win = W // ps + 1
    table = np.full((1, win + 2), pages, np.int32)
    table[0, :win] = np.arange(win)             # old window 0..3, new page 4
    table[0, win:] = [10, 11]                   # summary pages
    k = k.at[:W // ps].set(jnp.nan)
    v = v.at[:W // ps].set(jnp.nan)
    q = jnp.asarray(rng.normal(size=(1, 1, h, d)), jnp.float32)
    out = _paged.eva_read_attention(
        q, k, v, jnp.asarray(table), jnp.array([W], jnp.int32),
        jnp.array([W], jnp.int32), 0.25, window=W, chunk_size=C)
    assert np.isfinite(np.asarray(out)).all()
    keys = jnp.concatenate([k[10, :4], k[4, :1]]).reshape(-1, h, d)
    vals = jnp.concatenate([v[10, :4], v[4, :1]]).reshape(-1, h, d)
    p = jax.nn.softmax(jnp.einsum("hd,khd->hk", q[0, 0], keys) * 0.25, -1)
    np.testing.assert_allclose(out[0, 0], jnp.einsum("hk,khd->hd", p, vals),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# (e) what the engine refuses
# ---------------------------------------------------------------------------


def test_prefix_reuse_and_speculative_decoding_are_refused(net):
    with pytest.raises(ValueError, match="prefix_cache=True with EVA"):
        PagedDecodeEngine(net, **ENGINE, prefix_cache=True)
    draft = ComputationGraph(draft_transformer_lm(
        SMALL["vocab_size"], d_model=8, n_heads=2, d_ff=16, seed=5,
        max_cache_t=CONTEXT)).init()
    with pytest.raises(ValueError, match="draft_net with EVA"):
        PagedDecodeEngine(net, **ENGINE, draft_net=draft)
    with pytest.raises(ValueError, match="page_size=8 with EVA chunks"):
        PagedDecodeEngine(net, **dict(ENGINE, page_size=8, pages_per_seq=32))
    with pytest.raises(ValueError, match="does not divide the EVA window"):
        PagedDecodeEngine(net, **dict(ENGINE, prefill_chunk=48))


# ---------------------------------------------------------------------------
# the other attention kinds' programs are the parent's
# ---------------------------------------------------------------------------

# sha256 of the lowered text of the warm-up programs of small OPT, hybrid and
# latent engines on the commit before ISSUE 39 (e8e41ba), from the same
# construction run in this suite on that checkout (interpreted kernels:
# the expert layers' grouped products are Pallas calls in these texts); the
# EVA layer must change none of them. The ``@`` entries, at windows of
# several read trips, on the commit before ISSUE 40 (184276e): a trip that
# follows the value width changes no read whose value is 128 wide or less
PARENT_TEXT = {
    # the expert layers select each held pair's row before the weighted
    # sum (the gated kernel leaves its rows past the live tiles undefined,
    # and walks only those tiles): these replace the digests of before,
    # here and in the hybrid's entry at 1024
    "nemotron_h-small": {
        "fused_decode[S1xN4xP32]":
            "6397c397f81fbfde66535773db527be8c9eb2e175083d82d2961c03fadfe1895",
        "fused_decode[S2xN4xP32]":
            "fd814d7f74f09276f83b78065a01f6b71a5a56feea37aca80e6a76c8a5a000a3",
        "paged_decode[S1xT16xP32]":
            "5da3cbcd55aabb5e988ee855c1732fd7def8cb5012d664fce7186ff0c60bca3e",
        "paged_decode[S2xT16xP32]":
            "e809aee5df2f493c9476840bee86b41dbc8ba0cd799f963a512b55b52458f448",
    },
    "opt": {
        "fused_decode[S1xN4xP32]":
            "d14e03bbc54dca42a75c0a02cd4cc546936b16d62fe3159e6afbf4678ea6d53c",
        "fused_decode[S2xN4xP32]":
            "dc304d3cf2a23bb935c9c70e4d28d113c39f65539f3c5899cbe99a5d0c80618f",
        "paged_decode[S1xT16xP32]":
            "3069c8711e414606e96ad6921cbd01590d751f7e126ac3bdfb0e573a20f3c0f1",
        "paged_decode[S1xT1xP32]":
            "0ce2b2ec465c779f806091b5b4d06662fcf0e5bc8ee09d4982ec7600a537a2fa",
        "paged_decode[S2xT16xP32]":
            "5f9ce37365128033bb6e4e94b06b7c7b0675e82b21513a328e253ee6118f8c23",
        "paged_decode[S2xT1xP32]":
            "58bbaefabf497898625e2fabd41198a1145908a4eec7b1e77b396f17b42a112e",
    },
    # the latent read over its one K/V head folds each chunk as gathered
    # (``paged_gather_rows``), and its gated expert kernel walks only the
    # live tiles: these replace the digests of before
    "pangu-small": {
        "fused_decode[S1xN4xP32]":
            "ab4f6690904e0664dc7e15167e3f6ce43bf51ad419716fe1170b445e580cb3cd",
        "fused_decode[S2xN4xP32]":
            "eec087db842517dc4de0800bd3c1c03c12889eca83404cd2a8c92c2c10cf158e",
        "paged_decode[S1xT16xP32]":
            "eae68033b64a0495b86cfc7f67d0ee43570688ef2af962b8223a6c2ddb386a7d",
        "paged_decode[S1xT1xP32]":
            "d199f49e2e898f685964d111da3212ad78acb739dd1305e89952fc3c61cfcc80",
        "paged_decode[S2xT16xP32]":
            "c7e5abe4afef432d45cb9c49eda3b758989e8139cf612092c8997eae26857faa",
        "paged_decode[S2xT1xP32]":
            "f4c643df4bd7bc7f4a7c385ce4d76c94533f08b148ad54a57b309f9ba34fae4a",
    },
    "evabyte-small@2048": {
        "fused_decode[S1xN4xP13]":
            "f3983d396707cc69db0ee0a5fe73e5a886e6298e4afe221a1b87344b7813f735",
        "fused_decode[S2xN4xP13]":
            "a4fbcda3fb4215fc7a05de5346e2450905f0815395650ea6682960983be2eb37",
        "paged_decode[S1xT16xP13]":
            "434bcd7b18fbf9e9d1c7dfc662f2c3095530b3a39f46260ae5c2dfef70fa6591",
        "paged_decode[S2xT16xP13]":
            "da675cf35fc0cf3629baa75602bc51d727feec7ff4c439dde74e913b77ed4de3",
    },
    "nemotron_h-small@1024": {
        "fused_decode[S1xN4xP256]":
            "d7246cc85cc556f8b6df00f7cc7d3e524dc5b68aa9db177f8c75f04a60cc38c5",
        "fused_decode[S2xN4xP256]":
            "a5efaf956b99ebb4fd1eb24bb53d87a0f95f6bf4c83e9fb4d5ff808d6289374a",
        "paged_decode[S1xT16xP256]":
            "a0736f344fb34ef0d295d3304ae1d4d2eb1653254d894a406b559c9e87477804",
        "paged_decode[S2xT16xP256]":
            "b652db95701ad478146bdc27adf04363a2c2ffeaf18e112d40a6cc356a72c94f",
    },
    "opt@1024": {
        "fused_decode[S1xN4xP256]":
            "4ad580adbd2c9c263410002c15761d8a644d0ba4fe4bf361cffe4b2b9a91d6a4",
        "fused_decode[S2xN4xP256]":
            "5546002870032187de02601a566c11d5de5a734e7f7a482388d323282890f632",
        "paged_decode[S1xT16xP256]":
            "740b963f7ab70b10ffb1ec8f094e3f356438bc6dfd012df1c5f4193f6f7625bd",
        "paged_decode[S1xT1xP256]":
            "67a100a5b6304b194c67f63411949148b83d2d3a26d755ba184dc6ece30920be",
        "paged_decode[S2xT16xP256]":
            "ea08bc51d4efc8ca200e74fe83339134de5ffd7b67d48533cce0cc85aa9197fb",
        "paged_decode[S2xT1xP256]":
            "0d071b5e616d5b6b95599bb439074c24167df6eaa4c773fb07501a85a80850b8",
    },
}


def _programs(tag):
    """name -> sha256 of the lowered text of every program the warm-up of
    the ``tag`` engine dispatches: ``<net>`` at a window of 128 tokens,
    ``<net>@<window>`` at a longer one (a read of several trips)."""
    seen = {}
    real = PagedDecodeEngine._dispatch

    def recording(self, name, step, arena, params, args, **kw):
        if name not in seen:
            text = jax.jit(step, donate_argnums=(1, 2)).lower(
                params, arena.k_pools, arena.v_pools, *args).as_text()
            seen[name] = hashlib.sha256(text.encode()).hexdigest()
        return real(self, name, step, arena, params, args, **kw)

    kind, _, window = tag.partition("@")
    window = int(window or 128)
    page_size = C if kind == "evabyte-small" else 4
    engine = dict(max_batch=2, page_size=page_size,
                  pages_per_seq=window // page_size, prefill_chunk=16,
                  block_len=4)
    if kind == "opt":
        net = ComputationGraph(transformer_lm(
            53, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=3,
            input_ids=True, max_cache_t=window)).init()
        engine["prefix_cache"] = True
    else:
        with open(os.path.join(DATA, f"{kind}.json")) as f:
            cfg = json.load(f)
        net = build_net(common.load_family(cfg), cfg, 3, max_cache_t=window)
        engine["prefix_cache"] = kind == "pangu-small"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(PagedDecodeEngine, "_dispatch", recording)
        PagedDecodeEngine(net, **engine).warmup()
    return seen


@pytest.mark.parametrize("tag", sorted(PARENT_TEXT))
def test_the_other_attention_kinds_lower_to_the_parents_text(tag):
    assert _programs(tag) == PARENT_TEXT[tag]


def test_the_walker_hands_eva_vertices_positions(net):
    names = [n for n in _transformer.stateful_vertices(net)
             if net._vertex_layer(n).paged_entry(1).window]
    assert names == ["l0_attn", "l1_attn"]
    assert _transformer.stateful_vertices(net, "positions") == names
