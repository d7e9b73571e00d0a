"""The ``pangu_ultra_moe`` decoder (``models/pangu.py``) through the
program's normal paths, at a small size in float32, against its plain
reference (``benchmarks/configs/openpangu-ultra-moe-718b.reference.py``,
which imports nothing of the program and expands keys and values per head:
the TEXTBOOK form): the whole-sequence forward, the dense streaming carry,
prefill in chunks then decode through the latent pool (the ABSORBED form),
rotary angles at absolute positions, the shares of an expert layer, the
gated grouped product, a prefix hit under an expert layer, the arena's
one-pool entry, and what the engine refuses.

Tolerances. Program and reference compute in float32 here (x64 is on in
this suite, but every array is made float32) and differ by the order of
their sums: chunked running softmax against one softmax, absorbed against
expanded products, sorted dispatch against a loop over experts. Log
probabilities then agree to some 1e-6; the limit is 5e-5, and the same
comparison with one product's operands rounded to bfloat16 reads 1e-3 and
more (each test that states float32 shows it)."""

import contextlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import generate, transformer_lm
from deeplearning4j_tpu.models import transformer as _transformer
from deeplearning4j_tpu.nn.conf.layers import gated_ffn
from deeplearning4j_tpu.nn.conf.mla import MLAttentionLayer
from deeplearning4j_tpu.nn.conf.rotary import rotary
from deeplearning4j_tpu.nn.conf.moe import (GatedMoELayer, dense_expert_ffn,
                                            sparse_expert_ffn)
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.ops import grouped_ffn
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine)
from deeplearning4j_tpu.serving.kv_cache import PagedEntry, PagedKVArena

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from lib import common, weights  # noqa: E402
from lib.train_cell import build_net  # noqa: E402
from test_nemotron_h import ENGINE, WINDOW, engine_logprobs  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "configs",
                       "pangu-small.json")) as _f:
    SMALL = json.load(_f)
SEED = 2147483659
TOL = 5e-5


@pytest.fixture(scope="module")
def family():
    return common.load_family(SMALL)


@pytest.fixture(scope="module")
def reference():
    return common.load_reference(SMALL)


@pytest.fixture(scope="module")
def net(family):
    return build_net(family, SMALL, SEED, max_cache_t=WINDOW)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, SMALL["vocab_size"], 45,
                                             dtype=np.int32)


@pytest.fixture(scope="module")
def want(reference, family, ids):
    """The reference's log-probabilities at every position of ``ids``."""
    flat = weights.make_weights(family, SMALL, SEED)
    z = reference.logits_at(flat, ids, np.arange(len(ids)), cfg=SMALL,
                            mode="f32", q_block=len(ids))
    return np.asarray(jax.nn.log_softmax(z, axis=-1))


def test_full_forward_is_the_reference(net, ids, want, reference, family):
    got = np.log(np.asarray(net.output(ids[None, :, None])[0], np.float32))
    assert np.abs(got - want).max() < TOL
    # float32 is stated: the reference with bfloat16 operands is 20 times
    # and more beyond the limit
    flat = weights.make_weights(family, SMALL, SEED)
    low = reference.logits_at(flat, ids, np.arange(len(ids)), cfg=SMALL,
                              mode="bf16", q_block=len(ids))
    assert np.abs(np.asarray(jax.nn.log_softmax(low, -1)) - want).max() \
        > 20 * TOL


def test_the_streaming_carry_is_the_reference(net, ids, want):
    """``generate()``'s path: the absorbed form over the dense latent
    cache, the prompt in one chunk and then token by token."""
    net.rnn_clear_previous_state()
    rows = [np.asarray(net.rnn_time_step(ids[None, :30, None]))[0]]
    for i in range(30, len(ids)):
        rows.append(np.asarray(net.rnn_time_step(ids[None, i:i + 1, None]))[0])
    got = np.log(np.concatenate(rows).astype(np.float32))
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("n_prefill,chunk", [(29, 8), (16, 16), (1, 8)])
def test_prefill_then_decode_through_the_latent_pool_is_the_reference(
        net, ids, want, n_prefill, chunk):
    """Chunks of 8 over pages of 4: positions cross chunk and page borders,
    the last chunk is partly padding, and the one-token steps that follow
    read what the chunks wrote. Logits, not tokens."""
    got, eng = engine_logprobs(net, ids, n_prefill, chunk=chunk)
    assert np.abs(got - want).max() < TOL
    layer = net._vertex_layer("l0_attn")
    pool = eng.arena.k_pools[0]
    assert pool.shape == (eng.arena.num_pages, 4, layer.pool_width)
    assert eng.arena.v_pools[0] is None
    # the row's padding stays zero, its numbers do not
    assert not np.asarray(pool[..., layer.row_width:]).any()
    assert np.asarray(pool[..., :layer.row_width]).any()


def test_a_rotary_term_turned_by_chunk_relative_positions_is_caught(
        net, ids, want):
    """What the walker hands the layer matters: with each dispatch's
    positions counted from its own first token (the view-relative slot is
    right, the angle is not) the same comparison fails."""
    real = PagedDecodeEngine._extra_args

    def relative(self, names, lanes, rel, *rest):
        out = list(real(self, names, lanes, rel, *rest))
        out[names.index("positions")] = np.zeros(len(rel), np.int32)
        return tuple(out)

    PagedDecodeEngine._extra_args = relative
    try:
        got, _ = engine_logprobs(net, ids, 29)
    finally:
        PagedDecodeEngine._extra_args = real
    assert np.abs(got - want).max() > 100 * TOL


def test_rotary_scores_by_distance_and_slices_like_the_sequence():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 12, 3, 8)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(1, 12, 8)), jnp.float32)
    pos = jnp.arange(12)[None]
    rx, ry = rotary(x, pos, 100.0), rotary(y, pos, 100.0)
    # a chunk's rows at their absolute positions are the sequence's rows
    np.testing.assert_allclose(rotary(x[:, 5:9], pos[:, 5:9], 100.0),
                               rx[:, 5:9], rtol=1e-6)
    # q(t) . k(s) depends on t - s alone: shift both by 7 positions
    far = rotary(x, pos + 7, 100.0), rotary(y, pos + 7, 100.0)
    np.testing.assert_allclose(
        jnp.einsum("bthr,bsr->bhts", far[0], far[1]),
        jnp.einsum("bthr,bsr->bhts", rx, ry), atol=2e-5)
    # position 0 turns nothing; a norm is kept everywhere
    np.testing.assert_allclose(rx[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(rx, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_positions_handed_to_the_layer_are_absolute(net):
    eng = PagedDecodeEngine(net, **ENGINE)
    assert eng._extra == ("positions",) and eng._extra_paged == eng._extra
    lane = eng.acquire_lane(40)
    lt = eng.lane_tables
    lt.base[lane], lt.pos[lane] = 8, 19                   # a window that slid
    rel = np.array([lt.rel_pos(lane), 3], np.int32)
    (pos,) = eng._extra_args(eng._extra, np.array([lane, eng.lanes]), rel)
    assert pos.tolist() == [19, 3]                   # a padded slot: its rel


# -- the expert layer ---------------------------------------------------------

def expert_layer(**kw):
    layer = GatedMoELayer(n_in=32, n_out=32, d_hidden=24, d_shared=40,
                          n_experts=16, top_k=3, routed_scale=2.5,
                          activation="identity", **kw)
    return layer


def expert_params(layer, key=0):
    rng = np.random.default_rng(key)
    return {k: jnp.asarray(rng.normal(size=s) * (0.3 if len(s) > 1 else 0.05),
                           jnp.float32)
            for k, s in sorted(layer.param_shapes().items())}


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        reference):
    """Sixteen experts over four chips: each share routes over all 16 and
    computes its own four; their routed parts plus the shared expert,
    counted once, are the uncut layer, which is the reference's."""
    whole = expert_layer()
    params = expert_params(whole)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 9, 32)),
                    jnp.float32)
    uncut, st = whole.apply(params, x)
    shared = gated_ffn(x, params["sg"], params["su"], params["sd"])
    total, held = shared, 0
    for offset in range(0, 16, 4):
        share = expert_layer(experts_held=4, expert_offset=offset)
        own = dict(params, **{k: params[k][offset:offset + 4]
                              for k in ("wg", "wu", "wd")})
        out, s = share.apply(own, x)
        total = total + (out - shared)
        held += int(s["moe_stats"][0])
        assert int(s["moe_stats"][0]) + int(s["moe_stats"][1]) == 2 * 9 * 3
    assert held == 2 * 9 * 3 == int(st["moe_stats"][0])
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    cfg = {"n_routed_experts": 16, "num_experts_per_tok": 3,
           "routed_scaling_factor": 2.5}
    ref = reference.experts(params, x.reshape(18, 32), cfg=cfg, mode="f32")
    np.testing.assert_allclose(uncut.reshape(18, 32), ref, atol=2e-5)


@pytest.mark.parametrize("d,f", [(64, 48), (2304, 640)])
def test_gated_grouped_product_against_the_dense_oracle(d, f):
    """The interpreted kernel and the XLA ``while`` against every expert
    computing every token; at 2304 x 640 the kernel walks two chunks of
    ``D`` and five of ``F``. The kernel's grid walks the live tiles alone,
    so the two agree on those; what it leaves past them is never read."""
    if d > 64:
        assert grouped_ffn.gated_chunks(d, f) == (1152, 128)
    rng = np.random.default_rng(3)
    t, k, e_held, offset = 6, 2, 3, 2
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 8, (t, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    stacks = tuple(jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]), jnp.float32)
                   for s in ((e_held, d, f), (e_held, d, f), (e_held, f, d)))
    want = dense_expert_ffn(x, idx, w, *stacks, offset=offset)
    scale = float(jnp.abs(want).max())
    got, stats = sparse_expert_ffn(x, idx, w, *stacks, offset=offset,
                                   n_published=8)          # interpreted
    assert np.abs(got - want).max() < 1e-5 * scale
    held = int(((idx >= offset) & (idx < offset + e_held)).sum())
    assert (int(stats[0]), int(stats[1])) == (held, t * k - held)
    tiles = jnp.pad(x, ((0, 2), (0, 0))).reshape(4, 2, d)
    tile_e = jnp.asarray([0, 2, 1, 0], jnp.int32)
    n_tiles = jnp.int32(3)
    by_xla = grouped_ffn.tile_ffn_xla(tiles, tile_e, n_tiles, *stacks)
    by_kernel = grouped_ffn.tile_ffn_pallas(tiles, tile_e, n_tiles, *stacks,
                                            interpret=True)
    assert np.abs(by_kernel[:3] - by_xla[:3]).max() < 1e-5 * scale
    assert not np.asarray(by_xla[3]).any()                 # past n_tiles
    with poisoned_past_the_live_tiles():
        got, _ = sparse_expert_ffn(x, idx, w, *stacks, offset=offset,
                                   n_published=8)
    assert np.abs(got - want).max() < 1e-5 * scale
    hand = gated_ffn(tiles[1], *(s[2] for s in stacks))
    assert np.abs(by_kernel[1] - hand).max() < 1e-5 * scale
    # float32 is stated: bfloat16 rows are a hundred times beyond
    low = grouped_ffn.tile_ffn_xla(tiles.astype(jnp.bfloat16), tile_e,
                                   n_tiles, *stacks)
    assert np.abs(low - by_xla).max() > 1e-3 * scale


@contextlib.contextmanager
def poisoned_past_the_live_tiles():
    """``tile_ffn`` whose rows past ``n_tiles`` are NaN: what the gated
    kernel may leave there (the interpreter does)."""
    real = grouped_ffn.tile_ffn

    def poisoned(x_tiles, tile_e, n_tiles, *stacks):
        out = real(x_tiles, tile_e, n_tiles, *stacks)
        past = jnp.arange(out.shape[0]) >= n_tiles
        return jnp.where(past[:, None, None], jnp.nan, out)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(grouped_ffn, "tile_ffn", poisoned)
        yield


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
@pytest.mark.parametrize("offset", [2, 40])
def test_what_lies_past_the_live_tiles_is_never_read(gated, offset):
    """Tiles past ``n_tiles`` full of NaN, for gated and squared-ReLU
    stacks alike: ``sparse_expert_ffn`` still equals the dense oracle,
    absent pairs included (their indices are clamped onto some tile). At
    offset 40 every pair goes to an absent expert (``n_tiles`` 0): finite
    zeros, poisoned or through the interpreted kernel."""
    rng = np.random.default_rng(7)
    t, k, e_held, d, f = 7, 3, 3, 32, 24
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 8, (t, k)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    shapes = (((e_held, d, f), (e_held, d, f), (e_held, f, d)) if gated
              else ((e_held, d, f), (e_held, f, d)))
    stacks = tuple(jnp.asarray(rng.normal(size=s) / np.sqrt(s[1]),
                               jnp.float32) for s in shapes)
    want = dense_expert_ffn(x, idx, w, *stacks, offset=offset)
    with poisoned_past_the_live_tiles():
        got, stats = sparse_expert_ffn(x, idx, w, *stacks, offset=offset,
                                       n_published=8)
    held = int(((idx >= offset) & (idx < offset + e_held)).sum())
    assert (int(stats[0]), int(stats[1])) == (held, t * k - held)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(got - want).max() < 1e-5 * max(
        float(jnp.abs(want).max()), 1.0)
    if offset == 40:
        assert held == 0 and int(stats[2]) == 0
        assert not np.asarray(got).any()
        by_kernel, _ = sparse_expert_ffn(x, idx, w, *stacks, offset=offset,
                                         n_published=8)   # interpreted
        assert np.isfinite(np.asarray(by_kernel)).all()
        assert not np.asarray(by_kernel).any()


# -- prefix reuse under an expert layer ---------------------------------------

def drive(sched, prompt, n_new=3):
    """One request through the scheduler; the distribution its first token
    was drawn from and its tokens."""
    eng, rows = sched.engine, []
    real = eng.run

    def recording(*a, **kw):
        rows.append(real(*a, **kw))
        return rows[-1]

    eng.run = recording
    try:
        req = sched.submit(prompt, n_new)
        while not req.done:
            sched.step_once()
    finally:
        eng.run = real
    first = len(rows) - (n_new - 1)         # block_len 1: one run a token
    return rows[first - 1][0], list(req.tokens), req.prefix_covered_tokens


def pairs(eng):
    metric = eng.registry.get("moe_routed_pairs_total")
    return sum(s["value"] for s in metric.snapshot()["series"])


def test_a_prefix_hit_under_an_expert_layer_gives_the_misss_logits(net, ids):
    """A prompt of whole pages, sent twice: the second is covered whole and
    re-feeds its last token with the write dropped. The expert layers must
    compute that position (it is no padding) and count its pairs once."""
    prompt = ids[:24]                                       # 6 pages of 4
    eng = PagedDecodeEngine(net, **ENGINE, prefix_cache=True)
    assert eng._extra_paged == ("positions", "fed")
    sched = DecodeScheduler(eng, start_thread=False)
    miss, miss_tokens, covered = drive(sched, prompt)
    assert covered == 0
    before = pairs(eng)
    hit, hit_tokens, covered = drive(sched, prompt)
    assert covered == 24
    # the same position over the same cached rows: float32, one token's
    # sums in the order of a one-token step instead of a chunk's
    assert np.abs(np.log(hit) - np.log(miss)).max() < TOL
    assert hit_tokens == miss_tokens == generate(net, prompt, 3).tolist()
    # the re-fed position and two decode steps, two expert layers, 3 a token
    assert pairs(eng) - before == 3 * 2 * SMALL["num_experts_per_tok"]
    # taken for padding (no `fed`: the mask falls back to "write kept"),
    # the re-fed position skips its experts and the logits move
    eng2 = PagedDecodeEngine(net, **ENGINE, prefix_cache=True)
    eng2._extra_paged = eng2._extra
    sched2 = DecodeScheduler(eng2, start_thread=False)
    drive(sched2, prompt)
    before = pairs(eng2)
    wrong, _, covered = drive(sched2, prompt)
    assert covered == 24
    assert np.abs(np.log(wrong) - np.log(miss)).max() > 100 * TOL
    assert pairs(eng2) - before == 2 * 2 * SMALL["num_experts_per_tok"]


# -- the arena's one-pool entry, and what is refused --------------------------

def test_the_arena_gives_a_latent_vertex_one_pool():
    arena = PagedKVArena({"lat": PagedEntry((128,)),
                          "kv": PagedEntry((16, 16), 2)}, num_pages=6,
                         page_size=4)
    assert arena.k_pools[0].shape == (6, 4, 128)
    assert arena.v_pools[0] is None and arena.v_pools[1].shape == (6, 4, 16)
    assert arena.token_nbytes() == 128 * 4 + 2 * 16 * 4
    assert arena.nbytes() == 6 * 4 * arena.token_nbytes()
    arena.k_pools[0] = arena.k_pools[0].at[1, 2].set(1.0)
    arena.reset_pools()
    assert arena.v_pools[0] is None and not np.asarray(arena.k_pools[0]).any()
    with pytest.raises(ValueError, match="a latent row has no heads"):
        PagedKVArena({"lat": PagedEntry((128,))}, num_pages=6, page_size=4,
                     kv_dtype="int8")


def test_a_failed_dispatch_rebuilds_the_latent_pools(net, ids):
    eng = PagedDecodeEngine(net, **ENGINE, prefix_cache=True)
    sched = DecodeScheduler(eng, start_thread=False)
    drive(sched, ids[:24])
    assert eng.arena.prefix_index.cached_pages == 6
    assert np.asarray(eng.arena.k_pools[0]).any()

    def broken(params, k_pools, v_pools):
        raise RuntimeError("planted")

    with pytest.raises(RuntimeError, match="planted"):
        eng._dispatch("broken[S1]", broken, eng.arena, eng.net.params, (),
                      kind="paged")
    assert [p.shape for p in eng.arena.k_pools] == [(128, 4, 128)] * 3
    assert eng.arena.v_pools == [None] * 3
    assert not any(np.asarray(p).any() for p in eng.arena.k_pools)
    assert eng.arena.prefix_index.cached_pages == 0
    _, tokens, covered = drive(sched, ids[:24])             # serves on
    assert covered == 0 and tokens == generate(net, ids[:24], 3).tolist()


def test_what_the_engine_refuses_of_a_rotary_model(net):
    draft = ComputationGraph(transformer_lm(
        SMALL["vocab_size"], n_layers=1, d_model=16, n_heads=2, d_ff=32,
        input_ids=True, max_cache_t=WINDOW)).init()
    with pytest.raises(ValueError, match="view-relative positions only"):
        PagedDecodeEngine(net, **ENGINE, draft_net=draft)
    with pytest.raises(ValueError, match="a latent row has no heads"):
        PagedDecodeEngine(net, **ENGINE, kv_dtype="int8")
    sched = DecodeScheduler(PagedDecodeEngine(net, **ENGINE),
                            start_thread=False)
    with pytest.raises(ValueError, match="with every earlier position in "
                                         "view"):
        sched.submit(np.arange(100) % 7, 40)       # 140 > the window of 128
    gauge = sched.engine.registry.get("decode_kv_bytes_per_token")
    assert gauge.snapshot()["series"][0]["value"] == 3 * 128 * 4
    assert isinstance(net._vertex_layer("l0_attn"), MLAttentionLayer)
    assert _transformer.stateful_vertices(net, "positions") == [
        "l0_attn", "l1_attn", "l2_attn"]
