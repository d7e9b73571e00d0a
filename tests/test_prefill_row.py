"""A paged decode program returns the one row a lane that its caller names
(ISSUE 34): ``PagedDecodeEngine.run(..., out_rows)`` gives ``probs [B, V]``,
lane ``i``'s distribution at position ``out_rows[i]`` of its chunk, and the
head is computed at that position alone. Held four ways:

- the row equals that row of the same program with no row named (the
  all-positions form a verify pass uses), and the pools and the recurrent
  state come out as that form leaves them: a short last chunk, a full
  chunk, a one-token step, a bucket with a padded slot, a prefix-cache
  re-feed whose covered position writes nothing; for an OPT-like net and
  for the small hybrid net of ``test_nemotron_h.py``;
- the program's jaxpr holds no array of the shape ``[S, T, V]`` and its
  head's product has ``S`` rows (the all-positions form has both, so the
  walker sees them);
- the fused block and the verify program, which name no row, lower to the
  text they had before this argument existed;
- one dispatch adds ``B * V * itemsize`` to
  ``decode_d2h_bytes_total{kind="paged"}`` (and the six int32 of a net
  whose expert layers count).
"""

import hashlib

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models import transformer as _transformer
from deeplearning4j_tpu.models.transformer import (draft_transformer_lm,
                                                   transformer_lm)
from deeplearning4j_tpu.nn.conf.moe import MOE_STATS
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.serving.decode import PagedDecodeEngine
from test_nemotron_h import SEED, SMALL, all_positions, build_net, common
from test_pool_layout import _sub_jaxprs

VOCAB = 53              # no other axis of the OPT-like programs is 53 long
CHUNK = 16
WINDOW = 128
ENGINE = dict(max_batch=4, page_size=4, pages_per_seq=WINDOW // 4,
              prefill_chunk=CHUNK)
VOCABS = {"opt": VOCAB, "hybrid": SMALL["vocab_size"]}


@pytest.fixture(scope="module")
def nets():
    opt = ComputationGraph(transformer_lm(
        VOCAB, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=3,
        input_ids=True, max_cache_t=WINDOW)).init()
    hybrid = build_net(common.load_family(SMALL), SMALL, SEED,
                       max_cache_t=WINDOW)
    return {"opt": opt, "hybrid": hybrid}


# ---------------------------------------------------------------------------
# 1. the named row is that row of the all-positions forward
# ---------------------------------------------------------------------------


def pack(eng, work, t):
    """The scheduler's packing (``DecodeScheduler._compact`` and
    ``_prefill_chunk``) for ``work``, a list of ``(lane, tokens,
    n_dropped)``: a power-of-two bucket whose spare slots are padding;
    the first ``n_dropped`` positions of a lane are cache-resident and
    write nothing; the row named is the lane's last fed token."""
    b = 1
    while b < len(work):
        b <<= 1
    ids = np.zeros((b, t), np.int32)
    wslots = np.full((b, t), -1, np.int32)
    rel = np.zeros(b, np.int32)
    rows = np.zeros(b, np.int32)
    tables = np.full((b, eng.pages_per_seq), eng.arena.sentinel, np.int32)
    lanes = np.full(b, eng.lanes, np.int32)
    lt = eng.lane_tables
    for i, (lane, tokens, n_dropped) in enumerate(work):
        n = len(tokens)
        lt.ensure(lane, n)
        ids[i, :n] = tokens
        wslots[i, :n] = lt.rel_pos(lane) + np.arange(n)
        wslots[i, :n_dropped] = -1
        rel[i] = lt.rel_pos(lane)
        rows[i] = n - 1
        tables[i] = lt.tables[lane]
        lanes[i] = lane
    return ids, wslots, rel, tables, rows, lanes


def dispatch(eng, work, t):
    """One dispatch of ``work`` through ``eng.run``, held to the
    all-positions form on the same arena; returns the rows."""
    ids, wslots, rel, tables, rows, lanes = pack(eng, work, t)
    want, k_want, v_want = all_positions(eng, ids, wslots, rel, tables, lanes)
    assert want.shape[:2] == ids.shape
    got = eng.run(ids, wslots, rel, tables, rows, lanes)
    assert got.shape == (len(rel), want.shape[-1])
    for i, (lane, tokens, _) in enumerate(work):
        # the head's product has other row counts: rounding alone
        np.testing.assert_allclose(got[i], want[i, rows[i]], rtol=2e-6,
                                   atol=1e-9)
        assert np.argmax(got[i]) == np.argmax(want[i, rows[i]])
        eng.lane_tables.advance(lane, len(tokens))
    # every position's K/V, state and routing went where it always went
    for a, b in zip(
            jax.tree_util.tree_leaves((eng.arena.k_pools, eng.arena.v_pools)),
            jax.tree_util.tree_leaves((k_want, v_want))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return got


def short_last_chunk(eng, toks):
    lane = eng.acquire_lane(64)
    dispatch(eng, [(lane, toks(CHUNK), 0)], CHUNK)
    dispatch(eng, [(lane, toks(5), 0)], CHUNK)          # n = 5 < c = 16


def full_chunk(eng, toks):
    lane = eng.acquire_lane(64)
    dispatch(eng, [(lane, toks(CHUNK), 0)], CHUNK)
    dispatch(eng, [(lane, toks(CHUNK), 0)], CHUNK)      # on 16 tokens of context


def one_token_step(eng, toks):
    lane = eng.acquire_lane(64)
    dispatch(eng, [(lane, toks(11), 0)], CHUNK)
    for _ in range(3):                                  # the ticked rung
        dispatch(eng, [(lane, toks(1), 0)], 1)


def padded_slot(eng, toks):
    a, b, c = (eng.acquire_lane(64) for _ in range(3))
    dispatch(eng, [(a, toks(CHUNK), 0)], CHUNK)
    # three lanes in a bucket of four: rows 15, 6, 0 and a padded slot
    got = dispatch(eng, [(a, toks(CHUNK), 0), (b, toks(7), 0),
                         (c, toks(1), 0)], CHUNK)
    assert got.shape[0] == 4


def refeed_covered(eng, toks):
    """A prompt whose every page is cache-resident re-feeds its last token
    with the write dropped, here beside a lane that prefills: the re-fed
    lane's row is 0 and nothing of its shared page is written."""
    prompt = toks(CHUNK)
    first = eng.acquire_lane(CHUNK + 4, prompt=prompt)
    dispatch(eng, [(first, prompt, 0)], CHUNK)
    lt = eng.lane_tables
    assert lt.register_prefix(first, prompt) == CHUNK // eng.page_size
    eng.release_lane(first)
    hit = eng.acquire_lane(CHUNK + 4, prompt=prompt)
    assert lt.covered[hit] == CHUNK and lt.pos[hit] == CHUNK - 1
    other = eng.acquire_lane(64, prompt=toks(9))
    dispatch(eng, [(hit, prompt[-1:], 1), (other, toks(9), 0)], CHUNK)
    hit2 = eng.acquire_lane(CHUNK + 4, prompt=prompt)   # alone: the t = 1 form
    dispatch(eng, [(hit2, prompt[-1:], 1)], 1)


CASES = [("opt", short_last_chunk), ("opt", full_chunk),
         ("opt", one_token_step), ("opt", padded_slot),
         ("opt", refeed_covered),
         # (a net with recurrent state is refused a prefix cache)
         ("hybrid", short_last_chunk), ("hybrid", full_chunk),
         ("hybrid", one_token_step), ("hybrid", padded_slot)]


@pytest.mark.parametrize("family,case", CASES,
                         ids=[f"{f}-{c.__name__}" for f, c in CASES])
def test_run_returns_the_named_row_of_the_all_positions_forward(
        nets, family, case):
    eng = PagedDecodeEngine(nets[family], **ENGINE,
                            prefix_cache=case is refeed_covered)
    rng = np.random.default_rng(7)
    case(eng, lambda n: rng.integers(0, VOCABS[family], n, dtype=np.int32))


# ---------------------------------------------------------------------------
# 2. the program holds no [S, T, V], and its head's product has S rows
# ---------------------------------------------------------------------------


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def shapes_and_head_rows(jaxpr, v):
    """Every array shape of the program, and the row count (the product
    of the other axes) of each ``dot_general`` whose result is ``v``
    wide: the head's product."""
    shapes, head_rows = set(), []
    for eqn in _eqns(jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", None)
            if shape is not None:
                shapes.add(tuple(shape))
        if eqn.primitive.name == "dot_general":
            out = tuple(eqn.outvars[0].aval.shape)
            if out and out[-1] == v:
                head_rows.append(int(np.prod(out[:-1])))
    return shapes, head_rows


@pytest.fixture(scope="module")
def recorded(nets):
    """name -> (step, its arguments) of every program the warm-up of a
    fused engine and of a speculative engine dispatches (OPT-like net)."""
    seen = {}
    real = PagedDecodeEngine._dispatch

    def recording(self, name, step, arena, params, args, **kw):
        seen.setdefault(name, (step, (params, arena.k_pools, arena.v_pools,
                                      *args)))
        return real(self, name, step, arena, params, args, **kw)

    draft = ComputationGraph(draft_transformer_lm(
        VOCAB, d_model=8, n_heads=2, d_ff=16, seed=5,
        max_cache_t=WINDOW)).init()
    common_kw = dict(ENGINE, max_batch=2, prefix_cache=True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(PagedDecodeEngine, "_dispatch", recording)
        PagedDecodeEngine(nets["opt"], block_len=4, **common_kw).warmup()
        PagedDecodeEngine(nets["opt"], draft_net=draft, draft_k=2,
                          **common_kw).warmup()
    return seen


@pytest.mark.parametrize("program", ["paged_decode[S2xT16xP32]",
                                     "paged_decode[S2xT1xP32]",
                                     "draft_prefill[S2xT16xP32]"])
def test_the_program_holds_no_array_over_every_position(recorded, program):
    s, t = 2, int(program.split("xT")[1].split("x")[0])
    step, args = recorded[program]
    shapes, head_rows = shapes_and_head_rows(
        jax.make_jaxpr(step)(*args).jaxpr, VOCAB)
    assert head_rows == [s], head_rows
    assert (s, VOCAB) in shapes
    over_positions = {(s, t, VOCAB), (s * t, VOCAB)} - {(s, 1, VOCAB),
                                                        (s, VOCAB)}
    assert not over_positions & shapes
    assert not any(len(x) == 3 and x[-1] == VOCAB and x[1] > 1
                   for x in shapes)


def test_the_walker_sees_the_all_positions_form(nets, recorded):
    """The guard guards: with no row named, the same forward holds
    ``[S, T, V]`` and its head's product has ``S x T`` rows."""
    _, (params, k, v, ids, tables, wslots, rel, _rows) = recorded[
        "paged_decode[S2xT16xP32]"]
    jaxpr = jax.make_jaxpr(
        lambda *a: _transformer.paged_decode_forward(nets["opt"], *a))(
            params, k, v, ids, tables, wslots, rel).jaxpr
    shapes, head_rows = shapes_and_head_rows(jaxpr, VOCAB)
    assert head_rows == [2 * CHUNK] and (2, CHUNK, VOCAB) in shapes


# ---------------------------------------------------------------------------
# 3. the programs that name no row lower to the text they had
# ---------------------------------------------------------------------------

# sha256 of the lowered text on the commit before ISSUE 34 (37977e8), from
# this very fixture run on that checkout. A PR that means to change one of
# these programs replaces its digest; this PR must not.
PARENT_TEXT = {
    "fused_decode[S2xN4xP32]":
        "07ef1c771ac2d735313bdd5e0143b0033b26fe7e15264c1f71405012a73ded85",
    "spec_verify[S2xK2xP32]":
        "dea99889da15cbec38d2f9e69ca86e91f8bd7bb88687d500b2a59f04e0dcdb16",
    "spec_draft[S2xK2xP32]":
        "cca4afce2026abaa1014f9085044ad46639705726db6797b48eb3eb03e27595d",
    # the paged programs, on the commit before ISSUE 40 (184276e): the
    # read's trip follows the value width, and OPT's 64 keeps 128 keys
    "draft_prefill[S2xT16xP32]":
        "e6ba8741fbbf7e5641477a2b1a6f3f3a188ba9b148b99270e9de7be4b7a6b022",
    "paged_decode[S2xT16xP32]":
        "bd652579f0dfb776e6e5d6508d6cd87d6e8f7bc95612c0130f6cbb0097d637f4",
    "paged_decode[S2xT1xP32]":
        "e37fd422415ea27593f0afbfddb008ec5d06ed36728123af762e72f0f60118f5",
}


@pytest.mark.parametrize("program", sorted(PARENT_TEXT))
def test_a_program_that_names_no_row_lowers_to_the_parents_text(
        recorded, program):
    step, args = recorded[program]
    text = jax.jit(step, donate_argnums=(1, 2)).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[program]


# ---------------------------------------------------------------------------
# 4. what a dispatch brings to the host
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["opt", "hybrid"])
def test_a_paged_dispatch_fetches_one_row_a_lane(nets, family):
    eng = PagedDecodeEngine(nets[family], **ENGINE, prefix_cache=False)
    d2h = eng.registry.get("decode_d2h_bytes_total")
    rng = np.random.default_rng(3)
    lanes = [eng.acquire_lane(64) for _ in range(3)]
    work = [(lane, rng.integers(0, VOCABS[family], n, dtype=np.int32), 0)
            for lane, n in zip(lanes, (CHUNK, 7, 2))]
    got = dispatch(eng, work, CHUNK)                    # a bucket of 4
    counts = len(MOE_STATS) * 4 if family == "hybrid" else 0
    assert d2h.value(kind="paged") == \
        4 * VOCABS[family] * got.dtype.itemsize + counts
