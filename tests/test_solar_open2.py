"""The ``solar_open2`` hybrid decoder (``models/solar_open2.py``) and its
two new parts, Kimi delta attention (``nn/conf/kda.py``) and the gated
grouped-query layer with heads of their own width, through the program's
normal paths at a small size, against the plain reference
(``benchmarks/configs/solar-open2-250b.reference.py``, which imports nothing
of the program): the chunked form against the recurrence (across chunk
edges and at decays no float32 factorisation survives), prefill in chunks
then decode through the paged engine against the reference's full forward,
``generate()`` against the engine, the per-lane state rules, the expert
shares against the uncut layer, the attention layer's new widths and gate,
and what the engine refuses.

The small configuration's KDA layers get ``A_log`` and ``dt_bias`` that
make the decay keep thousands of tokens (the benchmark's draws keep a few),
so that a state carried wrongly anywhere shows in the logits."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.models import (generate, nemotron_h_lm,
                                       transformer_lm)
from deeplearning4j_tpu.models import transformer as _transformer
from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.kda import KimiDeltaAttention, _decayed_pairs
from deeplearning4j_tpu.nn.conf.layers import gated_ffn
from deeplearning4j_tpu.nn.conf.moe import GatedMoELayer
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from lib import common, weights  # noqa: E402
from lib.train_cell import build_net  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "configs",
                       "solar-open2-small.json")) as _f:
    SMALL = json.load(_f)
SEED = 2147483659
WINDOW = 256
ENGINE = dict(max_batch=4, page_size=4, pages_per_seq=WINDOW // 4,
              prefill_chunk=16)
POL = dtypes.FLOAT32


def slow_decay(flat: dict) -> dict:
    """``A_log`` near -4 and ``dt_bias`` near -4: ``g`` near -exp(-4) x
    softplus(-4), some -3e-4 a token."""
    return {k: (v - 4.0 if k.endswith((".A_log", ".dt_bias")) else v)
            for k, v in flat.items()}


@pytest.fixture(scope="module")
def family():
    return common.load_family(SMALL)


@pytest.fixture(scope="module")
def reference():
    return common.load_reference(SMALL)


@pytest.fixture(scope="module")
def flat(family):
    return slow_decay(weights.make_weights(family, SMALL, SEED))


@pytest.fixture(scope="module")
def net(family, flat):
    net = build_net(family, SMALL, SEED, max_cache_t=WINDOW)
    like = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), net.params)
    net.params = weights.to_program(family, SMALL, flat, like)
    return net


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(5).integers(0, SMALL["vocab_size"], 160,
                                             dtype=np.int32)


# -- (a) the chunked form is the recurrence -------------------------------------

def kda_layer():
    layer = KimiDeltaAttention(n_heads=2, head_dim=8, gate_rank=4,
                               activation="identity")
    layer.set_n_in(InputType.recurrent(16))
    return layer


def token_by_token(layer, params, x):
    h, c = layer._zero_state(x.shape[0], POL)
    state, outs = {"h": h, "c": c}, []
    for t in range(x.shape[1]):
        out, state = layer.apply(params, x[:, t:t + 1], state=state,
                                 policy=POL)
        outs.append(out)
    return jnp.concatenate(outs, 1), state


@pytest.mark.parametrize("steep", [False, True])
def test_chunked_form_is_the_recurrence(steep):
    """150 positions in chunks of 64 (two edges and a padded tail) against
    150 recurrence steps; ``steep``: log-decays of about -60 a token and
    channel, so a chunk's sum passes -88 within two positions and the
    factorised form's ``exp(-G)`` would overflow float32."""
    layer = kda_layer()
    params = layer.init_params(jax.random.PRNGKey(0), POL)
    if steep:
        params = dict(params, A_log=jnp.full((2,), 3.0),
                      dt_bias=jnp.full((16,), 3.0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 150, 16))
    chunked, _ = layer.apply(params, x, policy=POL)
    stepped, state = token_by_token(layer, params, x)
    assert bool(jnp.isfinite(chunked).all())
    np.testing.assert_allclose(chunked, stepped, atol=2e-5, rtol=1e-4)
    # the streaming carry takes a chunk too: 80, then 70, as one call of 150
    h, c = layer._zero_state(2, POL)
    first, st = layer.apply(params, x[:, :80], state={"h": h, "c": c},
                            policy=POL)
    rest, st = layer.apply(params, x[:, 80:], state=st, policy=POL)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), stepped,
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(st["c"], state["c"], atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("inclusive", [False, True])
def test_decayed_pairs_by_brute_force(inclusive):
    """The recursive halving against the ``[L, L, D]`` sum it avoids, at
    decays mild enough for the brute force to hold them."""
    key = jax.random.PRNGKey(3)
    a, b = jax.random.normal(key, (2, 3, 16, 8))
    gam = jnp.cumsum(-jax.random.uniform(jax.random.fold_in(key, 1),
                                         (3, 16, 8)), axis=1)
    got = _decayed_pairs(a, b, gam, inclusive=inclusive)
    full = jnp.einsum("ntc,nsc,ntsc->nts", a, b,
                      jnp.exp(gam[:, :, None] - gam[:, None]))
    keep = jnp.tril(jnp.ones((16, 16), bool), 0 if inclusive else -1)
    np.testing.assert_allclose(got, jnp.where(keep, full, 0.0), atol=1e-5)


# -- (b) prefill then decode through the engine is the reference -----------------

def reference_logprobs(reference, flat, ids):
    z = reference.logits_at(flat, ids, np.arange(len(ids)), cfg=SMALL,
                            mode="f32")
    return np.asarray(jax.nn.log_softmax(z, axis=-1))


def all_positions(eng, ids, slots, rel, tables, lanes):
    """The engine's program with no row named (``out_rows=None``): the
    distribution at every position. The arena is read, not donated and not
    written: ``eng.run`` on the same arguments comes after."""
    fwd = getattr(eng, "_all_positions", None)
    if fwd is None:
        fwd = eng._all_positions = jax.jit(
            lambda params, k, v, *args, **extra:
            _transformer.paged_decode_forward(eng.net, params, k, v, *args,
                                              **extra))
    probs, _, _ = fwd(
        eng.net.params, eng.arena.k_pools, eng.arena.v_pools, ids, tables,
        slots, rel, **dict(zip(eng._extra,
                               eng._extra_args(eng._extra, lanes, rel))))
    return np.asarray(probs)


def engine_logprobs(net, ids, n_prefill, chunk=16):
    """Teacher-forced through the paged engine: the first ``n_prefill``
    tokens in prefill chunks of ``chunk`` (the last one partly padding),
    the rest as one-token steps."""
    eng = PagedDecodeEngine(net, **dict(ENGINE, prefill_chunk=chunk))
    lane, lt = eng.acquire_lane(len(ids) + 1), eng.lane_tables
    out, pos = [], 0
    while pos < len(ids):
        t = chunk if pos < n_prefill else 1
        n = min(t, n_prefill - pos) if pos < n_prefill else 1
        lt.ensure(lane, n)
        fed = np.zeros((1, t), np.int32)
        fed[0, :n] = ids[pos:pos + n]
        slots = np.full((1, t), -1, np.int32)
        slots[0, :n] = lt.rel_pos(lane) + np.arange(n)
        args = (fed, slots, np.array([lt.rel_pos(lane)], np.int32),
                lt.tables[lane][None])
        lanes = np.array([lane], np.int32)
        probs = all_positions(eng, *args, lanes)
        row = eng.run(*args, np.array([n - 1], np.int32), lanes)
        np.testing.assert_allclose(row[0], probs[0, n - 1], rtol=1e-5)
        out.append(np.asarray(probs[0, :n], np.float32))
        lt.advance(lane, n)
        pos += n
    return np.log(np.concatenate(out)), eng


def test_full_forward_is_the_reference(net, reference, flat, ids):
    want = reference_logprobs(reference, flat, ids)
    got = np.log(np.asarray(net.output(ids[None, :, None])[0], np.float32))
    # float32 on both sides, sums in another order (chunked form, sorted
    # dispatch, fused products): rounding alone
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("n_ids, n_prefill, chunk", [
    (64, 45, 16),        # prefill dispatches inside one chunk of the layer
    (160, 150, 128)])    # dispatches of two of the layer's chunks of 64
def test_chunked_prefill_then_decode_through_the_engine_is_the_reference(
        net, reference, flat, ids, n_ids, n_prefill, chunk):
    ids = ids[:n_ids]
    want = reference_logprobs(reference, flat, ids)
    got, eng = engine_logprobs(net, ids, n_prefill, chunk)
    np.testing.assert_allclose(got, want, atol=1e-4)
    series = {m: eng.registry.get(m).snapshot()["series"] for m in (
        "kda_positions_total", "kda_state_bytes_total",
        "decode_state_bytes")}
    pos = {(s["labels"]["form"], s["labels"]["kind"]): s["value"]
           for s in series["kda_positions_total"]}
    # 3 KDA layers: the prompt in whole dispatches of ``chunk`` (the last
    # one padded), then one-token steps
    dispatches = -(-n_prefill // chunk)
    steps = n_ids - n_prefill
    assert pos == {("chunked", "valid"): 3 * n_prefill,
                   ("chunked", "pad"): 3 * (dispatches * chunk - n_prefill),
                   ("step", "valid"): 3 * steps, ("step", "pad"): 0}
    lane = 4 * (3 * 3 * 64 + 4 * 16 * 16)     # tail + state, float32, a layer
    assert series["decode_state_bytes"][0]["value"] == 3 * 4 * lane
    # read and written, 3 layers, one real lane, every dispatch
    assert series["kda_state_bytes_total"][0]["value"] == \
        2 * 3 * (dispatches + steps) * lane


# -- (c) generate() is the engine, ticked and fused -----------------------------

def serve(net, prompts, n_new, **engine):
    eng = PagedDecodeEngine(net, **dict(ENGINE, **engine))
    sched = DecodeScheduler(eng, start_thread=False)
    reqs = [sched.submit(p, n) for p, n in zip(prompts, n_new)]
    for _ in range(10_000):
        if all(r.done for r in reqs):
            break
        sched.step_once()
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    return [np.asarray(r.tokens) for r in reqs], eng


def test_generate_is_the_engine_ticked_and_fused(net, ids):
    """Five requests over four lanes (one lane given twice: its second
    owner starts from zero state), prompts across several prefill chunks
    and buckets of 1, 2 and 4 lanes, lanes retired inside fused blocks; the
    first prompt crosses two of the full-sequence form's chunk edges in
    ``generate()``."""
    prompts = [ids[:150], ids[10:17], ids[30:63], ids[:19], ids[5:6]]
    n_new = [12, 9, 7, 12, 10]
    oracle = [generate(net, p, n) for p, n in zip(prompts, n_new)]
    ticked, _ = serve(net, prompts, n_new, block_len=1)
    fused, eng = serve(net, prompts, n_new, block_len=4)
    for want, a, b in zip(oracle, ticked, fused):
        np.testing.assert_array_equal(a, want)
        np.testing.assert_array_equal(b, want)
    resets = eng.registry.get("decode_state_resets_total").snapshot()
    assert resets["series"][0]["value"] == 5
    traces = {s["labels"]["fn"] for s in eng.registry.get(
        "jit_retraces_total").snapshot()["series"]}
    assert any(k.startswith("fused_decode[S4xN4") for k in traces)


# -- (d) the per-lane state rules -------------------------------------------------

def test_a_lane_without_a_valid_position_keeps_its_state_bit_for_bit():
    layer = kda_layer()
    params = layer.init_params(jax.random.PRNGKey(5), POL)
    key = jax.random.PRNGKey(6)
    tail_shape, state_shape = layer.state_shapes(3)
    tail = jax.random.normal(key, tail_shape)
    state = jax.random.normal(jax.random.fold_in(key, 1), state_shape)
    x = jax.random.normal(jax.random.fold_in(key, 2), (3, 5, 16))
    lanes = jnp.array([0, 2, 3], jnp.int32)         # 3: a padded slot
    valid = jnp.array([[False] * 5, [True] * 3 + [False] * 2, [True] * 5])
    fresh = jnp.array([False, True, False])
    _, tail1, state1 = layer.apply_paged(params, x, tail, state, lanes,
                                         valid, fresh, policy=POL)
    # lane 0 had no valid position, lane 1 was not in the dispatch
    for lane in (0, 1):
        assert np.array_equal(np.asarray(tail1[lane]), np.asarray(tail[lane]))
        assert np.array_equal(np.asarray(state1[lane]),
                              np.asarray(state[lane]))
    # lane 2 started fresh: what three positions give from zero state
    h, c = layer._zero_state(1, POL)
    _, st = layer.apply(params, x[1:2, :3], state={"h": h, "c": c},
                        policy=POL)
    np.testing.assert_allclose(state1[2], st["c"][0], atol=1e-6)
    np.testing.assert_allclose(tail1[2], st["h"][0], atol=1e-6)


# -- (e) the expert shares add up to the uncut layer -----------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """16 experts, 3 a token, held 2 a chip by 8 chips: each share's output
    is its routed part plus the shared expert; the shares' routed parts and
    the shared expert counted once are the uncut layer's output."""
    def layer(held=None, offset=0):
        moe = GatedMoELayer(n_in=16, n_out=16, d_hidden=8, d_shared=8,
                            n_experts=16, experts_held=held,
                            expert_offset=offset, top_k=3,
                            activation="identity")
        moe.set_n_in(InputType.recurrent(16))
        return moe
    whole = layer()
    params = whole.init_params(jax.random.PRNGKey(7), POL)
    params["e_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (16,))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 11, 16))
    want, _ = whole.apply(params, x, policy=POL)
    shared = gated_ffn(x, params["sg"], params["su"], params["sd"])
    total = shared
    for chip in range(8):
        own = dict(params, **{k: params[k][2 * chip:2 * chip + 2]
                              for k in ("wg", "wu", "wd")})
        out, _ = layer(2, 2 * chip).apply(own, x, policy=POL)
        total = total + (out - shared)
    np.testing.assert_allclose(total, want, atol=1e-5)


# -- (f) the attention layer's head width and gate -------------------------------

def wide_gated_layer(max_cache_t=None):
    layer = SelfAttentionLayer(n_heads=4, n_kv_heads=2, head_size=12,
                               output_gate=True, has_bias=False, causal=True,
                               activation="identity", max_cache_t=max_cache_t)
    layer.set_n_in(InputType.recurrent(20))     # 20 is no multiple of 4 x 12
    return layer


def test_head_width_and_gate_dense_streaming_paged_against_the_textbook():
    from deeplearning4j_tpu.serving.kv_cache import PagedKVArena
    layer = wide_gated_layer(max_cache_t=32)
    key = jax.random.PRNGKey(11)
    params = layer.init_params(key, POL)
    assert {k: v.shape for k, v in params.items()} == {
        "Wqkv": (20, (4 + 2 * 2) * 12), "Wgate": (20, 48), "Wo": (48, 20)}
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 20, 20))
    dense, _ = wide_gated_layer().apply(params, x, policy=POL)
    # by hand: query head i reads K/V head i // 2; the heads' output gated
    q, k, v = jnp.split(x @ params["Wqkv"], [48, 72], -1)
    q = q.reshape(2, 20, 4, 12)
    k = jnp.repeat(k.reshape(2, 20, 2, 12), 2, 2)
    v = jnp.repeat(v.reshape(2, 20, 2, 12), 2, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(12.0)
    s = jnp.where(jnp.tril(jnp.ones((20, 20), bool)), s, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    want = (att.reshape(2, 20, 48) * jax.nn.sigmoid(x @ params["Wgate"])
            ) @ params["Wo"]
    np.testing.assert_allclose(dense, want, atol=1e-5)
    # streaming: 12 at once, then token by token
    h, c = layer._zero_state(2, POL)
    out, st = layer.apply(params, x[:, :12], state={"h": h, "c": c},
                          policy=POL)
    outs = [out]
    for t in range(12, 20):
        out, st = layer.apply(params, x[:, t:t + 1], state=st, policy=POL)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=1e-5)
    # paged: a chunk of 12, then one-token steps, pools kv x d wide
    arena = PagedKVArena({"a": layer.paged_entry(2)}, num_pages=16,
                         page_size=4)
    tables = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    kp, vp = arena.k_pools[0], arena.v_pools[0]
    slots = jnp.tile(jnp.arange(12, dtype=jnp.int32), (2, 1))
    out, kp, vp = layer.apply_paged(params, x[:, :12], kp, vp, tables, slots,
                                    jnp.zeros(2, jnp.int32), policy=POL)
    outs = [out]
    for t in range(12, 20):
        out, kp, vp = layer.apply_paged(
            params, x[:, t:t + 1], kp, vp, tables,
            jnp.full((2, 1), t, jnp.int32), jnp.full(2, t, jnp.int32),
            policy=POL)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=1e-5)


def attention_shapes(net):
    return {name: {k: tuple(v.shape) for k, v in net.params[name].items()}
            for name in _transformer.attention_vertices(net)}


def test_opt_and_the_hybrid_keep_their_attention_parameters():
    opt = ComputationGraph(transformer_lm(64, n_layers=1, d_model=32,
                                          n_heads=4, d_ff=64,
                                          input_ids=True)).init()
    assert attention_shapes(opt) == {
        "blk0_attn": {"Wqkv": (32, 96), "Wo": (32, 32), "b": (32,)}}
    hybrid = ComputationGraph(nemotron_h_lm(
        32, pattern="M*E", d_model=16, n_heads=2, n_kv_heads=1,
        mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=4,
        n_experts=4, top_k=2, d_latent=8, d_expert=8, d_shared=8)).init()
    assert attention_shapes(hybrid) == {
        "l1_mix": {"Wqkv": (16, 32), "Wo": (16, 16)}}
    assert _transformer.stateful_vertices(hybrid, "lane_ids") == ["l0_mix"]
    assert _transformer.kda_vertices(hybrid) == []
    with pytest.raises(ValueError, match="n_in=30 not divisible"):
        SelfAttentionLayer(n_heads=4).set_n_in(InputType.recurrent(30))


# -- (g) what the engine refuses ---------------------------------------------------

def test_what_the_engine_refuses_for_a_kda_net(net):
    assert _transformer.kda_vertices(net) == ["l1_mix", "l2_mix", "l3_mix"]
    assert _transformer.stateful_vertices(net, "lane_ids") == \
        _transformer.kda_vertices(net)
    with pytest.raises(ValueError, match="Kimi delta attention vertices.*"
                                         "recurrent state after that prefix "
                                         "is kept nowhere"):
        PagedDecodeEngine(net, **ENGINE, prefix_cache=True)
    draft = ComputationGraph(transformer_lm(
        SMALL["vocab_size"], n_layers=1, d_model=16, n_heads=2, d_ff=32,
        input_ids=True, max_cache_t=WINDOW)).init()
    with pytest.raises(ValueError, match="Kimi delta attention vertices.*"
                                         "cannot be rolled back"):
        PagedDecodeEngine(net, **ENGINE, draft_net=draft)
    sched = DecodeScheduler(PagedDecodeEngine(net, **ENGINE),
                            start_thread=False)
    with pytest.raises(ValueError, match="has no window"):
        sched.submit(np.arange(230) % 7, 40)       # 270 > the window of 256
