"""The ``nemotron_h`` hybrid decoder (``models/nemotron_h.py``) through the
program's normal paths, at a small size, against its plain reference
(``benchmarks/configs/nemotron-3-super-120b-a12b.reference.py``, which
imports nothing of the program): the full forward, prefill in chunks then
decode through the paged engine, the fused block against ticked steps and
``generate()``, grouped K/V heads on the three attention paths, the trace
ladder under churn, and what the engine refuses."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import generate, nemotron_h_lm, transformer_lm
from deeplearning4j_tpu.models import transformer as _transformer
from deeplearning4j_tpu.nn.conf.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.serving.decode import (DecodeScheduler,
                                               PagedDecodeEngine)
from deeplearning4j_tpu.util import metrics as _metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from lib import common, weights  # noqa: E402
from lib.train_cell import build_net  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "tests", "data", "configs",
                       "nemotron_h-small.json")) as _f:
    SMALL = json.load(_f)
BF16 = dict(SMALL, dtype="stored_bf16", param_dtype="bfloat16")
SEED = 2147483659
WINDOW = 128
ENGINE = dict(max_batch=4, page_size=4, pages_per_seq=WINDOW // 4,
              prefill_chunk=8)


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
    return np.random.default_rng(5).integers(0, SMALL["vocab_size"], 64,
                                             dtype=np.int32)


def reference_logprobs(reference, family, cfg, ids):
    flat = weights.make_weights(family, cfg, SEED)
    z = reference.logits_at(flat, ids, np.arange(len(ids)), cfg=cfg,
                            mode="f32", q_block=len(ids))
    return np.asarray(jax.nn.log_softmax(z, axis=-1))


def all_positions(eng, ids, slots, rel, tables, lanes=None):
    """The engine's program with no row named (``out_rows=None``, the form
    a verify pass uses): the distribution at every position, and the two
    state lists as this dispatch would leave them. The arena is read, not
    donated and not written: ``eng.run`` on the same arguments comes
    after, and has to find what this call found."""
    fwd = getattr(eng, "_all_positions", None)
    if fwd is None:                 # one jit an engine: its shapes' cache
        fwd = eng._all_positions = jax.jit(
            lambda params, k, v, *args, **extra:
            _transformer.paged_decode_forward(eng.net, params, k, v, *args,
                                              **extra))
    probs, k_pools, v_pools = fwd(
        eng.net.params, eng.arena.k_pools, eng.arena.v_pools, ids, tables,
        slots, rel, **dict(zip(eng._extra,
                               eng._extra_args(eng._extra, lanes, rel))))
    return np.asarray(probs), k_pools, v_pools


def engine_logprobs(net, ids, n_prefill, chunk=8):
    """Teacher-forced through the paged engine: the first ``n_prefill``
    tokens in prefill chunks (the last one partly padding), the rest as
    one-token steps; log-probabilities at every position. A dispatch
    returns one row a lane (the chunk's last token here), so the other
    positions come from the same program with no row named, and the row
    the engine did return is held to it."""
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
        probs, _, _ = all_positions(eng, *args, np.array([lane], np.int32))
        row = eng.run(*args, np.array([n - 1], np.int32),
                      np.array([lane], np.int32))
        np.testing.assert_allclose(row[0], probs[0, n - 1], rtol=1e-5)
        out.append(np.asarray(probs[0, :n], np.float32))
        lt.advance(lane, n)
        pos += n
    return np.log(np.concatenate(out)), eng


def test_full_forward_is_the_reference(net, reference, family, ids):
    want = reference_logprobs(reference, family, SMALL, ids)
    got = np.log(np.asarray(net.output(ids[None, :, None])[0], np.float32))
    # float32 on both sides, sums in another order (chunked scan, sorted
    # dispatch, fused products): rounding alone
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_chunked_prefill_then_decode_through_the_engine_is_the_reference(
        net, reference, family, ids):
    want = reference_logprobs(reference, family, SMALL, ids)
    got, eng = engine_logprobs(net, ids, n_prefill=29)   # 3 chunks + 5 of 8
    np.testing.assert_allclose(got, want, atol=1e-4)
    snap = {m: eng.registry.get(m).snapshot()["series"] for m in (
        "moe_routed_pairs_total", "moe_computed_pairs_total",
        "decode_state_resets_total", "decode_state_bytes")}
    routed = {s["labels"]["where"]: s["value"]
              for s in snap["moe_routed_pairs_total"]}
    # 2 expert layers x 64 tokens x 3 experts a token, padding not counted
    assert routed["held"] + routed["absent"] == 2 * 64 * 3
    assert snap["moe_computed_pairs_total"][0]["value"] >= routed["held"]
    assert snap["decode_state_resets_total"][0]["value"] == 1
    # 2 Mamba layers x 4 lanes x (4 x 32 x 16 + 3 x (128 + 2 x 2 x 16)) x 4 B
    assert snap["decode_state_bytes"][0]["value"] == \
        2 * 4 * (4 * 32 * 16 + 3 * 192) * 4


def test_stored_bf16_against_the_float32_reference(reference, family, ids):
    """Stored-bf16 parameters, bf16 operands: each product rounds its
    operands to 8 bits of mantissa (relative 2^-9), five layers deep, so
    log-probabilities of size 6 move by hundredths; and where two router
    scores lie closer than the rounding of their input the program and the
    reference keep different experts, both rightly, which moves that
    position by a whole expert: the median is held tight and the worst
    position loosely."""
    net = build_net(family, BF16, SEED, max_cache_t=WINDOW)
    assert {a.dtype for a in jax.tree_util.tree_leaves(net.params)} == {
        jnp.dtype(jnp.bfloat16)}
    want = reference_logprobs(reference, family, BF16, ids)
    got, eng = engine_logprobs(net, ids, n_prefill=29)
    err = np.abs(got - want).max(axis=-1)
    assert np.median(err) < 0.05 and err.max() < 0.5, (np.median(err),
                                                       err.max())
    assert all(p.dtype == jnp.float32
               for p in eng.arena.k_pools + eng.arena.v_pools)


def serve(net, prompts, n_new, **engine):
    eng = PagedDecodeEngine(net, **dict(ENGINE, **engine))
    sched = DecodeScheduler(eng, start_thread=False)
    reqs = [sched.submit(p, n) for p, n in zip(prompts, n_new)]
    for _ in range(10_000):
        if all(r.done for r in reqs):
            break
        sched.step_once()
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    return [np.asarray(r.tokens) for r in reqs], eng, sched


def test_fused_block_is_ticked_steps_is_generate(net, ids):
    prompts = [ids[:19], ids[10:17], ids[30:63], ids[:19], ids[5:6]]
    n_new = [12, 9, 7, 12, 10]
    oracle = [generate(net, p, n) for p, n in zip(prompts, n_new)]
    ticked, _, _ = serve(net, prompts, n_new, block_len=1)
    fused, eng, _ = serve(net, prompts, n_new, block_len=4)
    for want, a, b in zip(oracle, ticked, fused):
        np.testing.assert_array_equal(a, want)
        np.testing.assert_array_equal(b, want)
    # five requests over four lanes: one lane was given twice, and its
    # second owner (from zero state) still matched the oracle above
    resets = eng.registry.get("decode_state_resets_total").snapshot()
    assert resets["series"][0]["value"] == 5


def test_one_retrace_a_bucket_under_churn(net, ids):
    rng = np.random.default_rng(11)
    prompts = [ids[a:a + n] for a, n in zip(rng.integers(0, 30, 9),
                                            rng.integers(1, 30, 9))]
    _, eng, _ = serve(net, prompts, list(rng.integers(2, 12, 9)),
                      block_len=4)
    traces = {s["labels"]["fn"]: s["value"] for s in eng.registry.get(
        "jit_retraces_total").snapshot()["series"]}
    assert traces and set(traces.values()) == {1.0}
    assert any(k.startswith("fused_decode[S4xN4") for k in traces)
    assert any(k.startswith("paged_decode[S1xT8") for k in traces)


def test_what_the_engine_refuses(net, family):
    with pytest.raises(ValueError, match="recurrent state after that "
                                         "prefix is kept nowhere"):
        PagedDecodeEngine(net, **ENGINE, prefix_cache=True)
    draft = ComputationGraph(transformer_lm(
        SMALL["vocab_size"], n_layers=1, d_model=16, n_heads=2, d_ff=32,
        input_ids=True, max_cache_t=WINDOW)).init()
    with pytest.raises(ValueError, match="cannot be rolled back"):
        PagedDecodeEngine(net, **ENGINE, draft_net=draft)
    sched = DecodeScheduler(PagedDecodeEngine(net, **ENGINE),
                            start_thread=False)
    with pytest.raises(ValueError, match="has no window"):
        sched.submit(np.arange(100) % 7, 40)       # 140 > the window of 128
    # an expert layer over an attention-only net takes the prefix cache
    # since the walker tells "computed" from "write kept" (tests/test_pangu)
    moe_only = ComputationGraph(nemotron_h_lm(
        32, pattern="*E", d_model=16, n_heads=2, n_kv_heads=1,
        mamba_heads=2, mamba_head_dim=8, mamba_groups=1, state_size=4,
        n_experts=4, top_k=2, d_latent=8, d_expert=8, d_shared=8,
        max_cache_t=WINDOW)).init()
    eng = PagedDecodeEngine(moe_only, **ENGINE, prefix_cache=True)
    assert eng._extra == () and eng._extra_paged == ("fed",)
    # and an LSTM's carry still has no arena: the message names what has
    from deeplearning4j_tpu.models import char_rnn_lstm
    with pytest.raises(ValueError):
        PagedDecodeEngine(char_rnn_lstm(8, hidden=8), **ENGINE)
    with pytest.raises(ValueError, match="kinds"):
        nemotron_h_lm(8, pattern="MXE", d_model=8, n_heads=1, n_kv_heads=1,
                      mamba_heads=1, mamba_head_dim=8, mamba_groups=1,
                      state_size=4, n_experts=2, top_k=1, d_latent=4,
                      d_expert=4, d_shared=4)


def test_failed_dispatch_rebuilds_the_state_arrays_too(net, ids, monkeypatch):
    eng = PagedDecodeEngine(net, **ENGINE)
    lane = eng.acquire_lane(20)
    eng.lane_tables.ensure(lane, 8)
    args = (ids[None, :8], np.arange(8, dtype=np.int32)[None],
            np.zeros(1, np.int32), eng.lane_tables.tables[lane][None],
            np.full(1, 7, np.int32), np.array([lane], np.int32))
    eng.run(*args)
    owners = _transformer.stateful_vertices(net)
    mamba = [i for i, n in enumerate(owners) if n in eng.state_layers]
    assert len(mamba) == 2 and len(owners) == 3
    assert np.asarray(eng.arena.v_pools[mamba[0]][lane]).any()
    shapes = [tuple(p.shape) for p in eng.arena.k_pools + eng.arena.v_pools]

    def boom(*a, **k):
        raise RuntimeError("planted")
    monkeypatch.setattr(_transformer, "paged_decode_forward", boom)
    eng._jit_cache.clear()
    with pytest.raises(RuntimeError, match="planted"):
        eng.run(*args)
    assert [tuple(p.shape) for p in eng.arena.k_pools
            + eng.arena.v_pools] == shapes
    assert not np.asarray(eng.arena.v_pools[mamba[0]]).any()


def test_a_stopped_server_is_collectable_while_its_registry_lives(net):
    """The benchmark keeps the server's registry (its counters are read at
    the window's edges) and then needs the chip for the reference: at 8.66
    GiB of parameters there is no room for both, so nothing the registry
    holds may keep the server, the engine or the net alive."""
    import gc
    import weakref
    from deeplearning4j_tpu.serving import InferenceServer
    small = ComputationGraph(net.conf).init()
    server = InferenceServer(small, decode=dict(ENGINE, block_len=4,
                                                  prefix_cache=False))
    req = server.decode.submit(np.arange(1, 9), 6)
    assert req.wait(120) and len(req.tokens) == 6
    registry = server.registry
    refs = [weakref.ref(o) for o in (server, server.decode,
                                     server.decode.engine, small)]
    server.stop(drain=False)
    del server, small, req
    gc.collect()
    assert [r() for r in refs] == [None] * 4
    assert registry.get("decode_tokens_total") is not None
    registry.expose()           # dead gauges drop out, nothing raises


# -- grouped K/V heads ---------------------------------------------------------

def gqa_layer(kv, max_cache_t=None):
    layer = SelfAttentionLayer(n_heads=4, n_kv_heads=kv, has_bias=False,
                               causal=True, activation="identity",
                               max_cache_t=max_cache_t)
    layer.set_n_in(InputType.recurrent(32))
    return layer


@pytest.mark.parametrize("kv", [1, 2])
def test_grouped_kv_heads_paged_is_streaming_is_dense(kv):
    from deeplearning4j_tpu import dtypes
    from deeplearning4j_tpu.serving.kv_cache import PagedKVArena
    pol = dtypes.FLOAT32
    layer = gqa_layer(kv, max_cache_t=32)
    key = jax.random.PRNGKey(kv)
    params = layer.init_params(key, pol)
    assert params["Wqkv"].shape == (32, 32 + 2 * kv * 8) and "b" not in params
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 20, 32),
                          jnp.float32)
    dense, _ = gqa_layer(kv).apply(params, x, policy=pol)
    # the dense oracle by hand: query head i reads K/V head i // (4 // kv)
    q, k, v = jnp.split(x @ params["Wqkv"], [32, 32 + kv * 8], -1)
    q = q.reshape(2, 20, 4, 8)
    k = jnp.repeat(k.reshape(2, 20, kv, 8), 4 // kv, 2)
    v = jnp.repeat(v.reshape(2, 20, kv, 8), 4 // kv, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8.0)
    s = jnp.where(jnp.tril(jnp.ones((20, 20), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(dense, want.reshape(2, 20, 32) @ params["Wo"],
                               atol=1e-5)
    # streaming: 12 at once, then token by token
    h, c = layer._zero_state(2, pol)
    assert h.shape == (2, 33, kv * 8)
    out, st = layer.apply(params, x[:, :12], state={"h": h, "c": c},
                          policy=pol)
    outs = [out]
    for t in range(12, 20):
        out, st = layer.apply(params, x[:, t:t + 1], state=st, policy=pol)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), dense, atol=1e-5)
    # paged: a chunk of 12, then one-token steps, pools kv·d wide
    arena = PagedKVArena({"a": layer.paged_entry(2)}, num_pages=16,
                         page_size=4)
    assert arena.k_pools[0].shape == (16, 4, kv * 8)
    tables = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    kp, vp = arena.k_pools[0], arena.v_pools[0]
    slots = jnp.tile(jnp.arange(12, dtype=jnp.int32), (2, 1))
    out, kp, vp = layer.apply_paged(params, x[:, :12], kp, vp, tables, slots,
                                    jnp.zeros(2, jnp.int32), policy=pol)
    outs = [out]
    for t in range(12, 20):
        out, kp, vp = layer.apply_paged(
            params, x[:, t:t + 1], kp, vp, tables,
            jnp.full((2, 1), t, jnp.int32), jnp.full(2, t, jnp.int32),
            policy=pol)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), dense, atol=1e-5)


def test_multi_head_keeps_its_parameters_and_its_pools():
    layer = SelfAttentionLayer(n_heads=4)
    layer.set_n_in(InputType.recurrent(32))
    assert layer.param_shapes() == {"Wqkv": (32, 96), "Wo": (32, 32),
                                    "b": (32,)}
    assert layer.kv_heads == 4 and layer.kv_width == 32
    with pytest.raises(ValueError, match="n_kv_heads"):
        SelfAttentionLayer(n_heads=4, n_kv_heads=3).set_n_in(
            InputType.recurrent(32))


def test_leaf_shapes_cover_the_program_tree(net, family):
    shapes = family.leaf_shapes(SMALL)
    names = family.program_names(SMALL)
    assert set(shapes) == set(names)
    tree = {v: set(leaves) for v, leaves in net.params.items() if leaves}
    covered = {}
    for vertex, leaf in names.values():
        covered.setdefault(vertex, set()).add(leaf)
    assert covered == tree
    assert _metrics  # the registry module is what the engine counts into
