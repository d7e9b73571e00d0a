"""The LatentMoE layer (``nn/conf/moe.py``): sparse dispatch against the
dense oracle, no pair dropped under any imbalance, the shares of an
expert-parallel deployment adding up to the uncut layer, and the counts a
layer reports beside its output."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn.conf import moe
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (layer_from_dict,
                                               layer_to_dict)
from deeplearning4j_tpu.nn.conf.moe import (MOE_STATS, LatentMoELayer,
                                            dense_expert_ffn,
                                            sparse_expert_ffn, tile_rows)

POLICY = dtypes.FLOAT32
T, K, L, F, E_HELD, E_PUB = 37, 3, 16, 24, 4, 16


@pytest.fixture(scope="module")
def pairs():
    key = jax.random.PRNGKey(0)
    f = lambda i, shape, s=1.0: s * jax.random.normal(      # noqa: E731
        jax.random.fold_in(key, i), shape, jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (T, K), 0,
                             E_PUB).astype(jnp.int32)
    w = jax.random.uniform(jax.random.fold_in(key, 2), (T, K), jnp.float32)
    return (f(0, (T, L)), idx, w, f(3, (E_HELD, L, F), 0.3),
            f(4, (E_HELD, F, L), 0.3))


@pytest.mark.parametrize("offset", [0, 4, 12])
def test_sparse_dispatch_is_the_dense_oracle(pairs, offset):
    lat, idx, w, w1, w2 = pairs
    got, stats = jax.jit(lambda *a: sparse_expert_ffn(
        *a, offset=offset, n_published=E_PUB))(lat, idx, w, w1, w2)
    want = dense_expert_ffn(lat, idx, w, w1, w2, offset=offset)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    stats = dict(zip(MOE_STATS, np.asarray(stats)))
    local = np.asarray(idx) - offset
    held = (local >= 0) & (local < E_HELD)
    assert stats["held"] == held.sum()
    assert stats["absent"] == T * K - held.sum()
    per_expert = np.bincount(local[held], minlength=E_HELD)
    assert stats["peak"] == per_expert.max()
    rows = tile_rows(T * K, E_PUB)
    assert stats["computed"] == (-(-per_expert // rows)).sum() * rows
    assert stats["steps"] == 1


@pytest.mark.parametrize("expert", [5, 9])
def test_every_pair_to_one_expert_drops_none(pairs, expert):
    """A router forced to send every token's every choice to one expert:
    held (5) it gets ceil(T·k / rows) tiles and every pair is computed;
    absent (9) nothing is."""
    lat, _, w, w1, w2 = pairs
    idx = jnp.full((T, K), expert, jnp.int32)
    got, stats = sparse_expert_ffn(lat, idx, w, w1, w2, offset=4,
                                   n_published=E_PUB)
    want = dense_expert_ffn(lat, idx, w, w1, w2, offset=4)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    stats = dict(zip(MOE_STATS, np.asarray(stats)))
    if expert == 5:
        one = jnp.square(jax.nn.relu(lat @ w1[1])) @ w2[1]
        np.testing.assert_allclose(got, jnp.sum(w, -1)[:, None] * one,
                                   atol=2e-5, rtol=1e-5)
        assert stats["held"] == stats["peak"] == T * K
    else:
        assert not np.asarray(got).any() and stats["held"] == 0
        assert stats["computed"] == 0 and stats["absent"] == T * K


def test_a_masked_token_costs_and_counts_nothing(pairs):
    lat, idx, w, w1, w2 = pairs
    mask = jnp.arange(T) < 20
    got, stats = sparse_expert_ffn(lat, idx, w, w1, w2, offset=0,
                                   n_published=E_PUB, token_mask=mask)
    want, ref = sparse_expert_ffn(lat[:20], idx[:20], w[:20], w1, w2,
                                  offset=0, n_published=E_PUB)
    np.testing.assert_allclose(got[:20], want, atol=1e-5)
    assert not np.asarray(got[20:]).any()
    assert int(stats[0]) == int(ref[0]) and int(stats[1]) == int(ref[1])


def test_tile_rows():
    # one-token steps over many small experts: tiles of 2; a prefill chunk
    # of 32 lanes x 128 positions: an expert's mean load, 176, rounds to 256
    assert tile_rows(32 * 22, 512) == 2
    assert tile_rows(128 * 22, 512) == 8
    assert tile_rows(4096 * 22, 512) == 256
    assert tile_rows(10 ** 7, 8) == 256


def layer(held=None, offset=0, **kw):
    m = LatentMoELayer(d_latent=L, d_hidden=F, d_shared=40, n_experts=E_PUB,
                       experts_held=held, expert_offset=offset, top_k=K,
                       routed_scale=2.5, activation="identity", **kw)
    m.set_n_in(InputType.recurrent(32))
    return m


@pytest.fixture(scope="module")
def whole():
    m = layer()
    key = jax.random.PRNGKey(3)
    params = m.init_params(key, POLICY)
    params["e_bias"] = 0.3 * jax.random.normal(key, (E_PUB,), jnp.float32)
    params["router"] = 4.0 * params["router"]       # spread the scores
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 9, 32),
                          jnp.float32)
    return m, params, x


def test_the_shares_add_up(whole):
    """The four chips' routed parts, with what every chip computes alike
    (the shared expert) counted once, are the uncut layer."""
    m, params, x = whole
    full, st = m.apply(params, x, policy=POLICY)
    assert int(st["moe_stats"][1]) == 0             # no expert is absent
    no_shared = dict(params, ws2=jnp.zeros_like(params["ws2"]))
    shared = full - m.apply(no_shared, x, policy=POLICY)[0]
    total, held_pairs = shared, 0
    for chip in range(4):
        part = layer(held=4, offset=4 * chip)
        own = dict(no_shared, w1=params["w1"][4 * chip:4 * chip + 4],
                   w2=params["w2"][4 * chip:4 * chip + 4])
        y, st = part.apply(own, x, policy=POLICY)
        total = total + y
        held_pairs += int(st["moe_stats"][0])
        assert int(st["moe_stats"][0]) + int(st["moe_stats"][1]) == 2 * 9 * K
    np.testing.assert_allclose(total, full, atol=1e-5, rtol=1e-5)
    assert held_pairs == 2 * 9 * K                  # each pair on one chip


def test_the_selection_bias_chooses_and_does_not_weigh(whole):
    m, params, x = whole
    idx, w = m.route(params, x.reshape(-1, 32))
    s = jax.nn.sigmoid(x.reshape(-1, 32) @ params["router"])
    want_idx = np.argsort(-(np.asarray(s) + np.asarray(params["e_bias"])),
                          axis=-1, kind="stable")[:, :K]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    chosen = np.take_along_axis(np.asarray(s), np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, chosen / chosen.sum(-1, keepdims=True) * 2.5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    unbiased, _ = m.route(dict(params, e_bias=jnp.zeros(E_PUB)),
                          x.reshape(-1, 32))
    assert not np.array_equal(np.sort(unbiased, -1), np.sort(idx, -1))


def test_layer_is_its_equations(whole):
    m, params, x = whole
    got, _ = m.apply(params, x, policy=POLICY)
    u = x.reshape(-1, 32)
    idx, w = m.route(params, u)
    routed = dense_expert_ffn(u @ params["W_down"], idx, w, params["w1"],
                              params["w2"], offset=0) @ params["W_up"]
    shared = jnp.square(jax.nn.relu(u @ params["ws1"])) @ params["ws2"]
    np.testing.assert_allclose(got.reshape(-1, 32), routed + shared,
                               atol=1e-5, rtol=1e-5)


def test_serde_shapes_and_refusals():
    m = layer(held=4, offset=8)
    assert layer_from_dict(layer_to_dict(m)) == m
    assert m.param_shapes()["w1"] == (4, L, F)
    assert m.param_shapes()["router"] == (32, E_PUB)
    with pytest.raises(ValueError, match="outside the router"):
        layer(held=4, offset=14)
    with pytest.raises(ValueError, match="top_k"):
        LatentMoELayer(n_experts=2, top_k=3).set_n_in(InputType.recurrent(8))
    # the routing helper is the one MoELayer uses
    idx, chosen = moe.top_k_route(jnp.array([[0.1, 0.9, 0.9, 0.2]]),
                                  jnp.array([[1., 2., 3., 4.]]), 2)
    assert idx.tolist() == [[1, 2]] and chosen.tolist() == [[2., 3.]]
