"""The Mamba-2 mixer (``nn/conf/ssm.py``): the chunked scan, the recurrence
step and a plain ``lax.scan`` give the same numbers; what is carried between
calls is carried exactly; padding and retired lanes advance no state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (layer_from_dict,
                                               layer_to_dict)
from deeplearning4j_tpu.nn.conf.ssm import Mamba2Mixer

POLICY = dtypes.FLOAT32
D, T = 12, 50


def mixer(**kw):
    m = Mamba2Mixer(n_heads=4, head_dim=8, n_groups=2, state_size=8,
                    chunk_size=16, activation="identity", **kw)
    m.set_n_in(InputType.recurrent(D))
    return m


@pytest.fixture(scope="module")
def setup():
    m = mixer()
    key = jax.random.PRNGKey(0)
    params = {k: (v + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                              v.shape)).astype(jnp.float32)
              for i, (k, v) in enumerate(m.init_params(key, POLICY).items())}
    x = jax.random.normal(jax.random.fold_in(key, 99), (2, T, D),
                          jnp.float32)
    return m, params, x


def plain_scan(m, p, x):
    """The layer's equations as a ``lax.scan`` over positions, one
    sequence: no chunk, no carried call."""
    t = x.shape[0]
    h, pd, g, n = m.n_heads, m.head_dim, m.n_groups, m.state_size
    z, xbc, dt = jnp.split(x @ p["W_in"],
                           [m.d_inner, m.d_inner + m.conv_channels], -1)
    cat = jnp.concatenate([jnp.zeros((m.conv_kernel - 1, xbc.shape[1])),
                           xbc])
    conv = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][:, k] * cat[k:k + t] for k in range(m.conv_kernel)))
    xs, bm, cm = jnp.split(conv, [m.d_inner, m.d_inner + g * n], -1)
    xs = xs.reshape(t, h, pd)
    bm = jnp.repeat(bm.reshape(t, g, n), h // g, 1)
    cm = jnp.repeat(cm.reshape(t, g, n), h // g, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], -1) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((h, pd, n)), (xs, bm, cm, dt))
    y = (y.reshape(t, m.d_inner) * jax.nn.silu(z)).reshape(t, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + m.norm_eps)
    return (y.reshape(t, m.d_inner) * p["norm_g"]) @ p["W_out"]


def stream(m, p, x, step):
    h, c = m._zero_state(x.shape[0], POLICY)
    state, outs = {"h": h, "c": c}, []
    for t in range(0, x.shape[1], step):
        o, state = m.apply(p, x[:, t:t + step], state=state, policy=POLICY)
        outs.append(o)
    return jnp.concatenate(outs, 1), state


def test_chunked_scan_is_the_recurrence_is_the_plain_scan(setup):
    m, p, x = setup
    full, _ = m.apply(p, x, policy=POLICY)        # 50 positions, chunks of 16
    by_token, st1 = stream(m, p, x, 1)
    by_seven, st7 = stream(m, p, x, 7)
    plain = jnp.stack([plain_scan(m, p, x[i]) for i in range(2)])
    # float32 sums formed in another order: rounding, nothing else
    for got in (full, by_token, by_seven):
        np.testing.assert_allclose(got, plain, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(st7["c"], st1["c"], atol=1e-6)
    np.testing.assert_array_equal(st7["h"], st1["h"])    # rows of the input


def test_step_is_apply_of_one_position(setup):
    m, p, x = setup
    h, c = m._zero_state(2, POLICY)
    out, st = m.step(p, x[:, 0], {"h": h, "c": c}, policy=POLICY)
    ref, st2 = m.apply(p, x[:, :1], state={"h": h, "c": c}, policy=POLICY)
    np.testing.assert_array_equal(out, ref[:, 0])
    np.testing.assert_array_equal(st["c"], st2["c"])


def arena(m, lanes=4):
    return [jnp.zeros(s, jnp.float32) for s in m.state_shapes(lanes)]


def test_paged_prefill_then_steps_match_the_stream(setup):
    m, p, x = setup
    by_token, _ = stream(m, p, x, 1)
    conv, ssm = arena(m)
    lanes = jnp.array([2, 0])
    got = []
    for t0 in (0, 16):                             # two chunks of 16
        o, conv, ssm = m.apply_paged(
            p, x[:, t0:t0 + 16], conv, ssm, lanes, jnp.ones((2, 16), bool),
            jnp.full(2, t0 == 0), policy=POLICY)
        got.append(o)
    for t in range(32, 40):                        # then token by token
        o, conv, ssm = m.apply_paged(
            p, x[:, t:t + 1], conv, ssm, lanes, jnp.ones((2, 1), bool),
            jnp.zeros(2, bool), policy=POLICY)
        got.append(o)
    np.testing.assert_allclose(jnp.concatenate(got, 1), by_token[:, :40],
                               atol=2e-5, rtol=1e-4)
    # lanes 1 and 3 were never dispatched
    assert not np.asarray(ssm[1]).any() and not np.asarray(ssm[3]).any()


def test_padding_and_retired_lanes_leave_the_state_bit_for_bit(setup):
    m, p, x = setup
    conv, ssm = arena(m)
    lanes = jnp.array([1, 3])
    _, conv, ssm = m.apply_paged(p, x[:, :16], conv, ssm, lanes,
                                 jnp.ones((2, 16), bool), jnp.ones(2, bool),
                                 policy=POLICY)
    # a chunk whose padded positions hold garbage == the same chunk with
    # zeros there: lane 1 has 5 valid positions, lane 3 none (retired)
    valid = jnp.arange(16)[None, :] < jnp.array([5, 0])[:, None]
    chunk = x[:, 16:32]
    junk = jnp.where(valid[:, :, None], chunk, 1e3)
    zero = jnp.where(valid[:, :, None], chunk, 0.0)
    a = m.apply_paged(p, junk, conv, ssm, lanes, valid, jnp.zeros(2, bool),
                      policy=POLICY)
    b = m.apply_paged(p, zero, conv, ssm, lanes, valid, jnp.zeros(2, bool),
                      policy=POLICY)
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2], b[2])
    np.testing.assert_array_equal(a[0][0, :5], b[0][0, :5])
    # the retired lane's state did not move at all, the live one's did
    np.testing.assert_array_equal(a[2][3], ssm[3])
    np.testing.assert_array_equal(a[1][3], conv[3])
    assert not np.array_equal(a[2][1], ssm[1])
    # and the live lane's state is that of feeding its 5 positions alone
    c = m.apply_paged(p, chunk[:, :5], conv, ssm, lanes,
                      jnp.array([[True] * 5, [False] * 5]),
                      jnp.zeros(2, bool), policy=POLICY)
    np.testing.assert_allclose(a[2][1], c[2][1], atol=1e-6)
    np.testing.assert_array_equal(a[1][1], c[1][1])
    # a one-token step of a retired lane (the fused block's masked step)
    d = m.apply_paged(p, x[:, 40:41], a[1], a[2], lanes,
                      jnp.array([[True], [False]]), jnp.zeros(2, bool),
                      policy=POLICY)
    np.testing.assert_array_equal(d[2][3], a[2][3])
    np.testing.assert_array_equal(d[1][3], a[1][3])


def test_a_fresh_lane_starts_from_zero_state_and_a_padded_slot_writes_nothing(
        setup):
    m, p, x = setup
    conv, ssm = arena(m)
    lanes = jnp.array([2, 4])          # 4 is one past the last lane: padded
    ones = jnp.ones((2, 16), bool)
    first = m.apply_paged(p, x[:, :16], conv, ssm, lanes, ones,
                          jnp.ones(2, bool), policy=POLICY)
    # the lane's next owner: same prompt, marked fresh, over the old state
    again = m.apply_paged(p, x[:, :16], first[1], first[2], lanes, ones,
                          jnp.ones(2, bool), policy=POLICY)
    np.testing.assert_array_equal(again[0], first[0])
    np.testing.assert_array_equal(again[2], first[2])
    # not marked fresh it continues from the retired owner's state
    cont = m.apply_paged(p, x[:, :16], first[1], first[2], lanes, ones,
                         jnp.zeros(2, bool), policy=POLICY)
    assert not np.allclose(cont[0][0], first[0][0])
    # the padded slot (x[1]) touched no lane
    for lane in (0, 1, 3):
        assert not np.asarray(first[2][lane]).any()


def test_stored_bf16_keeps_the_state_in_float32(setup):
    m, p, x = setup
    pol = dtypes.policy_from_name("stored_bf16")
    pb = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
    h, c = m._zero_state(2, pol)
    assert h.dtype == c.dtype == jnp.float32
    out, st = m.apply(pb, x.astype(jnp.bfloat16), state={"h": h, "c": c},
                      policy=pol)
    assert out.dtype == jnp.bfloat16 and st["c"].dtype == jnp.float32
    ref, _ = m.apply(p, x, policy=POLICY)
    # bf16 operands of the two projections (8 bits of mantissa) around a
    # float32 recurrence: a few hundredths on outputs of size one
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < 0.1


def test_serde_round_trip_and_shapes():
    m = mixer()
    again = layer_from_dict(layer_to_dict(m))
    assert again == m and isinstance(again, Mamba2Mixer)
    assert m.conv_channels == 32 + 2 * 2 * 8
    shapes = m.param_shapes()
    assert shapes["W_in"] == (D, 32 + 64 + 4)
    params = m.init_params(jax.random.PRNGKey(1), POLICY)
    assert {k: v.shape for k, v in params.items()} == shapes
    with pytest.raises(ValueError, match="n_groups"):
        Mamba2Mixer(n_heads=4, n_groups=3).set_n_in(InputType.recurrent(D))
