"""Ring-flash attention: the Pallas flash kernel riding the ppermute ring
(ops/flash_attention.py block-callable carry entry + ops/attention.py ring
VJP), parity-tested against the dense oracle on the 8-device CPU mesh —
the kernels run in interpret mode (conftest.py asks), so the carry protocol, the
cross-hop masking trichotomy, and the VJP-through-ppermute are the REAL
code paths, not stand-ins."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops.attention import (
    dot_product_attention, make_ring_attention, ring_flash_available)
from deeplearning4j_tpu.parallel import create_mesh


def _qkv(rng, b=2, t=32, h=2, d=8):
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    return mk(), mk(), mk()


class TestFlashCarry:
    """The block-callable entry: (m, l, o) carry across flash calls."""

    def test_single_hop_reproduces_monolithic_kernel(self, rng):
        # one hop fed the WHOLE sequence == the monolithic kernel: same
        # out AND same lse (the backward depends on the lse surviving
        # the carry fold exactly)
        q, k, v = _qkv(rng, t=64, d=16)
        carry = fa.flash_carry_init(q)
        carry = fa.flash_attention_block(q, k, v, carry, causal=True,
                                         block_q=32, interpret=True)
        out, lse = fa.flash_carry_finalize(carry)
        mono = np.asarray(fa.flash_attention(q, k, v, True, None, 32, True))
        np.testing.assert_allclose(np.asarray(out), mono, rtol=2e-5,
                                   atol=2e-5)
        b, t, h, d = q.shape
        mask = jnp.ones((b, t), jnp.float32)
        _, lse_mono = fa._core_fwd(q, k, v, mask, True, None, 32, True)
        lse_mono = lse_mono.reshape(b, h, t).transpose(0, 2, 1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_mono),
                                   rtol=1e-5, atol=1e-5)

    def test_two_hop_split_matches_dense(self, rng):
        # one local q shard visited by two shard-sized K/V hops through
        # the carry == attending the full sequence at once (the
        # order-independent online-softmax merge, exactly what each ring
        # device computes)
        q, k, v = _qkv(rng)
        qs = q[:, :16]                           # the "local" shard
        carry = fa.flash_carry_init(qs)
        for sl in (slice(0, 16), slice(16, 32)):
            carry = fa.flash_attention_block(qs, k[:, sl], v[:, sl], carry,
                                             causal=False, block_q=16,
                                             interpret=True)
        out, _ = fa.flash_carry_finalize(carry)
        ref = np.asarray(dot_product_attention(qs, k, v))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5)

    def test_hop_length_mismatch_rejected(self, rng):
        q, k, v = _qkv(rng)
        with pytest.raises(ValueError, match="shard-sized"):
            fa.flash_attention_block(q, k[:, :16], v[:, :16],
                                     fa.flash_carry_init(q), block_q=16,
                                     interpret=True)

    def test_empty_carry_finalizes_to_zero(self, rng):
        q, *_ = _qkv(rng, t=16)
        out, lse = fa.flash_carry_finalize(fa.flash_carry_init(q))
        assert np.allclose(np.asarray(out), 0.0)
        assert np.all(np.asarray(lse) <= fa._HALF_NEG)

    def test_bwd_block_sums_to_dense_gradient(self, rng):
        # per-hop (dq, dk, dv) against the GLOBAL lse sum exactly to the
        # dense gradient — the property the ring backward relies on
        q, k, v = _qkv(rng)
        qs = q[:, :16]
        carry = fa.flash_carry_init(qs)
        halves = [slice(0, 16), slice(16, 32)]
        for sl in halves:
            carry = fa.flash_attention_block(qs, k[:, sl], v[:, sl], carry,
                                             block_q=16, interpret=True)
        out, lse = fa.flash_carry_finalize(carry)
        g = jnp.asarray(rng.normal(size=qs.shape).astype(np.float32))
        dq = np.zeros(qs.shape, np.float32)
        dk = np.zeros(k.shape, np.float32)
        dv = np.zeros(v.shape, np.float32)
        for sl in halves:
            dq_h, dk_h, dv_h = fa.flash_attention_bwd_block(
                qs, k[:, sl], v[:, sl], out.astype(qs.dtype), lse, g,
                block_q=16, interpret=True)
            dq += np.asarray(dq_h)
            dk[:, sl] += np.asarray(dk_h)
            dv[:, sl] += np.asarray(dv_h)
        ref = jax.vjp(lambda q, k, v: dot_product_attention(q, k, v),
                      qs, k, v)[1](g)
        for got, want in zip((dq, dk, dv), ref):
            np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4,
                                       atol=2e-4)


class TestRingFlashParity:
    """Ring-flash vs the dense oracle over a real multi-device mesh."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_and_grads_match_dense(self, rng, causal, monkeypatch):
        q, k, v = _qkv(rng)
        ref = np.asarray(dot_product_attention(q, k, v, causal=causal))
        g_ref = jax.grad(lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)

        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        mesh = create_mesh({"seq": 4})
        ring = make_ring_attention(mesh, "seq", causal=causal)
        # forward + grads in ONE jit: the VJP trace contains the forward,
        # so a separate jit(ring) would compile the same program twice
        out, g_fl = jax.jit(lambda q, k, v: (
            ring(q, k, v),
            jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
                     argnums=(0, 1, 2))(q, k, v)))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_non_divisible_t_pads_under_key_mask(self, rng, causal,
                                                 monkeypatch):
        # t=20 over 4 devices → t_local=5, padded to the flash tile at
        # the END of every shard; padded keys masked, padded query rows
        # sliced — output and grads still match dense exactly
        q, k, v = _qkv(rng, t=20)
        ref = np.asarray(dot_product_attention(q, k, v, causal=causal))
        g_ref = jax.grad(lambda q: jnp.sum(dot_product_attention(
            q, k, v, causal=causal) ** 2))(q)
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        mesh = create_mesh({"seq": 4})
        ring = make_ring_attention(mesh, "seq", causal=causal)
        out, g_fl = jax.jit(lambda q: (
            ring(q, k, v),
            jax.grad(lambda q: jnp.sum(ring(q, k, v) ** 2))(q)))(q)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_ragged_key_mask_rides_the_ring(self, rng, monkeypatch):
        q, k, v = _qkv(rng)
        mask = np.ones((2, 32), np.float32)
        mask[0, 25:] = 0.0
        mask[1, 19:] = 0.0
        mask = jnp.asarray(mask)
        ref = np.asarray(dot_product_attention(q, k, v, causal=True,
                                               mask=mask))
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        mesh = create_mesh({"seq": 4})
        ring = make_ring_attention(mesh, "seq", causal=True,
                                   with_mask=True)
        out = np.asarray(jax.jit(ring)(q, k, v, mask))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_fully_masked_rows_output_zero_not_nan(self, rng, monkeypatch):
        # leading padding + causal: query rows with NO attendable key
        # anywhere on the ring finalize to 0 (carry never leaves NEG_INF)
        q, *_ = _qkv(rng)
        mask = np.ones((2, 32), np.float32)
        mask[:, :9] = 0.0
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        mesh = create_mesh({"seq": 4})
        ring = make_ring_attention(mesh, "seq", causal=True,
                                   with_mask=True)
        out = np.asarray(jax.jit(ring)(q, q, q, jnp.asarray(mask)))
        assert np.all(np.isfinite(out))
        assert np.allclose(out[:, :9], 0.0)

    def test_jax_bwd_fallback_selected_in_ring(self, rng, monkeypatch):
        # DL4JTPU_FLASH_BWD=jax swaps the per-hop backward to the
        # lax.scan blockwise path INSIDE the ring VJP
        q, k, v = _qkv(rng)
        g_ref = jax.grad(lambda q: jnp.sum(dot_product_attention(
            q, k, v, causal=True) ** 2))(q)
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        monkeypatch.setenv("DL4JTPU_FLASH_BWD", "jax")
        mesh = create_mesh({"seq": 4})
        ring = make_ring_attention(mesh, "seq", causal=True)
        g_fl = jax.grad(lambda q: jnp.sum(ring(q, k, v) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_routing_flag(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "0")
        assert not ring_flash_available(8192)
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        assert ring_flash_available(8)
        monkeypatch.delenv("DL4JTPU_FLASH_ATTENTION")
        assert not ring_flash_available(8192)   # auto: CPU test backend

    def test_oracle_still_selectable(self, rng, monkeypatch):
        # flag 0 keeps the JAX-level online-softmax block — and it still
        # matches dense (the parity oracle stays healthy)
        q, k, v = _qkv(rng)
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "0")
        mesh = create_mesh({"seq": 4})
        ring = make_ring_attention(mesh, "seq", causal=True)
        out = np.asarray(jax.jit(ring)(q, k, v))
        ref = np.asarray(dot_product_attention(q, k, v, causal=True))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


class TestSequenceParallelFlashTraining:
    """One full sharded training step through the kernel-in-ring path."""

    def test_dsl_trainer_trains_through_flash_ring(self, rng, monkeypatch):
        from deeplearning4j_tpu.models import transformer_lm
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        from deeplearning4j_tpu.parallel import SequenceParallelGraphTrainer
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        V, T = 7, 16
        net = ComputationGraph(transformer_lm(
            V, n_layers=1, d_model=8, n_heads=2, d_ff=16, updater="sgd",
            learning_rate=0.05, seed=9)).init()
        tr = SequenceParallelGraphTrainer(net, create_mesh({"seq": 4}))
        ids = rng.integers(0, V, (2, T + 1))
        eye = np.eye(V, dtype=np.float32)
        loss = tr.fit_batch(eye[ids[:, :-1]], eye[ids[:, 1:]])
        assert np.isfinite(float(loss))

    def test_flash_ring_loss_matches_oracle_ring(self, rng, monkeypatch):
        from deeplearning4j_tpu.parallel.sequence import (
            SequenceParallelTrainer)
        t = 16
        ids = rng.integers(0, 7, (2, t + 1))
        eye = np.eye(7, dtype=np.float32)
        x, y = eye[ids[:, :-1]], eye[ids[:, 1:]]
        losses = {}
        for flag in ("0", "1"):
            monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", flag)
            tr = SequenceParallelTrainer(d_model=8, d_ff=16, n_heads=2,
                                         vocab=7,
                                         mesh=create_mesh({"seq": 4}),
                                         seed=5)
            losses[flag] = float(tr.fit_batch(x, y))
        assert losses["1"] == pytest.approx(losses["0"], rel=1e-5)
