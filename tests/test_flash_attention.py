"""Pallas flash attention: forward/backward parity vs the XLA path
(interpret mode on the CPU test backend; the kernel compiles natively on
TPU — measured in PERF.md's "Pallas flash attention" section)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.attention import dot_product_attention
from deeplearning4j_tpu.ops import flash_attention as fa
from deeplearning4j_tpu.ops.flash_attention import (flash_attention,
                                                    flash_available)


def _qkv(rng, b=2, t=256, h=2, d=64):
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, d)).astype(np.float32))
    return mk(), mk(), mk()


def _ragged_mask(b, t, lengths):
    m = np.zeros((b, t), np.float32)
    for i, l in enumerate(lengths):
        m[i, :l] = 1.0
    return jnp.asarray(m)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_dense(self, rng, causal):
        q, k, v = _qkv(rng)
        ref = np.asarray(dot_product_attention(q, k, v, causal=causal))
        out = np.asarray(flash_attention(q, k, v, causal, None, 128, True))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_masked_forward_matches_dense(self, rng, causal):
        q, k, v = _qkv(rng)
        mask = _ragged_mask(2, 256, [200, 131])
        ref = np.asarray(dot_product_attention(q, k, v, causal=causal,
                                               mask=mask))
        out = np.asarray(flash_attention(q, k, v, causal, None, 128, True,
                                         mask=mask))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_leading_padding_causal_outputs_zero(self, rng):
        # query steps with NO attendable keys must output 0, not NaN
        q, k, v = _qkv(rng, t=128)
        mask = np.ones((2, 128), np.float32)
        mask[:, :5] = 0.0
        out = np.asarray(flash_attention(q, k, v, True, None, 128, True,
                                         mask=jnp.asarray(mask)))
        assert np.all(np.isfinite(out))
        assert np.allclose(out[:, :5], 0.0)
        ref = np.asarray(dot_product_attention(q, k, v, causal=True,
                                               mask=jnp.asarray(mask)))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_blockwise_gradients_match_dense(self, rng, causal):
        # t=256 with block 128: gradients cross tile boundaries, so the
        # blockwise backward's accumulation over i/j blocks is exercised
        q, k, v = _qkv(rng, t=256)
        loss_f = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) ** 2)
        g_ref = jax.grad(loss_f(lambda q, k, v: dot_product_attention(
            q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss_f(lambda q, k, v: flash_attention(
            q, k, v, causal, None, 128, True)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_masked_gradients_match_dense(self, rng):
        q, k, v = _qkv(rng, t=256)
        mask = _ragged_mask(2, 256, [256, 170])
        loss_f = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) ** 2)
        g_ref = jax.grad(loss_f(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, mask=mask)), argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss_f(lambda q, k, v: flash_attention(
            q, k, v, True, None, 128, True, mask=mask)),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_lse_is_correct(self, rng):
        q, k, v = _qkv(rng, t=128)
        b, t, h, d = q.shape
        to_btd = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, t, d)
        mk = jnp.ones((b, t), jnp.float32)
        _, lse = fa._flash_fwd_btd(to_btd(q), to_btd(k), to_btd(v), mk,
                                   n_heads=h, scale=d ** -0.5, causal=True,
                                   block_q=128, interpret=True)
        logits = jnp.einsum("btd,bsd->bts", to_btd(q), to_btd(k)) * d ** -0.5
        cm = jnp.tril(jnp.ones((t, t), bool))
        logits = jnp.where(cm[None], logits, fa.NEG_INF)
        ref = jax.scipy.special.logsumexp(logits, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_routing_flag(self, rng, monkeypatch):
        q, _, _ = _qkv(rng)
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "0")
        assert not flash_available(q.shape, None)
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        assert flash_available(q.shape, None)
        assert flash_available(q.shape, np.ones((2, 256)))   # key masks ok
        assert not flash_available(q.shape, np.ones((2, 9)))  # odd mask shape
        assert not flash_available((2, 250, 2, 64), None)     # t % block
        # the flag cannot conjure a kernel: off the TPU and with nobody
        # asking for interpret mode (the suite does, conftest.py), even
        # "1" takes the XLA path instead of interpreting by surprise
        from deeplearning4j_tpu.util import xla
        with monkeypatch.context() as m:
            m.setattr(xla, "_interpret_kernels", False)
            assert xla.kernel_mode() is None
            assert not flash_available(q.shape, None)
        assert xla.kernel_mode() == "interpret"
        # auto: long sequences only, and only on a real TPU backend
        monkeypatch.delenv("DL4JTPU_FLASH_ATTENTION")
        assert not flash_available((2, 256, 2, 64), None)
        assert not flash_available((2, 4096, 2, 64), None)    # cpu tests

    @pytest.mark.parametrize("masked", [False, True])
    def test_streamed_variant_matches_dense(self, rng, masked, monkeypatch):
        # force the long-sequence streamed kernel by shrinking the VMEM
        # dispatch threshold; run it with and without a ragged mask
        monkeypatch.setattr(fa, "_VMEM_KV_LIMIT", 0)
        q, k, v = _qkv(rng, t=256)
        mask = _ragged_mask(2, 256, [190, 131]) if masked else None
        ref = np.asarray(dot_product_attention(q, k, v, causal=True,
                                               mask=mask))
        out = np.asarray(flash_attention(q, k, v, True, None, 128, True,
                                         mask=mask))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
        # backward through the streamed forward (lse path) too
        g_ref = jax.grad(lambda q: jnp.sum(dot_product_attention(
            q, k, v, causal=True, mask=mask) ** 2))(q)
        g_fl = jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, True, None, 128, True, mask=mask) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_wide_block_backward_matches_dense(self, rng):
        # t divisible by 512 engages the 512-wide backward tiles
        q, k, v = _qkv(rng, b=1, t=1024, h=1, d=64)
        loss_f = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) ** 2)
        g_ref = jax.grad(loss_f(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss_f(lambda q, k, v: flash_attention(
            q, k, v, True, None, 128, True)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


def _count_pallas_calls(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_pallas_calls(sub)
    return n


def _dense_attention_btd(q, k, v, mask_bt, causal, scale):
    """The XLA path on one batch row's [h, t, d] heads (a row with no
    attendable key gives 0 there as in the kernels)."""
    to_bthd = lambda a: a.transpose(1, 0, 2)[None]
    out = dot_product_attention(to_bthd(q), to_bthd(k), to_bthd(v),
                                causal=causal, mask=mask_bt, scale=scale)
    return out[0].transpose(1, 0, 2)


class TestPallasBackward:
    """``_flash_bwd_btd_pallas``: ONE call where a head's float32 dq fits
    ``_VMEM_DQ_LIMIT`` (each tile's P and dS computed once, dq summed in a
    scratch that holds the head's whole dq), two above it. t 512 in tiles
    of at most 128 is at least 4 x 4 tiles, so steps before the diagonal
    are skipped and their index maps clamped."""

    B, H, T, D = 1, 2, 512, 32

    def _case(self, rng, causal, masked):
        bh = self.B * self.H
        mk = lambda: jnp.asarray(rng.normal(size=(bh, self.T, self.D))
                                 .astype(np.float32))
        q, k, v, dout = mk(), mk(), mk(), mk()
        mask = np.ones((self.B, self.T), np.float32)
        if masked:                  # rows 0-4 see no key at all under causal
            mask[:, :5] = 0.0
            mask[:, 300:317] = 0.0
        mask = jnp.asarray(mask)
        scale = self.D ** -0.5
        out, lse = fa._flash_fwd_btd(q, k, v, mask, n_heads=self.H,
                                     scale=scale, causal=causal, block_q=128,
                                     interpret=True)
        if masked and causal:
            assert np.all(np.asarray(lse)[:, :5] == fa.NEG_INF)
        return q, k, v, dout, mask, out, lse, scale

    @pytest.mark.parametrize("limit", [None, 0], ids=["fused", "two_pass"])
    @pytest.mark.parametrize("tiles", [(128, 128), (64, 128), (128, 64)],
                             ids=lambda bt: f"{bt[0]}x{bt[1]}")
    @pytest.mark.parametrize("masked", [False, True],
                             ids=["nomask", "keymask"])
    @pytest.mark.parametrize("causal", [False, True],
                             ids=["full", "causal"])
    def test_matches_dense_and_jax_blockwise(self, rng, monkeypatch, causal,
                                             masked, tiles, limit):
        if limit is not None:
            monkeypatch.setattr(fa, "_VMEM_DQ_LIMIT", limit)
        q, k, v, dout, mask, out, lse, scale = self._case(rng, causal, masked)
        got = fa._flash_bwd_btd_pallas(
            q, k, v, mask, out, lse, dout, scale=scale, causal=causal,
            block_q=tiles[0], block_k=tiles[1], interpret=True,
            n_heads=self.H)
        _, vjp = jax.vjp(lambda q, k, v: _dense_attention_btd(
            q, k, v, mask, causal, scale), q, k, v)
        blockwise = fa._flash_bwd_btd(
            q, k, v, jnp.repeat(mask, self.H, axis=0), out, lse, dout,
            scale=scale, causal=causal, block_q=tiles[0], block_k=tiles[1])
        for name, g, dense, jx in zip("qkv", got, vjp(dout), blockwise):
            assert np.all(np.isfinite(np.asarray(g))), name
            np.testing.assert_allclose(np.asarray(g), np.asarray(dense),
                                       rtol=2e-4, atol=2e-4, err_msg=name)
            np.testing.assert_allclose(np.asarray(g), np.asarray(jx),
                                       rtol=2e-5, atol=2e-5, err_msg=name)

    @pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
    def test_one_call_equals_two_calls_bit_for_bit(self, rng, monkeypatch,
                                                   causal):
        # the same products on the same operands, dq summed over the key
        # blocks in the same order: float32 results are equal, not close
        q, k, v, dout, mask, out, lse, scale = self._case(rng, causal, True)
        run = lambda: fa._flash_bwd_btd_pallas(
            q, k, v, mask, out, lse, dout, scale=scale, causal=causal,
            block_q=128, block_k=64, interpret=True, n_heads=self.H)
        fused = run()
        monkeypatch.setattr(fa, "_VMEM_DQ_LIMIT", 0)
        for a, b in zip(fused, run()):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_backward_is_one_pallas_call_under_the_budget(self, monkeypatch,
                                                          dtype):
        def calls(t, d):
            a = jax.ShapeDtypeStruct((2, t, d), dtype)
            f = lambda q, k, v, out, dout: fa._flash_bwd_btd_pallas(
                q, k, v, jnp.ones((1, t)), out, jnp.zeros((2, t)), dout,
                scale=1.0, causal=True, block_q=128, block_k=128,
                interpret=True, n_heads=2)
            return _count_pallas_calls(jax.make_jaxpr(f)(a, a, a, a, a).jaxpr)
        # the budget is the float32 accumulator's size, whatever the dtype
        assert fa._VMEM_DQ_LIMIT == 4 * 1024 * 1024
        assert calls(16384, 64) == 1 and calls(8192, 128) == 1
        assert calls(16384, 128) == 2 and calls(32768, 64) == 2

    def test_grad_of_the_public_op_runs_one_backward_call(self, rng,
                                                          monkeypatch):
        q, k, v = _qkv(rng, b=1, t=256, h=1, d=32)
        loss = lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, True, None, 128, True) ** 2)

        def calls():
            jax.clear_caches()      # the route is chosen at trace time
            return _count_pallas_calls(jax.make_jaxpr(jax.grad(
                loss, argnums=(0, 1, 2)))(q, k, v).jaxpr)
        # one forward and one backward; the jax-blockwise route has none
        assert calls() == 2
        monkeypatch.setenv("DL4JTPU_FLASH_BWD", "jax")
        assert calls() == 1
        monkeypatch.delenv("DL4JTPU_FLASH_BWD")
        monkeypatch.setattr(fa, "_VMEM_DQ_LIMIT", 0)
        assert calls() == 3


class TestTraceTimeFlagRouting:
    """VERDICT r5 item 9: ``DL4JTPU_FLASH_ATTENTION`` / ``DL4JTPU_FLASH_BWD``
    are read at TRACE time, so historically a toggle only took effect
    after manually clearing jit caches. The runtimes now key their jit
    caches on ``util.xla.trace_env_key()``: flipping a flag makes the
    next call trace a FRESH program under the new routing, and flipping
    it back reuses the original compilation."""

    def _net(self):
        from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        conf = (NeuralNetConfiguration.builder().seed(5).updater("sgd")
                .learning_rate(0.1).list()
                .layer(DenseLayer(n_out=4, activation="tanh"))
                .layer(OutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(3)).build())
        return MultiLayerNetwork(conf).init()

    def test_toggle_takes_effect_without_manual_cache_clearing(
            self, rng, monkeypatch):
        monkeypatch.delenv("DL4JTPU_FLASH_ATTENTION", raising=False)
        monkeypatch.delenv("DL4JTPU_FLASH_BWD", raising=False)
        net = self._net()
        x = rng.normal(size=(4, 3)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 4)]
        net.fit_batch(x, y)
        keys0 = set(net._jit_cache)
        net.fit_batch(x, y)
        assert set(net._jit_cache) == keys0      # steady state: one program

        monkeypatch.setenv("DL4JTPU_FLASH_BWD", "jax")
        net.fit_batch(x, y)
        new = set(net._jit_cache) - keys0        # fresh trace, new routing
        assert len(new) == 1 and "fabwd=jax" in new.pop()

        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "0")
        net.output(x)
        assert any("fa=0" in k and k.startswith("output") for k in
                   net._jit_cache)

        # flipping BACK reuses the original compiled entry — no growth
        monkeypatch.delenv("DL4JTPU_FLASH_BWD")
        monkeypatch.delenv("DL4JTPU_FLASH_ATTENTION")
        n = len(net._jit_cache)
        net.fit_batch(x, y)
        assert len(net._jit_cache) == n

    def test_ring_caller_retraces_on_toggle_flip(self, rng, monkeypatch):
        """Ring callers honour the same contract: the sharded DSL
        trainer's jitted step is keyed on trace_env_key, so flipping
        DL4JTPU_FLASH_ATTENTION re-traces the step with the ring routed
        through (or away from) the Pallas kernel — no manual cache
        clearing — and flipping back reuses the original compilation."""
        from deeplearning4j_tpu.models import transformer_lm
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        from deeplearning4j_tpu.parallel import (
            SequenceParallelGraphTrainer, create_mesh)
        monkeypatch.delenv("DL4JTPU_FLASH_ATTENTION", raising=False)
        monkeypatch.delenv("DL4JTPU_FLASH_BWD", raising=False)
        net = ComputationGraph(transformer_lm(
            7, n_layers=1, d_model=8, n_heads=2, d_ff=16, updater="sgd",
            learning_rate=0.05, seed=9)).init()
        tr = SequenceParallelGraphTrainer(net, create_mesh({"seq": 4}))
        ids = np.random.default_rng(3).integers(0, 7, (2, 17))
        eye = np.eye(7, dtype=np.float32)
        x, y = eye[ids[:, :-1]], eye[ids[:, 1:]]
        tr.fit_batch(x, y)
        keys0 = set(tr._step_fns)
        tr.fit_batch(x, y)
        assert set(tr._step_fns) == keys0       # steady state: one program

        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        loss = tr.fit_batch(x, y)               # kernel-in-ring trace
        assert np.isfinite(float(loss))
        new = set(tr._step_fns) - keys0
        assert len(new) == 1 and "fa=1" in new.pop()

        monkeypatch.delenv("DL4JTPU_FLASH_ATTENTION")
        n = len(tr._step_fns)
        tr.fit_batch(x, y)                      # flip back: reuse, no growth
        assert len(tr._step_fns) == n

    def test_bespoke_sequence_trainer_keys_step_on_flags(
            self, rng, monkeypatch):
        from deeplearning4j_tpu.parallel import create_mesh
        from deeplearning4j_tpu.parallel.sequence import (
            SequenceParallelTrainer)
        monkeypatch.delenv("DL4JTPU_FLASH_ATTENTION", raising=False)
        tr = SequenceParallelTrainer(d_model=8, d_ff=16, n_heads=2,
                                     vocab=7, mesh=create_mesh({"seq": 4}),
                                     seed=1)
        ids = np.random.default_rng(5).integers(0, 7, (2, 17))
        eye = np.eye(7, dtype=np.float32)
        x, y = eye[ids[:, :-1]], eye[ids[:, 1:]]
        tr.fit_batch(x, y)
        monkeypatch.setenv("DL4JTPU_FLASH_ATTENTION", "1")
        assert np.isfinite(float(tr.fit_batch(x, y)))
        assert any("fa=1" in k for k in tr._step_fns)
        assert len(tr._step_fns) == 2

    def test_graph_runtime_keys_cache_on_flags(self, rng, monkeypatch):
        from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        monkeypatch.delenv("DL4JTPU_FLASH_BWD", raising=False)
        b = (NeuralNetConfiguration.builder().seed(5).updater("sgd")
             .learning_rate(0.1).graph_builder()
             .add_inputs("in")
             .add_layer("d", DenseLayer(n_in=3, n_out=4,
                                        activation="tanh"), "in")
             .add_layer("out", OutputLayer(n_in=4, n_out=2,
                                           activation="softmax",
                                           loss="mcxent"), "d")
             .set_outputs("out"))
        net = ComputationGraph(b.build()).init()
        x = rng.normal(size=(4, 3)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 4)]
        net.fit_batch(x, y)
        keys0 = set(net._jit_cache)
        monkeypatch.setenv("DL4JTPU_FLASH_BWD", "jax")
        net.fit_batch(x, y)
        new = set(net._jit_cache) - keys0
        assert len(new) == 1 and "fabwd=jax" in new.pop()
