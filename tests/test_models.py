"""Model zoo tests: configs build, shapes infer, small variants train."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import char_rnn_lstm, lenet, resnet, resnet50
from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


class TestLenet:
    def test_builds_and_infers(self):
        conf = lenet()
        net = MultiLayerNetwork(conf).init()
        # conv1 20@5x5x1 + b, conv2 50@5x5x20 + b, dense 800x500 + b, out 500x10 + b
        expect = (5 * 5 * 1 * 20 + 20) + (5 * 5 * 20 * 50 + 50) \
            + (4 * 4 * 50 * 500 + 500) + (500 * 10 + 10)
        assert net.num_params() == expect

    def test_forward_shape(self, rng):
        net = MultiLayerNetwork(lenet()).init()
        out = np.asarray(net.output(rng.normal(size=(4, 784)).astype(np.float32)))
        assert out.shape == (4, 10)


class TestResNet:
    def test_resnet50_builds(self):
        conf = resnet50(dtype="float32")
        net = ComputationGraph(conf).init()
        n = net.num_params()
        # ResNet-50 ImageNet: ~25.6M params
        assert 25_000_000 < n < 26_000_000, n

    def test_tiny_resnet_trains(self, rng):
        conf = resnet((1, 1), height=16, width=16, channels=3, n_classes=4,
                      width_base=8, dtype="float32", learning_rate=0.01)
        net = ComputationGraph(conf).init()
        x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)]
        s0 = net.score_for([x], [y])
        for _ in range(15):
            net.fit_batch(x, y)
        assert net.score() < s0
        assert np.asarray(net.output(x)).shape == (8, 4)

    def test_stage_downsampling_shapes(self):
        conf = resnet((1, 1), height=32, width=32, channels=3, n_classes=10,
                      width_base=8, dtype="float32")
        types = conf.infer_shapes()
        # stem /2, pool /2, stage1 /2 → 32/8 = 4
        assert types["s1b0_relu"].height == 4
        assert types["s1b0_relu"].channels == 8 * 2 * 4


class TestCharRnn:
    def test_builds_and_tbptt(self, rng):
        conf = char_rnn_lstm(vocab_size=12, hidden=8, layers=2,
                             tbptt_length=5)
        assert conf.backprop_type == "truncated_bptt"
        net = MultiLayerNetwork(conf).init()
        x = np.eye(12, dtype=np.float32)[rng.integers(0, 12, (4, 12))]
        y = np.eye(12, dtype=np.float32)[rng.integers(0, 12, (4, 12))]
        net.fit_batch(x, y)  # 12 steps > tbptt 5 → chunked path
        assert np.isfinite(net.score())

    def test_streaming_inference(self, rng):
        conf = char_rnn_lstm(vocab_size=8, hidden=8, layers=1)
        net = MultiLayerNetwork(conf).init()
        step1 = net.rnn_time_step(np.eye(8, dtype=np.float32)[[0, 1]])
        step2 = net.rnn_time_step(np.eye(8, dtype=np.float32)[[2, 3]])
        assert step1.shape == (2, 8) and step2.shape == (2, 8)


class TestSpaceToDepthStem:
    def test_s2d_layer_shapes_and_values(self, rng):
        from deeplearning4j_tpu.nn.conf.layers import SpaceToDepthLayer
        x = rng.normal(size=(2, 8, 8, 3)).astype(np.float32)
        layer = SpaceToDepthLayer(block_size=2)
        out, _ = layer.apply({}, jnp.asarray(x))
        assert out.shape == (2, 4, 4, 12)
        # channel order (di, dj, c): out[.., di*2c_ + dj*c + c_i]
        assert np.allclose(np.asarray(out)[0, 1, 2, 0:3], x[0, 2, 4, :])
        assert np.allclose(np.asarray(out)[0, 1, 2, 3:6], x[0, 2, 5, :])
        assert np.allclose(np.asarray(out)[0, 1, 2, 6:9], x[0, 3, 4, :])
        assert np.allclose(np.asarray(out)[0, 1, 2, 9:12], x[0, 3, 5, :])

    def test_stem_lowering_exact_equivalence(self, rng):
        """7x7/2 SAME conv == s2d(2x2) + 4x4/1 SAME conv with folded weights
        (the MXU stem lowering must be EXACT, not approximate)."""
        from deeplearning4j_tpu.models.resnet import fold_stem_7x7_to_s2d
        from deeplearning4j_tpu.nn.conf.layers import SpaceToDepthLayer
        from deeplearning4j_tpu.ops import convops

        x = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
        w7 = rng.normal(size=(7, 7, 3, 16)).astype(np.float32)
        ref = convops.conv2d(jnp.asarray(x), jnp.asarray(w7),
                             stride=(2, 2), padding="same")
        s2d, _ = SpaceToDepthLayer(block_size=2).apply({}, jnp.asarray(x))
        w4 = fold_stem_7x7_to_s2d(w7)
        out = convops.conv2d(s2d, jnp.asarray(w4), stride=(1, 1),
                             padding="same")
        assert out.shape == ref.shape == (2, 16, 16, 16)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4), \
            np.abs(np.asarray(out) - np.asarray(ref)).max()

    def test_resnet_s2d_stem_builds_and_trains(self, rng):
        from deeplearning4j_tpu.models.resnet import resnet
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        conf = resnet((1, 1), height=32, width=32, width_base=8,
                      n_classes=4, dtype="float32", stem="space_to_depth")
        net = ComputationGraph(conf).init()
        x = rng.normal(size=(4, 32, 32, 3)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 4)]
        loss0 = net.fit_batch([x], [y])
        loss1 = net.fit_batch([x], [y])
        assert np.isfinite(loss1) and float(loss1) < float(loss0) * 1.5


class TestClassicZoo:
    """AlexNet / VGG-16 / deep autoencoder builders (models/classic.py)."""

    def test_alexnet_forward_and_shapes(self, rng):
        from deeplearning4j_tpu.models import alexnet
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        net = MultiLayerNetwork(alexnet(height=64, width=64, n_classes=7,
                                        dtype="float32")).init()
        x = rng.normal(size=(2, 64, 64, 3)).astype(np.float32)
        out = np.asarray(net.output(x))
        assert out.shape == (2, 7)
        assert np.allclose(out.sum(axis=1), 1.0, atol=1e-5)

    def test_vgg16_trains(self, rng):
        from deeplearning4j_tpu.models import vgg16
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        net = MultiLayerNetwork(vgg16(height=32, width=32, n_classes=4,
                                      updater="adam", learning_rate=1e-3,
                                      dtype="float32")).init()
        # batch 4 / 6 steps: VGG16 CPU steps are ~2s each and the test
        # pins "training moves the loss", not a convergence curve
        x = rng.normal(size=(4, 32, 32, 3)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 4)]
        # dropout makes single-step losses noisy, and one unlucky draw can
        # spike a step several-fold: "training moves the loss down" is the
        # BEST of the last three against the first, which no single draw
        # decides (a mean over three is hostage to one spike)
        losses = [float(np.asarray(net.fit_batch(x, y))) for _ in range(6)]
        assert min(losses[-3:]) < losses[0], losses

    def test_deep_autoencoder_reconstructs_curves(self):
        from deeplearning4j_tpu.datasets.fetchers import CurvesDataSetIterator
        from deeplearning4j_tpu.models import deep_autoencoder
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        net = MultiLayerNetwork(deep_autoencoder(
            n_in=784, hidden=(256, 64, 16))).init()
        ds = CurvesDataSetIterator(batch_size=64, num_examples=64,
                                   seed=9).next()
        first = float(np.asarray(net.fit_batch(ds.features, ds.labels)))
        for _ in range(15):
            last = float(np.asarray(net.fit_batch(ds.features, ds.labels)))
        assert last < first

    def test_zoo_configs_json_roundtrip(self):
        from deeplearning4j_tpu.models import alexnet, deep_autoencoder, vgg16
        from deeplearning4j_tpu.nn.conf.multi_layer import (
            MultiLayerConfiguration)

        for conf in (alexnet(height=64, width=64, n_classes=5),
                     vgg16(height=32, width=32, n_classes=5),
                     deep_autoencoder(n_in=32, hidden=(16, 8))):
            restored = MultiLayerConfiguration.from_json(conf.to_json())
            assert restored.to_json() == conf.to_json()


class TestTransformerLM:
    """Decoder-only transformer from the DSL (attention + LN + residual
    vertices) — the long-context model family."""

    def test_trains_on_cyclic_task_and_serde(self, rng):
        from deeplearning4j_tpu.models import transformer_lm
        from deeplearning4j_tpu.nn.conf.graph import (
            ComputationGraphConfiguration)
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        V, T = 8, 16
        conf = transformer_lm(V, n_layers=2, d_model=16, n_heads=2,
                              d_ff=32, learning_rate=1e-2, seed=0)
        # serde round-trip BEFORE training (attention + preprocessor
        # vertices + layer-norm all survive json)
        conf = ComputationGraphConfiguration.from_json(conf.to_json())
        net = ComputationGraph(conf).init()
        ids = np.array([[(i + j) % V for i in range(T + 1)]
                        for j in range(8)])
        eye = np.eye(V, dtype=np.float32)
        x, y = eye[ids[:, :-1]], eye[ids[:, 1:]]
        losses = [float(net.fit_batch([x], [y])) for _ in range(150)]
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
        pred = np.asarray(net.output([x])).argmax(-1)
        acc = (pred[:, 4:] == ids[:, 5:]).mean()
        assert acc > 0.8, acc

    def test_integer_id_path_matches_one_hot(self, rng):
        """input_ids=True (EmbeddingSequenceLayer gather + sparse_mcxent)
        computes the SAME loss as the one-hot path with shared weights —
        one-hot @ W ≡ W[ids], and sparse labels ≡ one-hot labels."""
        import jax
        from deeplearning4j_tpu.models import transformer_lm
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        V, T, b = 11, 12, 4
        mk = lambda ids_mode: ComputationGraph(transformer_lm(
            V, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=7,
            input_ids=ids_mode)).init()
        net_i, net_o = mk(True), mk(False)
        po = jax.device_get(net_o.params)
        po["embed"]["W"] = jax.device_get(net_i.params)["embed"]["W"]
        net_o.params = jax.device_put(po)   # TDD bias is zero-init
        ids = rng.integers(0, V, (b, T + 1)).astype(np.int32)
        eye = np.eye(V, dtype=np.float32)
        li = float(net_i.fit_batch([ids[:, :-1]], [ids[:, 1:]]))
        lo = float(net_o.fit_batch([eye[ids[:, :-1]]], [eye[ids[:, 1:]]]))
        assert li == pytest.approx(lo, abs=1e-4)

    def test_integer_id_path_trains_and_serde(self, rng):
        from deeplearning4j_tpu.models import transformer_lm
        from deeplearning4j_tpu.nn.conf.graph import (
            ComputationGraphConfiguration)
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        V, T = 9, 16
        conf = transformer_lm(V, n_layers=2, d_model=16, n_heads=2,
                              d_ff=32, learning_rate=1e-2, seed=0,
                              input_ids=True)
        conf = ComputationGraphConfiguration.from_json(conf.to_json())
        net = ComputationGraph(conf).init()
        ids = np.array([[(i + j) % V for i in range(T + 1)]
                        for j in range(8)], dtype=np.int32)
        x, y = ids[:, :-1], ids[:, 1:]
        losses = [float(net.fit_batch([x], [y])) for _ in range(60)]
        assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])
        # fit_repeated takes the int inputs too (the bench path)
        out = net.fit_repeated([x], [y], 4)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_sparse_mcxent_equals_dense_mcxent(self, rng):
        from deeplearning4j_tpu import losses as L
        logits = rng.normal(size=(3, 5, 7)).astype(np.float32)
        ids = rng.integers(0, 7, (3, 5))
        eye = np.eye(7, dtype=np.float32)
        sparse = L.score_array("sparse_mcxent", ids, logits, "softmax")
        dense = L.score_array("mcxent", eye[ids], logits, "softmax")
        np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                                   rtol=1e-6, atol=1e-6)
        # per-timestep mask denominator matches the dense convention —
        # declared by the caller from the loss identity (is_sparse), so
        # dense losses fed integer-typed labels keep the per-output
        # contract
        assert L.is_sparse("sparse_mcxent") and not L.is_sparse("mcxent")
        mask = np.ones((3, 5), np.float32)
        mask[:, 3:] = 0.0
        d_sparse = L.masked_denominator(mask, np.asarray(ids), 3,
                                        sparse=True)
        d_dense = L.masked_denominator(mask, eye[ids], 3)
        assert float(d_sparse) == float(d_dense) == 9.0
        d_int_dense = L.masked_denominator(mask, np.asarray(ids), 3)
        assert float(d_int_dense) == 3.0    # per-output: active rows
        with pytest.raises(ValueError, match="softmax"):
            L.get("sparse_mcxent")(ids, logits, "identity")
        # out-of-range ids must poison the loss (NaN), never silently
        # clamp to the last class
        bad = np.array(ids)
        bad[0, 0] = 7                       # == n_out: off-by-one vocab bug
        per = np.asarray(L.get("sparse_mcxent")(bad, logits, "softmax"))
        assert np.isnan(per[0, 0]) and np.isfinite(per[1:]).all()

    def test_causality_end_to_end(self, rng):
        from deeplearning4j_tpu.models import transformer_lm
        from deeplearning4j_tpu.nn.graph_runtime import ComputationGraph
        V, T = 8, 10
        net = ComputationGraph(transformer_lm(
            V, n_layers=2, d_model=16, n_heads=2, d_ff=32, seed=1)).init()
        eye = np.eye(V, dtype=np.float32)
        ids = rng.integers(0, V, (2, T))
        x = eye[ids]
        base = np.asarray(net.output([x]))
        x2 = np.array(x)
        x2[:, -1] = eye[(ids[:, -1] + 1) % V]   # perturb the LAST token
        pert = np.asarray(net.output([x2]))
        assert np.allclose(base[:, :-1], pert[:, :-1], atol=1e-5)
