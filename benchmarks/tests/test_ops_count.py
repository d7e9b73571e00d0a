"""The analytic operation counts against a count by hand at one small
shape, and the peaks table's refusal of a chip it does not know."""

import pytest

from lib import common, ops_count, peaks, readers

opt = common.load_family({"model_type": "opt"})

# d 8, ff 16, 2 layers, vocabulary 32, 2 heads of 4
CFG = {"hidden_size": 8, "ffn_dim": 16, "num_hidden_layers": 2,
       "vocab_size": 32, "num_attention_heads": 2}


def test_matmul_params_by_hand():
    # a layer: Wqkv 8x24 = 192, Wo 8x8 = 64, W1 8x16 = 128, W2 16x8 = 128:
    # 512; two layers 1024; head 8x32 = 256
    assert opt.matmul_params(CFG) == 1280


def test_train_flops_per_token_by_hand():
    # forward of a token at T = 6: 2 x 1280 in the matrices; attention over
    # T/2 = 3 keys: q.k and p.v are 8 multiply-adds each a key (2 heads x 4)
    # = 2 x 2 x 8 x 3 = 96 a layer, 192 in two; backward twice the forward
    assert opt.train_flops_per_token(CFG, 6) == 3 * (2560 + 192)


def test_serve_flops_by_hand():
    # 5 tokens computed, 9 (token, key) pairs: 5 x 2560 + 9 x (2 layers x
    # 4 x 8 = 64)
    assert opt.serve_flops(CFG, {"computed_tokens": 5, "attended_keys": 9,
                                 "deltas": []}) == 12800 + 576


def test_flash_counts_by_hand():
    # [batch 1, T 4, 2 heads, 4]: 10 (query, key) pairs on and under the
    # diagonal; a product over them is 2 x 1 x 2 x 4 x 10 = 160
    assert ops_count.causal_attention_flops(1, 4, 2, 4, 2) == 320
    assert ops_count.causal_attention_flops(1, 4, 2, 4, 4) == 640
    # a tensor is 1 x 4 x 2 x 4 x 2 B = 64 B
    assert ops_count.attention_tensor_bytes(1, 4, 2, 4, 4) == 256
    assert ops_count.attention_tensor_bytes(1, 4, 2, 4, 8) == 512


def test_flash_roofline_reader_by_hand():
    """One forward call and one layer's two backward calls at the shape
    above, on a chip of 1000 op/s and 100 B/s: forward needs max(320/1000,
    256/100) = 2.56 s, backward max(640/1000, 512/100) = 5.12 s; they took
    10 + 22 s, so 24 %."""
    run = {"cfg": CFG, "mix": {"batch": 1, "seq_len": 4}, "family": opt,
           "peaks": {"flops_bf16": 1000.0, "hbm_bytes_per_s": 100.0},
           "trace": {"chips": 1,
                     "op_seconds": {"jvp__:tpu_custom_call": 10.0,
                                    "transpose_jvp___:tpu_custom_call": 22.0,
                                    "fusion": 5.0},
                     "op_calls": {"jvp__:tpu_custom_call": 1,
                                  "transpose_jvp___:tpu_custom_call": 2,
                                  "fusion": 7}}}
    spec = {"kind": "flash_roofline",
            "fwd_pattern": "^jvp.*:tpu_custom_call$",
            "bwd_pattern": "^transpose.*:tpu_custom_call$",
            "bwd_calls_per_layer": 2}
    assert readers.flash_roofline(spec, run) == pytest.approx(24.0)
    run["trace"]["op_seconds"] = {"fusion": 5.0}
    run["trace"]["op_calls"] = {"fusion": 7}
    assert readers.flash_roofline(spec, run) is None    # silent, never 0


def test_mfu_reader_by_hand():
    run = {"flops": 500.0, "window_s": 2.0, "chips": 1,
           "peaks": {"flops_bf16": 1000.0}}
    assert readers.mfu({}, run) == pytest.approx(25.0)
    assert readers.mfu({}, dict(run, flops=0.0)) is None


def test_peaks_table():
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks recorded"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_trace_share_reader_by_hand():
    """Kernels took 6 s of a slice in which the device was busy 8 s; a
    ``while`` that encloses other operations must not swell the base."""
    run = {"trace": {"busy_s": 8.0,
                     "op_seconds": {"attn:tpu_custom_call": 6.0,
                                    "while": 7.0, "fusion": 1.0}}}
    spec = {"kind": "trace_share", "pattern": ":tpu_custom_call$"}
    assert readers.trace_share(spec, run) == pytest.approx(75.0)
    assert readers.trace_share({"pattern": "^all-reduce"}, run) is None
    assert readers.trace_share(spec, {}) is None
