"""``correct`` has to be able to come out false.

The control: the plain reference put in the program's place with fp8
operands, one precision below the ``mixed_bf16`` the configurations state,
fails at least one compared number, where the same reference in bfloat16
(what the configurations state) passes all. The faults: a run driven
through the harness with the timed path broken underneath reports
``correct: false``. The sizes are ones a test run can hold; the limits at
these sizes are in the configurations' ``rehearsal`` blocks and here, set as
PERF.md section 2 sets the chip's: above the largest that a dozen sound
seeds read on this CPU, below the smallest that the control reads. The
readings at the cells' own sizes come from ``chip_control.py`` on the chip.
"""

import contextlib
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from lib import common, control, traffic


def drive(cell, seed, trace=0, seconds=1, **env_extra):
    """One rehearsal run inside this process; its result line."""
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace",
                             str(trace), "--rehearse"], env_extra=env_extra)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert err.getvalue().strip().splitlines()[-1] == \
        f"correct: {line['correct']}"
    return line


def failed(line):
    return sorted(k for k, v in line["compared"].items()
                  if not v["value"] <= v["limit"])


# -- training ---------------------------------------------------------------

@pytest.fixture(scope="module")
def train_sizes():
    cfg = common.load_json("configs", "opt-1.3b-train.json")
    cfg, mix = bench_run.rehearsal_sizes(cfg, traffic.load("t8192"))
    return (common.load_reference(cfg), common.load_family(cfg), cfg, mix)


@pytest.mark.parametrize("seed", [2147483659, 7, 8])
def test_training_control_fails_and_stated_precision_passes(train_sizes,
                                                            seed):
    cfg = train_sizes[2]
    stated = control.control_against_reference(*train_sizes, seed,
                                               mode="bf16")
    assert stated.correct, stated.compared()
    fp8 = control.control_against_reference(*train_sizes, seed, mode="fp8")
    assert not fp8.correct
    assert fp8.compared()["grad_diff_worst_leaf"]["value"] > \
        cfg["limits"]["grad_diff"]


def test_half_batch_planted_in_the_reference_fails(train_sizes):
    cfg = train_sizes[2]
    v = control.control_against_reference(*train_sizes, 9,
                                          fault=control.HALF_BATCH)
    got = v.compared()
    for name, limit in (("grad_norm_worst_leaf_gap", "grad_norm_gap"),
                        ("grad_diff_worst_leaf", "grad_diff"),
                        ("change_norm_worst_leaf_gap", "change_norm_gap")):
        assert got[name]["value"] > cfg["limits"][limit]


def plant_state_unchanged(net):
    """The step computes everything and hands its state back as it came."""
    real = net._make_train_step()

    def step(params, opt, states, inputs, labels, masks, rng, it):
        _, _, new_states, loss = real(params, opt, states, inputs, labels,
                                      masks, rng, it)
        return params, opt, new_states, loss

    net._jit_cache["train_step_override"] = jax.jit(step)


def plant_half_batch(net):
    """The later half of the rows never reaches the step: the first half
    goes twice, so the mean is over the rest."""
    real = net._make_train_step()

    def halve(a):
        h = a.shape[0] // 2
        return jnp.concatenate([a[:h], a[:h]])

    def step(params, opt, states, inputs, labels, masks, rng, it):
        return real(params, opt, states, [halve(a) for a in inputs],
                    [halve(a) for a in labels], masks, rng, it)

    net._jit_cache["train_step_override"] = jax.jit(step)


def test_sound_training_run_is_correct():
    line = drive("train-opt-t8192", 11)
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("plant,must_fail", [
    (plant_state_unchanged, "change_norm_worst_leaf_gap"),
    (plant_half_batch, "grad_diff_worst_leaf")])
def test_training_fault_comes_out_false(plant, must_fail):
    line = drive("train-opt-t8192", 11, plant=plant)
    assert line["correct"] is False
    assert must_fail in failed(line)
    assert "no_compile_in_window" not in failed(line)


# -- serving ----------------------------------------------------------------

# at the rehearsal's 64-wide model every logit is nearly the same and no
# precision shows; this is the smallest size tried at which fp8 does
SERVE_SIZES = {"hidden_size": 512, "ffn_dim": 2048, "num_attention_heads": 8,
               "vocab_size": 8192, "num_hidden_layers": 2,
               # a dozen seeds of the program read at most 0.0115 on this
               # CPU, eight of the control at least 0.0449 (PR 24)
               "limits": {"logit_gap": 0.025}}


@pytest.mark.parametrize("seed", [2147483659, 5])
def test_serving_control_reads_above_the_limit(seed, monkeypatch):
    monkeypatch.setenv("BENCH_READINGS", "1")
    line = drive("serve-opt-docqa", seed, control_mode="fp8",
                 rehearsal_sizes=SERVE_SIZES)
    assert line["correct"] is True, line["compared"]
    limit = SERVE_SIZES["limits"]["logit_gap"]
    assert line["compared"]["served_token_logit_gap_max"]["limit"] == limit
    assert line["readings"]["control_gaps_max"] > limit
    # the share of the control's tokens beyond the limit is read beside it
    assert 0.0 < line["readings"]["control_outlier_share"] <= 1.0
    assert line["readings"]["n_requests"] >= 3      # hits and a miss


def test_altered_token_comes_out_false(monkeypatch):
    """Every fused block hands back its first lane's first token altered,
    where the tokens are produced; everything else is sound."""
    from deeplearning4j_tpu.serving.decode import PagedDecodeEngine
    real = PagedDecodeEngine.run_fused

    def altered(self, *args, **kw):
        toks, valid, n_emitted = real(self, *args, **kw)
        toks = np.array(toks)
        toks[0, 0] = (toks[0, 0] + 1) % self.vocab
        return toks, valid, n_emitted

    sound = drive("serve-opt-chat", 13)
    assert sound["correct"] is True, sound["compared"]
    monkeypatch.setattr(PagedDecodeEngine, "run_fused", altered)
    line = drive("serve-opt-chat", 13)
    assert line["correct"] is False
    assert failed(line) == ["served_token_logit_gap_max"]
