"""``correct`` for a model that routes: where a configuration states
``limits.logit_gap_outlier_share``, that share of the compared tokens may
lie beyond ``logit_gap``, and the largest gap is read but not compared.

The proof runs ``data/configs/relu_moe-wide.json`` (the tests' family with
64 experts, 8 a token, under ``mixed_bf16``) through the paged engine at its
rehearsal sizes. There the program's bfloat16 router and the float32
reference keep different experts at some tokens, both rightly: the largest
gap of a sound run lies beside the fp8 control's, and the share of tokens
beyond the gap does not. Which requests a run samples depends on which
finished inside its window, so a reading moves by a few tokens from run to
run; ``limits_from`` in the configuration holds what 16 seeds read.
"""

import functools
import json
import os
from unittest import mock

import pytest

from lib import common, compare
from test_control import drive, failed
from test_families import OWN

WIDE = common.load_json("tests", "data", "configs", "relu_moe-wide.json")
LIMITS = WIDE["rehearsal"]["limits"]
SEEDS = WIDE["limits_from"]["rehearsal"]["seeds"][:8]
SERVE_NAMES = ["every_client_returned", "no_compile_in_window",
               "requests_ran_to_length", "served_token_logit_gap_max"]


def reversed_router(net):
    """Every layer's router scores the experts in the reverse order."""
    for vertex, leaves in net.params.items():
        if vertex.endswith("_moe"):
            leaves["router"] = leaves["router"][:, ::-1]


def one_expert_zeroed(net):
    """One expert of the first layer computes nothing."""
    leaves = net.params["blk0_moe"]
    for k in ("w1", "b1", "w2", "b2"):
        leaves[k] = leaves[k].at[0].set(0.0)


@functools.lru_cache(maxsize=None)
def wide(seed, plant=None):
    """One run of the routed cell with the fp8 control read beside it: a
    window in which each lane finishes two requests and most a third (at 9
    seconds a slow host left a lane at one, and ``tokens_compared`` failed
    a sound run)."""
    with mock.patch.dict(os.environ, BENCH_READINGS="1"):
        return drive("serve-moe-wide", seed, seconds=12, control_mode="fp8",
                     plant=plant, **OWN)


# -- the count --------------------------------------------------------------

@pytest.mark.parametrize("gaps,limit,share", [
    ([], 0.1, None),
    ([0.0, 0.1, 0.05], 0.1, 0.0),              # at the limit is not beyond
    ([0.0, 0.2, 0.05, 1.5], 0.1, 0.5),
    ([0.0, float("nan")], 0.1, 0.5),           # not a number lies beyond
    ([0.3], 0.1, 1.0)])
def test_the_share_of_the_gaps_beyond_a_limit(gaps, limit, share):
    assert compare.outlier_share(gaps, limit) == share


# -- seed x rule ------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_a_sound_run_is_correct_under_the_stated_share(seed):
    line = wide(seed)
    assert line["correct"] is True, line["compared"]
    assert list(line["compared"]) == SERVE_NAMES[:3] + [
        "served_token_gap_outlier_share", "tokens_compared"]
    share = line["compared"]["served_token_gap_outlier_share"]
    assert share["limit"] == LIMITS["logit_gap_outlier_share"]
    assert share["value"] == line["readings"]["gaps_outlier_share"]
    assert line["readings"]["n_tokens"] >= WIDE["rehearsal"]["check_tokens"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_put_in_the_programs_place_would_fail(seed):
    readings = wide(seed)["readings"]
    assert readings["control_outlier_share"] > \
        LIMITS["logit_gap_outlier_share"]
    assert readings["control_gaps_max"] > LIMITS["logit_gap"]


def test_the_largest_gap_fails_sound_runs_and_the_share_separates():
    runs = [wide(seed)["readings"] for seed in SEEDS]
    # the dense rule would have failed more than half of these sound runs
    beyond = [r["gaps_max"] > LIMITS["logit_gap"] for r in runs]
    assert sum(beyond) > len(SEEDS) / 2, [r["gaps_max"] for r in runs]
    # and no limit on the largest gap stands: an upper reading has to be
    # three times the lower (PERF.md section 2)
    assert min(r["control_gaps_max"] for r in runs) < \
        3.0 * max(r["gaps_max"] for r in runs)
    # the stated share: twice the largest sound share or above, half of
    # the control's smallest or below
    stated = LIMITS["logit_gap_outlier_share"]
    assert stated >= 2.0 * max(r["gaps_outlier_share"] for r in runs)
    assert stated <= 0.5 * min(r["control_outlier_share"] for r in runs)


@pytest.mark.parametrize("size", [s for s in ("rehearsal", "chip")
                                  if s in WIDE["limits_from"]])
def test_the_stated_share_was_set_from_the_readings_written_down(size):
    read = WIDE["limits_from"][size]
    limits = LIMITS if size == "rehearsal" else WIDE["limits"]
    assert len(read["seeds"]) == len(read["sound_share"]) \
        == len(read["control_share"]) >= 6
    stated = limits["logit_gap_outlier_share"]
    assert 2.0 * max(read["sound_share"]) <= stated \
        <= 0.5 * min(read["control_share"])
    # the dense rule would have failed sound runs (most of them at the
    # rehearsal's widths, half at the chip's), and no limit on the largest
    # gap has an upper reading three times its lower
    beyond = sum(g > limits["logit_gap"] for g in read["sound_gap_max"])
    assert beyond > len(read["seeds"]) / (2 if size == "rehearsal" else 3)
    assert min(read["control_gap_max"]) < 3.0 * max(read["sound_gap_max"])


# -- faults -----------------------------------------------------------------

def test_the_reversed_router_comes_out_false():
    line = wide(SEEDS[1], reversed_router)
    assert line["correct"] is False
    assert failed(line) == ["served_token_gap_outlier_share"]
    assert line["compared"]["served_token_gap_outlier_share"]["value"] > 0.5


def test_one_expert_zeroed_shows_in_the_share_and_fails_at_float32():
    """What this fault reads beside the stated share depends on the seed
    (``limits_from``: 0.027 to 0.105 against 0.04); the float32
    configuration, where no routing flips, is what catches it."""
    sound, broken = wide(SEEDS[1]), wide(SEEDS[1], one_expert_zeroed)
    assert broken["readings"]["gaps_outlier_share"] > \
        2.0 * sound["readings"]["gaps_outlier_share"]
    line = drive("serve-moe-chat", 13, plant=one_expert_zeroed, **OWN)
    assert line["correct"] is False
    assert failed(line) == ["served_token_logit_gap_max"]


# -- the key absent, and too few tokens -------------------------------------

def test_without_the_key_the_compared_names_and_limits_are_the_parents():
    cfg = common.load_json("tests", "data", "configs", "relu_moe.json")
    assert "logit_gap_outlier_share" not in cfg["limits"]
    line = drive("serve-moe-chat", 13, **OWN)
    assert line["correct"] is True
    assert list(line["compared"]) == SERVE_NAMES
    assert [v["limit"] for v in line["compared"].values()] == [
        0.0, 0.0, 0.0, cfg["limits"]["logit_gap"]]


def test_too_few_tokens_fail_by_tokens_compared_alone():
    line = drive("serve-moe-chat", 13, rehearsal_sizes={
        "check_tokens": 100000,
        "limits": {"logit_gap": 0.05, "logit_gap_outlier_share": 0.0}},
        **OWN)
    assert line["correct"] is False
    assert failed(line) == ["tokens_compared"]
    assert line["compared"]["served_token_gap_outlier_share"] == {
        "value": 0.0, "limit": 0.0}


def test_no_configuration_of_the_benchmark_states_the_key():
    bench = common.load_json("..", "BENCHMARK.json")
    for entry in bench["configs"]:
        with open(os.path.join(common.ROOT, entry["file"])) as f:
            cfg = json.load(f)
        for limits in (cfg["limits"], cfg["rehearsal"].get("limits", {})):
            assert "logit_gap_outlier_share" not in limits
