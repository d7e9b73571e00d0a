"""The family seam: what a configuration's ``model_type`` brings as one
file (``families/<model_type>.py``), and the readers that are files.

Moving OPT's leaves, builder and counts into ``families/opt.py`` changed no
draw and no count: ``data/parent_draw.json`` was recorded from the parent
commit before anything moved. A stored type below float32 is rounded once,
inside the draw. A family that is not OPT (``data/families/relu_moe.py``,
this repo's own expert block) runs a serving and a training cell to a
``correct`` line with no file of ``benchmarks/lib`` knowing it, and its
cell list brings a reader of a kind of its own (``data/readers/``).
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from lib import common, control, readers, traffic, weights
from test_control import drive

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OWN = {"benchmark": os.path.join(DATA, "cells.json"), "dirs": [DATA]}
with open(os.path.join(DATA, "parent_draw.json")) as f:
    PARENT = json.load(f)


def sizes(name: str, which: str) -> dict:
    cfg = common.load_json("configs", f"{name}.json")
    return {**cfg, **cfg["rehearsal"]} if which == "rehearsal" else cfg


# -- (a) nothing moved ------------------------------------------------------

@pytest.mark.parametrize("name", sorted(PARENT["weights"]))
@pytest.mark.parametrize("seed", [7, 2147483659])
def test_every_leaf_is_the_parents_bit_for_bit(name, seed):
    cfg = sizes(name, "rehearsal")
    flat = weights.make_weights(common.load_family(cfg), cfg, seed)
    got = {k: [str(v.dtype), list(v.shape),
               hashlib.sha256(np.asarray(v).tobytes()).hexdigest()]
           for k, v in flat.items()}
    assert got == PARENT["weights"][name][str(seed)]


@pytest.mark.parametrize("name", sorted(PARENT["counts"]))
@pytest.mark.parametrize("which", ["rehearsal", "published"])
def test_the_analytic_counts_are_the_parents(name, which):
    cfg = sizes(name, which)
    family, want = common.load_family(cfg), PARENT["counts"][name][which]
    for computed, keys, value in want["serve_flops"]:
        assert family.serve_flops(cfg, {
            "computed_tokens": computed, "attended_keys": keys,
            "deltas": []}) == value
    for seq_len, value in want["train_flops_per_token"]:
        assert family.train_flops_per_token(cfg, seq_len) == value


# -- (b) a stored type below float32 ----------------------------------------

@pytest.fixture(scope="module")
def stored_bf16():
    cfg = sizes("opt-1.3b", "rehearsal")
    family = common.load_family(cfg)
    low = dict(cfg, param_dtype="bfloat16")
    return (family, cfg, low, weights.make_weights(family, cfg, 7),
            weights.make_weights(family, low, 7))


def test_a_bfloat16_leaf_is_rounded_once_inside_the_draw(stored_bf16):
    family, cfg, low, as_f32, as_bf16 = stored_bf16
    # the jitted maker's own output types: no float32 copy leaves the draw
    make, _ = weights._jitted(weights._shapes_key(family, low))
    made = jax.eval_shape(make, weights._seed_words(7))
    assert {str(v.dtype) for v in made.values()} == {"bfloat16"}
    for k, v in as_bf16.items():
        assert v.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(v, np.float32), np.asarray(
            as_f32[k].astype(jnp.bfloat16), np.float32)), k
    # the change of a bfloat16 leaf is taken in float32
    moved = {k: v + jnp.asarray(0.5, v.dtype) for k, v in as_bf16.items()}
    change = weights.change_norms(family, low, 7, moved)
    assert float(change["lnf_b"]) == pytest.approx(
        0.5 * np.sqrt(cfg["hidden_size"]), rel=0.02)


def test_to_program_refuses_a_type_the_program_does_not_hold(stored_bf16):
    family, cfg, low, as_f32, as_bf16 = stored_bf16

    def like(dtype):
        tree = {}
        for name, (vertex, leaf) in family.program_names(cfg).items():
            tree.setdefault(vertex, {})[leaf] = jax.ShapeDtypeStruct(
                as_f32[name].shape, dtype)
        return tree

    tree = weights.to_program(family, low, as_bf16, like(jnp.bfloat16))
    assert tree["blk0_attn"]["Wqkv"] is as_bf16["l0.wqkv"]
    assert weights.from_program(family, low, tree)["head_b"] \
        is as_bf16["head_b"]
    # the program's own tree under mixed_bf16 is float32
    with pytest.raises(ValueError, match="made bfloat16.*holds .* float32"):
        weights.to_program(family, low, as_bf16, like(jnp.float32))
    weights.to_program(family, cfg, as_f32, like(jnp.float32))


def test_the_reference_computes_on_the_rounded_values_in_float32(
        stored_bf16):
    family, cfg, low, _, as_bf16 = stored_bf16
    ref = common.load_reference(cfg)
    ids, at = np.arange(24, dtype=np.int32) % cfg["vocab_size"], [3, 23]
    got = ref.logits_at(as_bf16, ids, at, cfg=low, q_block=8)
    same = ref.logits_at({k: v.astype(jnp.float32)
                          for k, v in as_bf16.items()}, ids, at, cfg=low,
                         q_block=8)
    assert got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(same))


# -- (c) what is not there fails with its path ------------------------------

def test_a_missing_family_fails_with_the_path_before_any_device_work(
        tmp_path, monkeypatch):
    with pytest.raises(FileNotFoundError,
                       match="benchmarks/families/nonesuch.py"):
        common.load_family({"model_type": "nonesuch"})
    cfg = dict(sizes("opt-1.3b", "published"), model_type="nonesuch")
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    with open(OWN["benchmark"]) as f:
        cells = json.load(f)
    cells["configs"][0]["file"] = str(tmp_path / "cfg.json")
    (tmp_path / "cells.json").write_text(json.dumps(cells))

    def no_device(*a, **kw):
        raise AssertionError("looked for a device first")

    monkeypatch.setattr(common, "device_info", no_device)
    with pytest.raises(FileNotFoundError) as e:
        bench_run.main(["--workload", "serve-moe-chat", "--rehearse"],
                       env_extra={"benchmark": str(tmp_path / "cells.json"),
                                  "dirs": [DATA]})
    assert "benchmarks/tests/data/families/nonesuch.py" in str(e.value)
    assert "benchmarks/families/nonesuch.py" in str(e.value)


def test_a_missing_reader_fails_with_the_path():
    spec = {"name": "m", "reader": {"kind": "nonesuch"}}
    for call in (lambda: readers.read(spec, {}),
                 lambda: readers.wants(spec["reader"]),
                 lambda: readers.gauges(spec["reader"])):
        with pytest.raises(FileNotFoundError,
                           match="benchmarks/readers/nonesuch.py"):
            call()


# -- (d), (e) a family that is not OPT, and a reader that is a file ----------

def test_the_second_family_covers_the_programs_expert_block():
    cfg = common.load_json("tests", "data", "configs", "relu_moe.json")
    family = common.load_family(cfg, (DATA, common.BENCH))
    shapes = family.leaf_shapes(cfg)
    assert shapes["l1.w1"] == ((4, 64, 128), "matrix")
    assert shapes["l0.router"] == ((64, 4), "matrix")
    assert "l0.ff1" not in shapes and set(shapes) == set(
        family.program_names(cfg))
    # by hand: a layer 4 x 64² + 64 x 4 + 2 experts x 2 x 64 x 128 = 49,408;
    # two layers and the 64 x 512 head: 131,584; the experts' 65,536 of it
    assert family.matmul_params(cfg) == 131584
    assert family.train_flops_per_token(cfg, 6) == 3 * (2 * 131584
                                                        + 2 * 4 * 64 * 3)


def test_the_second_family_trains_to_a_correct_line():
    line = drive("train-moe", 11, trace=1, **OWN)
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) >= {"grad_norm_worst_leaf_gap",
                                     "grad_diff_worst_leaf",
                                     "change_norm_worst_leaf_gap"}
    assert line["metrics"]["train_mfu"]["value"] > 0.0


def test_half_of_the_batch_left_out_of_the_second_family_fails():
    cfg, mix = bench_run.rehearsal_sizes(
        common.load_json("tests", "data", "configs", "relu_moe-train.json"),
        traffic.load("t8192"))
    v = control.control_against_reference(
        common.load_reference(cfg), common.load_family(cfg, (DATA,)), cfg,
        mix, 9, fault=control.HALF_BATCH)
    assert not v.correct
    got = v.compared()
    for name, limit in (("grad_norm_worst_leaf_gap", "grad_norm_gap"),
                        ("grad_diff_worst_leaf", "grad_diff"),
                        ("change_norm_worst_leaf_gap", "change_norm_gap")):
        assert got[name]["value"] > cfg["limits"][limit]


def test_the_second_family_serves_to_a_correct_line_and_its_reader_is_found():
    line = drive("serve-moe-chat", 13, trace=1, **OWN)
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["served_token_logit_gap_max"]["limit"] == \
        common.load_json("tests", "data", "configs",
                         "relu_moe.json")["limits"]["logit_gap"]
    # the family's WANTS were read at the window's edges: its count came out
    assert line["metrics"]["serve_mfu"]["value"] > 0.0
    # data/readers/expert_flops_share.py, which asks the family
    assert line["metrics"]["expert_flops_share"] == {
        "value": pytest.approx(100.0 * 65536 / 131584), "unit": "%"}


def test_a_reader_file_is_found_by_its_kind_and_may_stay_silent():
    metric = common.load_json("tests", "data", "metrics",
                              "expert_flops_share.json")
    assert metric["reader"]["kind"] not in readers.KINDS
    assert readers.wants(metric["reader"], (DATA,)) == \
        [metric["reader"]["seen"]]
    assert readers.gauges(metric["reader"], (DATA,)) == []

    class Edges:
        def delta(self, want):
            return 12.0

    run = {"family": common.load_family({"model_type": "opt"}),
           "cfg": sizes("opt-1.3b", "rehearsal"), "edges": Edges()}
    assert readers.read(metric, run, (DATA,)) is None    # OPT has no experts
    with pytest.raises(FileNotFoundError,
                       match="readers/expert_flops_share.py"):
        readers.read(metric, run)                        # not in benchmarks/
