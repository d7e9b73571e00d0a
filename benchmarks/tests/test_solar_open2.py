"""The ``solar_open2`` family and its cell, ``serve-solar2-longprompt``: the
configuration's arithmetic at the published widths, the file against the
catalog, the family's leaves against the program's tree, the cell and its
metrics entered by NAME (not by their place in ``per_layer``, which later
PRs append to), the mix as the cell states it, the counts by hand, the cell
rehearsed on the CPU with the KDA metrics read, the fp8 control and three
planted faults failing the rehearsal's limit, and the parent's clean
refusal.

At the rehearsal's sizes (float32, hidden 64) a sound run serves the
reference's own best token everywhere (its largest gap reads 0.0), so the
rehearsal's ``logit_gap`` of 0.001 lies under what the control and the
planted faults read.
"""

import os

import numpy as np
import pytest

from lib import common, traffic
from lib.train_cell import build_net
from deeplearning4j_tpu.nn.conf.kda import CHUNK
from test_control import drive, failed

CELL = "serve-solar2-longprompt"
CONFIG = "solar-open2-250b"
METRICS = ("kda_pad_position_share", "kda_state_kib_per_decoded_token")
CATALOG = {      # the catalog's config, as the model-configs guide has it
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
# metrics the cell must report beside its own, and those it must not
LISTED = ("paged_read_window_share", "paged_read_chunk_rounded_share",
          "paged_read_long_trip_share", "moe_held_pair_share",
          "moe_computed_over_routed", "moe_gated_ffn_roofline",
          "state_arena_gib", "kv_kib_per_cached_token")
UNLISTED = ("moe_expert_load_peak_over_mean", "prefix_hit_token_share",
            "eva_summary_row_share", "eva_read_kib_per_decoded_token",
            "eva_held_rows_per_context_token")


@pytest.fixture(scope="module")
def cfg():
    return common.load_json("configs", f"{CONFIG}.json")


@pytest.fixture(scope="module")
def bench():
    return common.load_json("..", "BENCHMARK.json")


def by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_published_widths_sum_to_the_stated_sizes(cfg):
    family = common.load_family(cfg)
    shapes = family.leaf_shapes(cfg)
    sizes = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}

    def layer(i, leaves):
        return sum(sizes[f"l{i}.{k}"] for k in leaves)
    assert family.layer_kinds(cfg) == "*KKK"
    # a KDA layer: q, k, v 100.66 M, o 33.55 M, two low-rank gates 2 x
    # 1.57 M, beta 0.26 M, convs 0.10 M, A_log, dt_bias, the norm's gain
    assert sizes["l1.W_in"] == 4096 * (3 * 8192 + 64 + 2 * 128)
    assert sizes["l1.conv_w"] == 3 * 8192 * 4
    assert round(layer(1, family.KDA) / 1e6, 1) == 137.7
    # the GQA layer: q 33.55 + k, v 8.39 + gate 33.55 + o 33.55
    assert sizes["l0.Wqkv"] == 4096 * (64 + 2 * 8) * 128
    assert round(layer(0, family.GQA) / 1e6, 1) == 109.1
    # an expert layer: router 1.31 + shared 15.73 + 40 x 15.73
    assert round(layer(2, family.EXPERTS) / 1e6, 1) == 646.2
    assert sizes["embed"] == sizes["head_w"] == 24576 * 4096
    assert round(sum(sizes.values()) / 1e9, 2) == 3.31
    assert round(sum(sizes.values()) * 2 / 2 ** 30, 2) == 6.16
    assert set(family.program_names(cfg)) == set(shapes)


def test_the_leaves_are_the_program_tree():
    small = common.load_json("tests", "data", "configs",
                             "solar-open2-small.json")
    family = common.load_family(small)
    net = build_net(family, small, 3)
    names = family.program_names(small)
    tree = {v: set(leaves) for v, leaves in net.params.items() if leaves}
    covered = {}
    for vertex, leaf in names.values():
        covered.setdefault(vertex, set()).add(leaf)
    assert covered == tree
    shapes = family.leaf_shapes(small)
    for name, (vertex, leaf) in names.items():
        assert tuple(net.params[vertex][leaf].shape) == shapes[name][0], name


def test_the_file_keeps_the_catalog_but_for_what_reduced_lists(cfg, bench):
    entry = by_name(bench["configs"], CONFIG)
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    reduced = {"num_hidden_layers": 4, "n_routed_experts": 40,
               "vocab_size": 24576}
    assert set(entry["reduced"]) == set(reduced)
    for key, value in CATALOG.items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg[f"{key}_published"] == value
    assert cfg["n_routed_experts_published"] == 320
    for key in ("kda", "kda_gate_rank", "gqa", "router", "experts", "mtp",
                "weights", "dtype", "cache", "engine"):
        assert cfg["assumed"][key]
    assert "8 chips" in cfg["deployment"] and "32 lanes" in cfg["deployment"]
    eng = cfg["engine"]
    assert (eng["max_batch"], eng["page_size"], eng["pages_per_seq"],
            eng["num_pages"], eng["block_len"], eng["prefill_chunk"],
            eng["prefix_cache"]) == (32, 16, 1056, 28672, 8, 128, False)
    longest = traffic.load("longprompt")["totals"]["longest_request_tokens"]
    assert longest <= eng["page_size"] * eng["pages_per_seq"] == 16896
    # a prefill chunk is whole chunks of the full-sequence form
    assert eng["prefill_chunk"] % CHUNK == 0
    assert cfg["reference_pad"] % 256 == 0


def test_the_limits_state_where_they_come_from(cfg):
    """The routed rule's two limits over at least 1,000 compared tokens:
    between the chip's sound and fp8-control readings at the cell's size
    where the file holds them, and the file says so where it does not."""
    limits, source = cfg["limits"], cfg["limits_from"]
    assert cfg["check_tokens"] >= 1000
    assert set(limits) == {"logit_gap", "logit_gap_outlier_share"}
    chip = source["chip"]
    if chip == "not measured":
        assert source["rule"].startswith("NOT SET FROM CHIP READINGS")
        return
    share = limits["logit_gap_outlier_share"]
    assert 2 * max(chip["sound_share"]) <= share \
        <= min(chip["control_share"]) / 2
    assert min(chip["fault_share"]) > share


def test_the_cell_and_its_metrics_are_entered_by_name(bench):
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longprompt", 1)
    assert len(cell["why"]) <= 200 and "8x" in cell["why"]
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    generic = {m["name"] for m in bench["per_layer"]
               if "serve-evabyte-longbytes" in m.get("workloads", [])
               and not m["name"].startswith("eva_")}
    assert {"serve_tokens_per_s", "decode_tokens_per_s", *generic, *LISTED,
            *METRICS} <= listed
    for name in UNLISTED:
        assert name not in listed, name
    for name in METRICS:
        entry = by_name(bench["per_layer"], name)
        assert entry["workloads"] == [CELL]
        assert entry["layer"] == "linear attention"
        assert entry["moves"] == "serve_tokens_per_s"
        own = common.load_json("metrics", f"{name}.json")
        assert own["name"] == name and own["unit"] == entry["unit"]


def test_the_mix_is_as_stated():
    mix = traffic.load("longprompt")
    assert mix["clients"] == 32 and mix["kind"] == "serve_closed_loop"
    assert mix["stagger_s"] == 0.25
    rows = np.array([r for c in mix["schedule"] for r in c])
    assert [len(c) for c in mix["schedule"]] == [12] * 32
    assert rows[:, 0].min() >= 2048 and rows[:, 0].max() <= 8192
    assert 4200 < rows[:, 0].mean() < 4700          # log-uniform: ~4,430
    assert rows[:, 1].min() >= 128 and rows[:, 1].max() <= 512
    assert (rows[:, 2] == -1).all() and (rows[:, 3] == 0).all()
    assert (rows[:, 0] + rows[:, 1]).max() <= 8704
    assert mix["totals"] == traffic.totals(mix)


def test_counts_by_hand(cfg):
    family = common.load_family(cfg)
    d, hd, r, e, f = 4096, 8192, 128, 320, 1280
    kda = d * (3 * hd + 64 + 2 * r) + 2 * r * hd + hd * d
    gqa = d * (64 + 16) * 128 + 2 * d * hd
    dense = 3 * kda + gqa + 4 * d * (e + 3 * f)
    # 200 tokens computed, 1,000 (token, key) pairs in the GQA layer, 300
    # pairs held, 10 decoded tokens and 2 paged dispatches for the head
    work = {"computed_tokens": 200.0, "attended_keys": 1000.0,
            "deltas": [300.0, 10.0, 2.0]}
    want = ((2 * dense + 3 * 6 * 64 * 128 * 128) * 200
            + 6 * d * f * 300 + 4 * 64 * 128 * 1000 + 2 * d * 24576 * 12)
    assert family.serve_flops(cfg, work) == want
    assert family.serve_flops(cfg, dict(work, deltas=[None] * 3)) is None
    ops, nbytes = family.grouped_ffn_work(cfg, 300.0, 20.0)
    assert ops == 6 * d * f * 300
    assert nbytes == 20 * 3 * d * f * 2 + 300 * d * 6


@pytest.mark.parametrize("seed", [2147483659, 7])
def test_fp8_control_fails_the_rehearsal_limit(seed, monkeypatch):
    """The reference against the program at the rehearsal's size: a sound
    run reads a largest gap under the limit, the fp8 control one above."""
    monkeypatch.setenv("BENCH_READINGS", "1")
    line = drive(CELL, seed, control_mode="fp8")
    assert line["correct"] is True, line["compared"]
    limit = line["compared"]["served_token_logit_gap_max"]["limit"]
    assert line["readings"]["gaps_max"] <= limit < \
        line["readings"]["control_gaps_max"]


def test_the_kda_metrics_read_in_a_traced_rehearsal():
    """Every per-layer metric that needs no device is in the line, the two
    KDA metrics among them: prefill buckets and prompt tails pad some of
    the chunked positions, and a decoded token costs at least the state of
    the rehearsal's three KDA layers read and written."""
    line = drive(CELL, 11, trace=1)
    assert line["correct"] is True, line["compared"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 < m["kda_pad_position_share"] < 100.0
    lane_kib = 3 * 4 * (3 * 3 * 64 + 4 * 16 * 16) / 1024
    assert m["kda_state_kib_per_decoded_token"] >= 2 * lane_kib
    assert m["state_arena_gib"] * 2 ** 30 == pytest.approx(8 * lane_kib * 1024)
    # the one GQA layer's K and V rows, 2 K/V heads of 16, float32
    assert m["kv_kib_per_cached_token"] == 2 * 2 * 16 * 4 / 1024
    assert m["compiles_in_window.serve"] == 0.0
    for name in ("serve_mfu", "moe_held_pair_share", "paged_read_window_share",
                 "prefill_dispatch_share", "kv_pages_peak_share"):
        assert name in m, name


# -- planted faults ------------------------------------------------------------

def plant(target, name, value):
    """A plant for ``drive``: ``target.name`` replaced by ``value`` while the
    run lasts (the program's jits are traced in the run's own engine)."""
    real = getattr(target, name)

    def put(net):
        setattr(target, name, value)
        net._unplant = lambda: setattr(target, name, real)
    return put


def zero_state_at_every_dispatch():
    """Every KDA lane of every dispatch starts from zero state."""
    from deeplearning4j_tpu.nn.conf.kda import KimiDeltaAttention
    real = KimiDeltaAttention.apply_paged

    def faulty(self, params, x, conv_state, state, lane_ids, valid, fresh,
               **kw):
        return real(self, params, x, conv_state, state, lane_ids, valid,
                    fresh | True, **kw)
    return plant(KimiDeltaAttention, "apply_paged", faulty)


def beta_without_its_factor():
    from deeplearning4j_tpu.nn.conf import kda
    import jax
    return plant(kda, "_beta", jax.nn.sigmoid)


def gqa_gate_dropped():
    """The GQA layer's heads reach ``Wo`` ungated."""
    from deeplearning4j_tpu.models import transformer
    layers = []

    def put(net):
        layers[:] = [net._vertex_layer(v)
                     for v in transformer.attention_vertices(net)]
        for layer in layers:
            layer.output_gate = False

        def unplant():
            for layer in layers:
                layer.output_gate = True
        net._unplant = unplant
    return put


@pytest.mark.parametrize("fault", [zero_state_at_every_dispatch,
                                   beta_without_its_factor,
                                   gqa_gate_dropped])
def test_planted_faults_come_out_false(fault):
    planted, put = [], fault()

    def remember(net):
        planted.append(net)
        put(net)
    try:
        line = drive(CELL, 13, plant=remember)
    finally:
        for net in planted:
            net._unplant()
    assert line["correct"] is False, line["compared"]
    assert failed(line) == ["served_token_logit_gap_max"]


def test_parent_has_no_such_program_and_says_so_at_once():
    """A checkout of the program without the family's model, with these
    benchmark files laid over it: the family's builder imports a module
    of the program that is not there, so the run ends with an error and no
    result line before any weight is made."""
    import subprocess
    import sys
    code = ("import sys\n"
            "sys.modules['deeplearning4j_tpu.models.solar_open2'] = None\n"
            "import run\n"
            "sys.exit(run.main(['--workload', %r, '--seed', '1', '--trace',"
            " '0', '--rehearse']))" % CELL)
    p = subprocess.run([sys.executable, "-c", code], cwd=common.BENCH,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "deeplearning4j_tpu.models.solar_open2" in p.stderr
