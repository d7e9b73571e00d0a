"""The ``evabyte`` family and its cell, ``serve-evabyte-longbytes``: the
configuration's arithmetic at the published widths, the file against the
catalog, the cell and its metrics entered by NAME (not by their place in
``per_layer``, which later PRs append to), the mix as ISSUE 39 states it, the
counts by hand, the cell rehearsed on the CPU with the EVA metrics read and
the fp8 control and a planted fault failing the rehearsal's limit, and the
parent's clean refusal.

At the rehearsal's sizes (float32, hidden 64, window 64) a sound run serves
the reference's own best token everywhere (its largest gap reads 0.0), so
the rehearsal's ``logit_gap`` of 0.001 lies under what the control and the
planted fault read.
"""

import os

import numpy as np
import pytest

from lib import common, traffic
from test_control import drive, failed

CELL = "serve-evabyte-longbytes"
CONFIG = "evabyte"
METRICS = ("eva_summary_row_share", "eva_read_kib_per_decoded_token",
           "eva_held_rows_per_context_token")
CATALOG = {      # the catalog's config, as the model-configs guide has it
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}


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


def test_published_widths_sum_to_what_the_issue_counted(cfg):
    family = common.load_family(cfg)
    shapes = family.leaf_shapes(cfg)
    sizes = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}
    layer = [sum(v for k, v in sizes.items() if k.startswith(f"l{i}."))
             for i in range(cfg["num_hidden_layers"])]
    # attention 67.1 M, FFN 135.3 M, norms and pooling vectors 16 k
    assert sizes["l0.W_q"] == sizes["l0.W_o"] == 4096 * 4096
    assert sizes["l0.W_gate"] == sizes["l0.W_down"] == 4096 * 11008
    assert shapes["l0.phi"] == ((32, 128), "matrix")
    assert [round(v / 1e6, 1) for v in layer] == [202.4] * 8
    assert sizes["embed"] == sizes["head_w"] == 320 * 4096
    assert round(sum(sizes.values()) / 1e9, 2) == 1.62
    assert round(sum(sizes.values()) * 2 / 2 ** 30, 2) == 3.02
    assert set(family.program_names(cfg)) == set(shapes)
    assert {kind for _, kind in shapes.values()} == {"matrix", "bias"}


def test_the_file_keeps_the_catalog_but_for_what_reduced_lists(cfg, bench):
    entry = by_name(bench["configs"], CONFIG)
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    reduced = {"num_hidden_layers": 8, "num_pred_heads": 1}
    assert set(entry["reduced"]) == set(reduced)
    for key, value in CATALOG.items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg[f"{key}_published"] == value
    for key in ("summary", "rotary", "head_dim", "ids", "weights",
                "pred_heads", "dtype", "cache", "engine"):
        assert cfg["assumed"][key]
    assert "pipeline" in cfg["deployment"] and "8 lanes" in cfg["deployment"]
    eng = cfg["engine"]
    assert (eng["max_batch"], eng["page_size"], eng["pages_per_seq"],
            eng["num_pages"], eng["block_len"], eng["prefill_chunk"],
            eng["prefix_cache"]) == (8, 16, 2048, 2048, 8, 128, False)
    # a page is a chunk, and a prefill chunk never crosses a window edge
    assert eng["page_size"] == cfg["chunk_size"]
    assert cfg["window_size"] % eng["prefill_chunk"] == 0
    # 8 lanes at the longest request fit the arena: 129 window pages and
    # the summary pages of 29,696 bytes (116) a lane
    longest = traffic.load("longbytes")["totals"]["longest_request_tokens"]
    lane = cfg["window_size"] // 16 + 1 + -(-(longest // 16) // 16)
    assert 8 * lane <= eng["num_pages"]
    assert cfg["reference_pad"] == cfg["window_size"]
    # the limit lies between its chip readings with room on both sides
    chip, limit = cfg["limits_from"]["chip"], cfg["limits"]["logit_gap"]
    assert len(chip["sound_gap_max"]) >= 12
    assert 3 * max(chip["sound_gap_max"]) <= limit \
        <= min(chip["control_gap_max"]) / 6


def test_the_cell_and_its_metrics_are_entered_by_name(bench):
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longbytes", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert {"serve_tokens_per_s", "decode_tokens_per_s", "serve_mfu",
            "compiles_in_window.serve", "hbm_peak_gib.serve",
            "device_idle_share.serve", "setup_warmup_s", *METRICS} <= listed
    for name in ("prefix_hit_token_share", "moe_held_pair_share",
                 "state_arena_gib", "paged_read_window_share"):
        assert name not in listed, name
    for name in METRICS:
        entry = by_name(bench["per_layer"], name)
        assert entry["workloads"] == [CELL]
        assert entry["layer"] == "EVA attention"
        assert entry["moves"] == "serve_tokens_per_s"
        own = common.load_json("metrics", f"{name}.json")
        assert own["name"] == name and own["unit"] == entry["unit"]


def test_the_mix_is_what_the_issue_states():
    mix = traffic.load("longbytes")
    assert mix["clients"] == 8 and mix["kind"] == "serve_closed_loop"
    rows = np.array([r for c in mix["schedule"] for r in c])
    assert [len(c) for c in mix["schedule"]] == [10] * 8
    assert rows[:, 0].min() >= 8192 and rows[:, 0].max() <= 28672
    assert rows[:, 1].min() >= 256 and rows[:, 1].max() <= 1024
    assert (rows[:, 2] == -1).all() and (rows[:, 3] == 0).all()
    assert (rows[:, 0] + rows[:, 1]).max() <= 29696 < 32768
    assert mix["totals"] == traffic.totals(mix)


def test_counts_by_hand(cfg):
    family = common.load_family(cfg)
    d, ff = 4096, 11008
    dense = 8 * (4 * d * d + 3 * d * ff)
    # one prefill chunk of 128 at the start (each position attends its own
    # window's rows so far) and one decode step at 20,000 (its window's 1,569
    # rows, 1,152 summaries), as the engine counts them
    attended = sum(p + 1 for p in range(128)) + (20000 - 18432 + 1) + 1152
    work = {"computed_tokens": 129.0, "attended_keys": 0.0,
            "deltas": [float(attended), 8.0 * 8, 1.0, 1.0]}
    want = (2 * dense * 129 + 8 * 4 * d * attended + 8 * 16 * d * 64
            + 2 * d * 320 * 2)
    assert family.serve_flops(cfg, work) == want
    assert family.serve_flops(cfg, dict(work, deltas=[None] * 4)) is None


@pytest.mark.parametrize("seed", [2147483659, 7])
def test_fp8_control_fails_the_rehearsal_limit(seed, monkeypatch):
    monkeypatch.setenv("BENCH_READINGS", "1")
    line = drive(CELL, seed, control_mode="fp8")
    assert line["correct"] is True, line["compared"]
    limit = line["compared"]["served_token_logit_gap_max"]["limit"]
    assert line["readings"]["gaps_max"] <= limit < \
        line["readings"]["control_gaps_max"]


def test_the_eva_metrics_read_in_a_traced_rehearsal():
    """Every per-layer metric that needs no device is in the line, the
    three EVA metrics among them, at what the rehearsal's schedule
    implies: windows of 64 and contexts of 128-464, so the summary rows
    are a fair share of the read and the lanes hold under their context."""
    line = drive(CELL, 11, trace=1)
    assert line["correct"] is True, line["compared"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 < m["eva_summary_row_share"] < 100.0
    assert m["eva_read_kib_per_decoded_token"] > 0.0
    assert 0.0 < m["eva_held_rows_per_context_token"] < 1.0
    assert m["compiles_in_window.serve"] == 0.0
    for name in ("serve_mfu", "decode_occupancy_share",
                 "prefill_dispatch_share", "kv_pages_peak_share",
                 "queue_wait_on_pages_share"):
        assert name in m, name


def plant_no_summaries(net):
    """The read leaves every summary row out (its summary trips at 0)."""
    from deeplearning4j_tpu.ops import paged_attention as paged
    real = paged.eva_read_trips

    def faulty(*args, **kw):
        first, end, _ = real(*args, **kw)
        return first, end, 0
    paged.eva_read_trips = faulty
    paged.eva_read_attention.clear_cache()

    def unplant():
        paged.eva_read_trips = real
        paged.eva_read_attention.clear_cache()
    net._unplant = unplant


def test_summaries_left_out_come_out_false():
    planted = []

    def remember(net):
        planted.append(net)
        plant_no_summaries(net)
    try:
        line = drive(CELL, 13, plant=remember)
    finally:
        for net in planted:
            net._unplant()
    assert line["correct"] is False, line["compared"]
    assert failed(line) == ["served_token_logit_gap_max"]


def test_parent_has_no_such_program_and_says_so_at_once():
    """What the driver sees on the parent commit, which gets this PR's
    benchmark files laid over it: the family's builder imports a module
    of the program that is not there, so the run ends with an error and no
    result line before any weight is made."""
    import subprocess
    import sys
    code = ("import sys\n"
            "sys.modules['deeplearning4j_tpu.models.evabyte'] = None\n"
            "import run\n"
            "sys.exit(run.main(['--workload', %r, '--seed', '1', '--trace',"
            " '0', '--rehearse']))" % CELL)
    p = subprocess.run([sys.executable, "-c", code], cwd=common.BENCH,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "deeplearning4j_tpu.models.evabyte" in p.stderr
