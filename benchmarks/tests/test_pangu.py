"""The ``pangu_ultra_moe`` family and its cell, ``serve-pangu-longdoc``: the
configuration's arithmetic at the published widths, the file against the
catalog, the mix as the issue states it, the counts by hand, the cell
rehearsed on the CPU with the fp8 control failing its limit and faults
planted under the harness coming out false, and the new reader against the
recorded trace.

At the rehearsal's sizes (float32, hidden 64) a sound run serves the
reference's own best token everywhere (its largest gap reads 0.0), so the
rehearsal's ``logit_gap`` of 0.001 lies under what the control and each
planted fault read.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from lib import common, traffic, xplane
from test_control import drive, failed

CELL = "serve-pangu-longdoc"
CONFIG = "openpangu-ultra-moe-718b"
CATALOG = {      # the catalog's config, as the model-configs guide has it
    "attention_bias": False, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7680, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}


@pytest.fixture(scope="module")
def cfg():
    return common.load_json("configs", f"{CONFIG}.json")


@pytest.fixture(scope="module")
def bench():
    return common.load_json("..", "BENCHMARK.json")


def test_published_widths_sum_to_what_the_issue_counted(cfg):
    family = common.load_family(cfg)
    shapes = family.leaf_shapes(cfg)
    sizes = {k: int(np.prod(s)) for k, (s, _) in shapes.items()}
    layer = [sum(v for k, v in sizes.items() if k.startswith(f"l{i}."))
             for i in range(cfg["num_hidden_layers"])]
    # MLA 196.58 M; a dense layer 621.25 M with its gated FFN; an expert
    # layer 245.73 M outside its 16 experts of 47.19 M: 1,000.70 M
    assert round(family.attention_params(cfg) / 1e6, 2) == 196.58
    assert [round(v / 1e6, 1) for v in layer] == [621.3] + [1000.7] * 4
    assert sizes["l1.wg"] == sizes["l1.wu"] == sizes["l1.wd"] \
        == 16 * 7680 * 2048
    assert sizes["embed"] == sizes["head_w"] == 19200 * 7680
    assert round(sum(sizes.values()) / 1e9, 3) == 4.919
    assert round(sum(sizes.values()) * 2 / 2 ** 30, 2) == 9.16
    assert set(family.program_names(cfg)) == set(shapes)
    assert {kind for _, kind in shapes.values()} == {"matrix", "bias", "gain"}
    assert shapes["l1.e_bias"] == ((256,), "bias")
    assert shapes["l0.W_kva"] == ((7680, 576), "matrix")
    assert shapes["l0.W_qb"] == ((1536, 128 * 192), "matrix")


def test_the_file_keeps_the_catalog_but_for_what_reduced_lists(cfg, bench):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    reduced = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
               "n_routed_experts": 16, "vocab_size": 19200,
               "num_nextn_predict_layers": 0}
    assert set(entry["reduced"]) == set(reduced)
    for key, value in CATALOG.items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg[f"{key}_published"] == value
    # the guide's floors: four layers after the leading dense one, at
    # least 8 experts a layer, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["vocab_size_published"]
    for key in ("rotary", "router", "norms", "mtp", "weights", "dtype",
                "cache", "engine"):
        assert cfg["assumed"][key]
    assert "16 chips" in cfg["deployment"] and "2 lanes" in cfg["deployment"]
    eng = cfg["engine"]
    assert (eng["max_batch"], eng["page_size"], eng["pages_per_seq"],
            eng["block_len"], eng["prefill_chunk"], eng["prefix_cache"]) \
        == (32, 16, 512, 8, 128, True)
    assert eng["num_pages"] % 1024 == 0
    lim = cfg["limits"]
    assert cfg["check_tokens"] >= 1000 and lim["logit_gap_outlier_share"]
    chip = cfg["limits_from"]["chip"]
    assert len(chip["seeds"]) >= 12
    assert 2 * max(chip["sound_share"]) <= lim["logit_gap_outlier_share"] \
        <= min(chip["control_share"]) / 2


def test_the_cell_and_its_metrics_are_entered_as_the_issue_states(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc", 1)
    assert "2 a chip" in cell["why"] and len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert {"serve_tokens_per_s", "decode_tokens_per_s", "serve_mfu",
            "prefix_hit_token_share", "paged_read_window_share",
            "moe_held_pair_share", "moe_computed_over_routed",
            "moe_gated_ffn_roofline",
            "kv_kib_per_cached_token", "queue_wait_on_pages_share",
            "compiles_in_window.serve", "hbm_peak_gib.serve"} <= listed
    # its reader sets a window's rate against a slice's (PERF.md section 7)
    assert "moe_grouped_ffn_roofline" not in listed
    # its file's scale is the hybrid's 128 held experts: 8 times the ratio
    # at 16 held (PERF.md section 7)
    assert "moe_expert_load_peak_over_mean" not in listed
    assert "state_arena_gib" not in listed
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "moe_gated_ffn_roofline", "kv_kib_per_cached_token"]


def test_the_mix_is_what_the_issue_states():
    mix = traffic.load("longdoc")
    assert mix["clients"] == 32 and mix["stagger_s"] == 0.25
    assert mix["kind"] == "serve_closed_loop"
    documents = set()
    for c, rows in enumerate(mix["schedule"]):
        rows = np.array(rows)
        # 4 documents of its own, 16 turns each but for the first, which
        # is entered at turn c mod 16
        docs, turns = np.unique(rows[:, 2], return_counts=True)
        assert docs.tolist() == [4 * c + d for d in range(4)]
        assert turns.tolist() == [16 - c % 16, 16, 16, 16]
        assert (np.diff(rows[:, 2]) >= 0).all()         # in a row
        documents |= set(docs.tolist())
        for d in docs:
            own = rows[rows[:, 2] == d]
            assert len(set(own[:, 3])) == 1             # one document
            assert 2048 <= own[0, 3] <= 6144
        question = rows[:, 0] - rows[:, 3]
        assert question.min() >= 16 and question.max() <= 64
        assert rows[:, 1].min() >= 128 and rows[:, 1].max() <= 384
    assert len(documents) == 128                        # none shared
    assert mix["totals"]["longest_request_tokens"] <= 6592 <= 8192
    # 15 of 16 prompts find their document: about 93 % of prompt tokens
    assert 0.90 <= mix["totals"]["shared_prompt_token_share"] <= 0.95


def test_counts_by_hand(cfg):
    family = common.load_family(cfg)
    # dense products a token: 5 x 196.58 M of attention, one gated FFN of
    # 3 x 7680 x 18432, four routers and shared experts of 49.15 M
    dense = family.dense_params(cfg)
    assert dense == 5 * family.attention_params(cfg) \
        + 3 * 7680 * 18432 + 4 * 7680 * (256 + 3 * 2048)
    assert family.pair_flops(cfg) == 3 * 2 * 7680 * 2048         # 94.4 M
    assert round(family.pair_flops(cfg) / 1e6, 1) == 94.4
    # textbook attention: 128 heads x 2 x (192 + 128) a key and layer
    assert family.attention_flops(cfg) == 5 * 128 * 2 * 320
    work = {"computed_tokens": 1000.0, "attended_keys": 5e5,
            "deltas": [700.0, 900.0, 11.0]}
    base = family.serve_flops(cfg, work)
    assert base == (2 * dense * 1000 + family.pair_flops(cfg) * 700
                    + family.attention_flops(cfg) * 5e5
                    + 2 * 7680 * 19200 * 911)        # the head: 900 + 11 rows
    more = family.serve_flops(cfg, dict(work, deltas=[800.0, 900.0, 11.0]))
    assert more - base == 100 * family.pair_flops(cfg)
    assert family.serve_flops(cfg, dict(work, deltas=[None] * 3)) is None
    # the grouped product: 10 pairs over 7 touched experts in bf16
    ops, nbytes = family.grouped_ffn_work(cfg, 10.0, 7.0)
    assert ops == 10 * 94371840
    assert nbytes == 7 * 3 * 7680 * 2048 * 2 + 10 * 7680 * (2 + 4)


def test_gated_roofline_reader_on_the_recorded_trace(cfg):
    """``data/flash3.xplane.pb`` holds three calls of the flash forward,
    20,631,016 + 20,632,044 + 20,630,506 ns less the backward kernels'; the
    reader is pointed at them as if they were the grouped product's. A
    window of 30 calls routed 3,000 pairs over 1,500 touched experts: a
    mean call needs a tenth of that, the slice holds 3 calls."""
    reader = common.find_module((common.BENCH,), "readers",
                                "gated_ffn_roofline")
    reduced = xplane.reduce(xplane.load(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data",
        "flash3.xplane.pb")))
    pattern = r"^jvp.*:tpu_custom_call$"
    took = xplane.seconds_matching(reduced, pattern)
    assert xplane.calls_matching(reduced, pattern) == 3 and took > 0
    family = common.load_family(cfg)
    spec = {"kind": "gated_ffn_roofline", "pattern": pattern,
            "pairs": {"metric": "p"}, "touched": {"metric": "t"},
            "calls": {"metric": "c"}}
    assert [w["metric"] for w in reader.wants(spec)] == ["p", "t", "c"]

    class Edges:
        values = {"p": 3000.0, "t": 1500.0, "c": 30.0}

        def delta(self, want):
            return self.values[want["metric"]]

    run = {"trace": reduced, "family": family, "cfg": cfg, "edges": Edges(),
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    ops, nbytes = family.grouped_ffn_work(cfg, 3000.0, 1500.0)
    need = max(ops / 197e12, nbytes / 819e9)
    assert need == nbytes / 819e9                       # memory-bound
    assert reader.read(spec, run) == pytest.approx(
        100.0 * (need / 30.0) * 3 / took, rel=1e-12)
    # silent, never 0: no kernel in the slice, no counter in the program
    assert reader.read(dict(spec, pattern="^moe_gated_ffn"), run) is None
    Edges.values = dict(Edges.values, c=None)
    assert reader.read(spec, run) is None
    assert reader.read(spec, dict(run, trace=None)) is None


@pytest.mark.parametrize("seed", [2147483659, 6])
def test_fp8_control_fails_the_rehearsal_limit(seed, monkeypatch):
    monkeypatch.setenv("BENCH_READINGS", "1")
    line = drive(CELL, seed, control_mode="fp8")
    assert line["correct"] is True, line["compared"]
    limit = line["compared"]["served_token_logit_gap_max"]["limit"]
    assert line["readings"]["gaps_max"] <= limit < \
        line["readings"]["control_gaps_max"]


def test_stored_bf16_rehearses_under_the_share_rule(monkeypatch):
    """The cell's own policy and rule of ``correct`` at the rehearsal's
    sizes, traced: every per-layer metric that needs no device is there,
    a hit and a miss are among the compared requests."""
    monkeypatch.setenv("BENCH_READINGS", "1")
    line = drive(CELL, 7, trace=1, rehearsal_sizes={
        "dtype": "stored_bf16", "param_dtype": "bfloat16",
        "check_tokens": 30,
        "limits": {"logit_gap": 0.05, "logit_gap_outlier_share": 0.1}})
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {
        "every_client_returned", "no_compile_in_window",
        "requests_ran_to_length", "served_token_gap_outlier_share",
        "tokens_compared"}
    for name in ("moe_held_pair_share", "moe_computed_over_routed",
                 "kv_kib_per_cached_token",
                 "prefix_hit_token_share", "paged_read_window_share",
                 "queue_wait_on_pages_share", "serve_mfu",
                 "compiles_in_window.serve"):
        assert name in line["metrics"], name
    assert "moe_gated_ffn_roofline" not in line["metrics"]   # no device
    assert line["metrics"]["prefix_hit_token_share"]["value"] >= 80.0
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0.0
    # 3 layers x 128 columns (a row of 40 rounded up to a lane tile) x 4 B
    assert line["metrics"]["kv_kib_per_cached_token"]["value"] == 1.5


# -- planted faults -----------------------------------------------------------

# A dropped rotary term is planted where logits are compared
# (tests/test_pangu.py, chunk-relative positions): at the rehearsal's widths
# a score is a few hundredths, the softmax is nearly even whatever turns the
# keys, and no served token leaves the reference's best for it.

def plant_value_from_the_wrong_columns(net):
    """The read takes its value from the row's columns 8.., not 0..: the
    latent's order is lost (the up-projection sees shifted columns)."""
    from deeplearning4j_tpu.ops import paged_attention as paged
    real = paged.paged_read_attention

    def faulty(q, k_pool, v_pool, table, rel, scale, group=1, v_width=None):
        if v_width is None:
            return real(q, k_pool, v_pool, table, rel, scale, group=group)
        out = real(q, k_pool, v_pool, table, rel, scale, group=group,
                   v_width=v_width + 8)
        return out[..., 8:]
    paged.paged_read_attention = faulty
    net._unplant = lambda: setattr(paged, "paged_read_attention", real)


def plant_expert_zeroed(net):
    """One held expert (the second) of each expert layer computes
    nothing."""
    for name in net.topo_order:
        if type(net._vertex_layer(name)).__name__ == "GatedMoELayer":
            wd = net.params[name]["wd"]
            net.params[name]["wd"] = wd.at[1].set(jnp.zeros_like(wd[1]))


def plant_hit_taken_for_padding(net):
    """A re-fed position whose write is dropped is skipped by the expert
    layers, as the walker's one mask had it."""
    from deeplearning4j_tpu.models import transformer
    real = transformer.paged_decode_forward

    def faulty(*args, fed=None, **kw):
        return real(*args, fed=None, **kw)
    transformer.paged_decode_forward = faulty
    net._unplant = lambda: setattr(transformer, "paged_decode_forward", real)


def drive_planted(plant, **env_extra):
    planted = []

    def remember(net):
        planted.append(net)
        plant(net)
    try:
        return drive(CELL, 13, plant=remember, **env_extra)
    finally:
        for net in planted:
            getattr(net, "_unplant", lambda: None)()


@pytest.mark.parametrize("plant", [plant_value_from_the_wrong_columns,
                                   plant_expert_zeroed])
def test_planted_fault_comes_out_false(plant):
    line = drive_planted(plant)
    assert line["correct"] is False, line["compared"]
    assert failed(line) == ["served_token_logit_gap_max"]


def test_a_hit_taken_for_padding_comes_out_false(tmp_path, bench):
    """The cell's turns end in fresh questions, so none of its prompts is
    covered whole; a mix that sends a prompt of whole pages again is: the
    hit re-feeds its last token with the write dropped. Sound, the cell is
    correct on that mix; with the walker's one mask it is not."""
    mix = {"kind": "serve_closed_loop", "clients": 2, "stagger_s": 0.0,
           "why": "test", "who": "nobody", "distribution": "test",
           "schedule": [[[24, 4, 0, 24]] * 6, [[32, 5, 1, 32]] * 6]}
    os.makedirs(tmp_path / "traffic")
    (tmp_path / "traffic" / "resend.json").write_text(json.dumps(mix))
    cells = json.loads(json.dumps(bench))
    next(w for w in cells["workloads"]
         if w["name"] == CELL)["traffic"] = "resend"
    (tmp_path / "cells.json").write_text(json.dumps(cells))
    own = {"benchmark": str(tmp_path / "cells.json"),
           "dirs": [str(tmp_path)]}
    sound = drive(CELL, 13, trace=1, **own)
    assert sound["correct"] is True, sound["compared"]
    assert sound["metrics"]["prefix_hit_token_share"]["value"] > 50.0
    line = drive_planted(plant_hit_taken_for_padding, **own)
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
            "sys.modules['deeplearning4j_tpu.models.pangu'] = None\n"
            "import run\n"
            "sys.exit(run.main(['--workload', %r, '--seed', '1', '--trace',"
            " '0', '--rehearse']))" % CELL)
    p = subprocess.run([sys.executable, "-c", code], cwd=common.BENCH,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "deeplearning4j_tpu.models.pangu" in p.stderr
