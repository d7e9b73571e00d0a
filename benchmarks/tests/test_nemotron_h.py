"""The ``nemotron_h`` family and its cell, ``serve-nemotron3-reason``: the
configuration's arithmetic at the published widths, the cell rehearsed on the
CPU under both rules of ``correct``, the fp8 control failing its limit, and
faults planted under the harness coming out false.

At the rehearsal's sizes (float32, hidden 64) a sound run serves the
reference's own best token everywhere: its largest gap reads 0.0 over a
dozen seeds on this CPU, and the fp8 control reads 0.035 to 0.053 (PR 33), so
the rehearsal's ``logit_gap`` of 0.001 lies between.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import common, traffic
from test_control import drive, failed

CELL = "serve-nemotron3-reason"
CONFIG = "nemotron-3-super-120b-a12b"


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
    by_kind = {"M": [], "E": [], "*": []}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        by_kind[kind].append(sum(v for k, v in sizes.items()
                                 if k.startswith(f"l{i}.")))
    # a Mamba layer 109.6 M, the attention layer 35.7 M, an expert layer
    # 54.5 M + 128 x 5.505 M, embedding and head 134.2 M each: 4.648 B
    assert {round(v / 1e6, 1) for v in by_kind["M"]} == {109.6}
    assert [round(v / 1e6, 1) for v in by_kind["*"]] == [35.7]
    assert {round(v / 1e6, 1) for v in by_kind["E"]} == {759.2}
    assert sizes["l1.w1"] == 128 * 1024 * 2688
    assert sizes["embed"] == sizes["head_w"] == 32768 * 4096
    assert round(sum(sizes.values()) / 1e9, 3) == 4.648
    assert round(sum(sizes.values()) * 2 / 2 ** 30, 2) == 8.66
    assert set(family.program_names(cfg)) == set(shapes)
    kinds = {kind for _, kind in shapes.values()}
    assert kinds == {"matrix", "bias", "gain"}          # lib/weights.py's
    assert shapes["l0.conv_w"] == ((10240, 4), "gain")
    assert shapes["l1.e_bias"] == ((512,), "bias")


def test_the_file_keeps_the_catalog_but_for_what_reduced_lists(cfg, bench):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    published = {"hidden_size": 4096, "mamba_num_heads": 128,
                 "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
                 "conv_kernel": 4, "chunk_size": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "num_experts_per_tok": 22,
                 "moe_latent_size": 1024, "moe_intermediate_size": 2688,
                 "moe_shared_expert_intermediate_size": 5376,
                 "routed_scaling_factor": 5, "norm_eps": 1e-05}
    assert {k: cfg[k] for k in published} == published       # no width cut
    assert cfg["n_routed_experts_published"] == 512
    assert cfg["hybrid_override_pattern"] in \
        cfg["hybrid_override_pattern_published"]             # a run of it
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]
    for key in ("rotary", "latent_moe", "mtp", "weights", "dtype", "state"):
        assert cfg["assumed"][key]
    assert "4 chips" in cfg["deployment"]
    lim = cfg["limits"]
    assert cfg["check_tokens"] >= 1000 and lim["logit_gap_outlier_share"]
    sound = cfg["limits_from"]["chip"]["sound_share"]
    control = cfg["limits_from"]["chip"]["control_share"]
    assert 2 * max(sound) <= lim["logit_gap_outlier_share"] <= min(control) / 2


def test_the_mix_is_what_the_issue_states():
    mix = traffic.load("reason")
    rows = np.array([r for c in mix["schedule"] for r in c])
    assert mix["clients"] == 32 and mix["stagger_s"] == 0.25
    assert {len(c) for c in mix["schedule"]} == {12}
    assert rows[:, 0].min() >= 64 and rows[:, 0].max() <= 1024
    assert rows[:, 1].min() >= 256 and rows[:, 1].max() <= 768
    assert (rows[:, 2] == -1).all() and (rows[:, 3] == 0).all()
    assert (rows[:, 0] + rows[:, 1]).max() <= 1792 <= 2048
    # log-uniform prompts: the median lies near the geometric mean, 256
    assert 200 <= np.median(rows[:, 0]) <= 330


def test_serve_flops_counts_held_pairs_and_nothing_of_padding(cfg):
    family = common.load_family(cfg)
    work = {"computed_tokens": 1000.0, "attended_keys": 5e5, "deltas": [5500.0]}
    base = family.serve_flops(cfg, work)
    more = family.serve_flops(cfg, dict(work, deltas=[6500.0]))
    assert more - base == 1000 * 2 * 2 * 1024 * 2688
    assert family.serve_flops(cfg, dict(work, deltas=[None])) is None
    # a token's dense products: 2 x (4.648 B - embedding - the routed
    # experts' 128 x 5.505 M a layer - gains, biases and taps)
    dense = family.dense_params(cfg)
    assert round(dense / 1e9, 3) == round(
        (5 * (4096 * 18560 + 8192 * 4096) + 4096 * 4608 + 4096 * 4096
         + 5 * 4096 * (512 + 2048 + 2 * 5376) + 4096 * 32768) / 1e9, 3)


@pytest.mark.parametrize("seed", [2147483659, 6])
def test_fp8_control_fails_the_rehearsal_limit(seed, monkeypatch):
    monkeypatch.setenv("BENCH_READINGS", "1")
    line = drive(CELL, seed, control_mode="fp8")
    assert line["correct"] is True, line["compared"]
    limit = line["compared"]["served_token_logit_gap_max"]["limit"]
    assert line["readings"]["gaps_max"] <= limit < \
        line["readings"]["control_gaps_max"]
    assert line["readings"]["control_outlier_share"] > 0.05


def test_stored_bf16_rehearses_under_the_share_rule(monkeypatch):
    """The cell's own policy and rule of ``correct`` at the rehearsal's
    sizes: parameters stored in bfloat16, a share of the served tokens
    allowed beyond the gap (a routed model's flips), at least
    ``check_tokens`` of them compared."""
    monkeypatch.setenv("BENCH_READINGS", "1")
    line = drive(CELL, 7, trace=1, rehearsal_sizes={
        "dtype": "stored_bf16", "param_dtype": "bfloat16",
        "limits": {"logit_gap": 0.05, "logit_gap_outlier_share": 0.05}})
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {
        "every_client_returned", "no_compile_in_window",
        "requests_ran_to_length", "served_token_gap_outlier_share",
        "tokens_compared"}
    for name in ("moe_held_pair_share", "moe_computed_over_routed",
                 "moe_expert_load_peak_over_mean", "state_arena_gib",
                 "serve_mfu", "compiles_in_window.serve"):
        assert name in line["metrics"], name
    assert "prefix_hit_token_share" not in line["metrics"]
    assert line["metrics"]["moe_computed_over_routed"]["value"] >= 1.0
    assert line["metrics"]["compiles_in_window.serve"]["value"] == 0.0


# -- planted faults -----------------------------------------------------------

def mixers(net, kind):
    return [net._vertex_layer(n) for n in net.topo_order
            if type(net._vertex_layer(n)).__name__ == kind]


def plant_expert_zeroed(net):
    """One held expert of the first expert layer computes nothing."""
    name = next(n for n in net.topo_order
                if type(net._vertex_layer(n)).__name__ == "LatentMoELayer")
    w2 = net.params[name]["w2"]
    net.params[name]["w2"] = w2.at[1].set(jnp.zeros_like(w2[1]))


def plant_tail_not_carried(net):
    """Every dispatch of the engine starts the convolution from an empty
    tail: the three rows before a chunk's (or a step's) first position are
    lost at each edge. The SSM state is carried as it should be."""
    for layer in mixers(net, "Mamba2Mixer"):
        def faulty(params, x, conv_state, *rest, _real=layer.apply_paged,
                   **kw):
            out, _, ssm = _real(params, x, jnp.zeros_like(conv_state),
                                *rest, **kw)
            return out, conv_state, ssm
        layer.apply_paged = faulty


def plant_bias_in_the_weights(net):
    """The selection bias, which only chooses, is added to the weights."""
    for layer in mixers(net, "LatentMoELayer"):
        def faulty(params, x, _layer=layer):
            s = jax.nn.sigmoid(jnp.matmul(
                x.astype(jnp.float32), params["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            biased = s + params["e_bias"].astype(jnp.float32)
            _, idx = jax.lax.top_k(biased, _layer.top_k)
            chosen = jnp.take_along_axis(biased, idx, axis=-1)
            w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) \
                * _layer.routed_scale
            return idx.astype(jnp.int32), w
        layer.route = faulty


@pytest.mark.parametrize("plant", [plant_expert_zeroed,
                                   plant_tail_not_carried,
                                   plant_bias_in_the_weights])
def test_planted_fault_comes_out_false(plant):
    line = drive(CELL, 13, plant=plant)
    assert line["correct"] is False, line["compared"]
    assert failed(line) == ["served_token_logit_gap_max"]


def test_parent_has_no_such_cell_and_says_so_at_once(tmp_path):
    """What the driver sees on a commit without this cell: exit code 2
    before any import of JAX, no result line."""
    import subprocess
    import sys
    bench = common.load_json("..", "BENCHMARK.json")
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != CELL]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    code = ("import sys, run; sys.exit(run.main(['--workload', %r, '--seed', "
            "'1', '--trace', '0'], env_extra={'benchmark': %r}))"
            % (CELL, str(path)))
    p = subprocess.run([sys.executable, "-c", code], cwd=common.BENCH,
                       capture_output=True, text=True, timeout=60,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "no cell" in p.stderr
