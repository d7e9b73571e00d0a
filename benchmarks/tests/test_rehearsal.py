"""The runner end to end at the rehearsal sizes (tiny model, interpreted
kernels, asked for by ``--rehearse``): the result line's keys, a schedule
that is the same for two seeds and token ids that are not, and the refusal
to measure anything without a TPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lib import common, traffic

RUN = os.path.join(common.BENCH, "run.py")
CELLS = [c["name"] for c in common.load_json("..", "BENCHMARK.json")
         ["workloads"]]


def run_cell(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_READINGS", None)
    return subprocess.run([sys.executable, RUN, *args], env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_last_line(cell, trace):
    p = run_cell("--workload", cell, "--seed", "2147483659", "--seconds",
                 "1", "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert list(line)[-1] == "compared"          # comes last in the line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    bench = common.load_json("..", "BENCHMARK.json")
    kind = "per_layer" if trace == "1" else "end_to_end"
    named = {m["name"]: m for m in bench[kind]
             if cell in m.get("workloads", [cell])}
    assert line["metrics"], "a cell reports something"
    for name, m in line["metrics"].items():
        assert name in named and m["unit"] == named[name]["unit"]
        assert isinstance(m["value"], float)
    if trace == "0":
        assert set(line["metrics"]) == set(named)
    # each number compared stands beside its limit, on stderr too
    for name, pair in line["compared"].items():
        assert set(pair) == {"value", "limit"}
        assert f"compared {name}:" in p.stderr
    # no configuration of BENCHMARK.json states a share of outliers: a
    # serving cell compares what PR 28's did, by the same names and limits
    entry = next(c for c in bench["configs"] if c["name"] == next(
        w["config"] for w in bench["workloads"] if w["name"] == cell))
    with open(os.path.join(common.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    if cfg["runner"] == "serve":
        assert {k: v["limit"] for k, v in line["compared"].items()} == {
            "every_client_returned": 0.0, "no_compile_in_window": 0.0,
            "requests_ran_to_length": 0.0, "served_token_logit_gap_max":
            cfg["rehearsal"]["limits"]["logit_gap"]}
    assert p.stderr.strip().splitlines()[-1] == "correct: True"


def test_no_tpu_no_result():
    p = run_cell("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing was measured" in p.stderr


def test_unknown_cell():
    p = run_cell("--workload", "no-such-cell", "--seed", "1", "--seconds",
                 "1", "--trace", "0", "--rehearse")
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("name", sorted(
    {c["traffic"] for c in common.load_json("..", "BENCHMARK.json")
     ["workloads"]}))
def test_schedule_is_the_file_and_ids_are_the_seed(name):
    mix = traffic.load(name)
    for key in ("why", "who", "distribution", "totals"):
        assert mix[key], f"{name}.json states its {key}"
    a, b = 2147483659, 5
    if mix["kind"] == "train":
        xa, ya = traffic.train_batch(mix, 50272, a, 0)
        xb, _ = traffic.train_batch(mix, 50272, b, 0)
        x1, _ = traffic.train_batch(mix, 50272, a, 1)
        assert xa.shape == xb.shape == (mix["batch"], mix["seq_len"])
        assert not np.array_equal(xa, xb) and not np.array_equal(xa, x1)
        assert np.array_equal(xa[:, 1:], ya[:, :-1])
        assert np.array_equal(xa, traffic.train_batch(mix, 50272, a, 0)[0])
        return
    assert mix["generator_constant"]
    assert traffic.totals(mix) == mix["totals"]
    assert len(mix["schedule"]) == mix["clients"]
    for c in range(mix["clients"]):
        gen = traffic.requests(mix, c)       # takes no seed at all
        for _ in range(len(mix["schedule"][c]) + 2):
            serial, row = next(gen)
            ia = traffic.prompt_ids(mix, 50272, a, c, serial, row)
            ib = traffic.prompt_ids(mix, 50272, b, c, serial, row)
            assert len(ia) == len(ib) == row[0]
            assert not np.array_equal(ia, ib)
            assert np.array_equal(
                ia, traffic.prompt_ids(mix, 50272, a, c, serial, row))


def test_shared_documents_share_their_prefix():
    mix = traffic.load("docqa")
    rows = mix["schedule"][0]
    first, second = rows[0], rows[1]
    assert first[2] == second[2] >= 0
    p1 = traffic.prompt_ids(mix, 50272, 9, 0, 0, first)
    p2 = traffic.prompt_ids(mix, 50272, 9, 0, 1, second)
    n = first[3]
    assert np.array_equal(p1[:n], p2[:n])
    assert not np.array_equal(p1[n:n + 16], p2[n:n + 16])
    other = traffic.prompt_ids(mix, 50272, 9, 1, 0, mix["schedule"][1][0])
    assert not np.array_equal(p1[:64], other[:64])
